"""Median `prefill.sync` of the prefill chunks (a `decode.prefill` with
an `offset`) that lie inside the whole window, from the default span
store: the chunk's device time as the host waits for it, in every run,
whatever chunks the profiler's few seconds caught."""
from benchmarks.metrics import _ticks

CHUNK, SYNC = "decode.prefill", "prefill.sync"


def syncs(store, lo, hi):
    """Seconds of each chunk's sync, the chunk inside [lo, hi]."""
    spans = _ticks._spans(store)
    chunks = {s.context.span_id for s in spans if s.name == CHUNK
              and "offset" in s.args and lo <= s.t0 and s.t1 <= hi}
    return [s.duration() for s in spans
            if s.name == SYNC and s.context.parent_span_id in chunks]


def read(ctx):
    return _ticks.percentile_ms(syncs(
        _ticks.default_store(), *_ticks.interval(ctx["probe"], head=False)),
        50)
