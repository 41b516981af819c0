"""Shared by the readers of the decode tick's timeline.

`DecodeEngine` leaves one `decode.tick` span for every tick that ran a
step, with six leaves that cover it in order (`LEAVES`), and for every
request a `decode.queue` span (arrival to the start of its prefill), all
in the process default tracer's store (`bigdl_tpu.observability.tracing.
get_tracer()`), stamped on `context.trace_now`.  The harness's window is
on `time.perf_counter`; `clock_offset` carries its ends over.

A program that leaves no such spans (the parent of the PR that brought
them) gives every reader here nothing to read: they return None and the
result line leaves the metric out.  A store that dropped spans is an
error: a median over the tail of a run is not the run's.
"""
import statistics
import time

from benchmarks import harness, loadgen

TICK = "decode.tick"
QUEUE = "decode.queue"
LEAVES = ("decode.admit", "decode.schedule", "decode.stage",
          "decode.dispatch", "decode.sync", "decode.emit")
# The readers of a traced run take the window's head only, the part
# before the profiler starts.  Once it has started, and for as long as
# it takes to stop, every host phase of a tick is slower: over the whole
# traced window the median tick read 22.4 ms against 18.6 ms in an
# untraced run of the same cell and 17.8 ms over the head (my chip runs,
# PR 25; all three readings of each metric are in PERF.md section 6).
HEAD_ONLY = True


def clock_offset():
    """trace_now - perf_counter, both read at this moment."""
    from bigdl_tpu.observability.context import trace_now
    a = time.perf_counter()
    t = trace_now()
    b = time.perf_counter()
    return t - (a + b) / 2.0


def interval(probe, head=None):
    """(lo, hi) on the trace clock: the whole window, or its head, up to
    the instant the profiler had started."""
    head = HEAD_ONLY if head is None else head
    off = clock_offset()
    hi = probe.traced[0] if head and probe.traced else probe.t_close
    return probe.t_open + off, hi + off


def _spans(store):
    if store.dropped:
        raise harness.BenchmarkError(
            f"the span store dropped {store.dropped} spans: it does not "
            "hold the whole run (tracing.DEFAULT_CAPACITY)")
    return store.spans()


def ticks(store, lo, hi):
    """[{name: seconds}] for every tick that lies inside [lo, hi]: its
    own duration under `TICK`, each leaf's under the leaf's name."""
    spans = _spans(store)
    rows = {s.context.span_id: {TICK: s.duration()} for s in spans
            if s.name == TICK and s.t0 >= lo and s.t1 <= hi}
    for s in spans:
        row = rows.get(s.context.parent_span_id)
        if row is not None and s.name in LEAVES:
            row[s.name] = row.get(s.name, 0.0) + s.duration()
    return list(rows.values())


def longest(store, lo, hi):
    """Where a stall would show (PERF.md section 7): the longest tick of
    the interval with its leaves, and the longest stretch between two
    consecutive ticks (the loop parked with nothing live, ran ticks
    without a step, or was not scheduled)."""
    spans = _spans(store)
    found = sorted((s for s in spans if s.name == TICK
                    and s.t0 >= lo and s.t1 <= hi), key=lambda s: s.t0)
    if not found:
        return {}
    worst = max(found, key=lambda s: s.duration())
    between, after = max(((b.t0 - a.t1, a) for a, b in
                          zip(found, found[1:])),
                         key=lambda g: g[0], default=(0.0, found[0]))
    return {"longest_tick_ms": 1e3 * worst.duration(),
            "longest_tick_step": worst.args["step"],
            "longest_tick_at_s": worst.t0 - lo,
            "longest_tick_leaves_ms": {
                s.name: 1e3 * s.duration() for s in spans
                if s.context.parent_span_id == worst.context.span_id},
            "longest_gap_between_ticks_ms": 1e3 * between,
            "longest_gap_after_step": after.args["step"]}


def queue_waits(store, lo, hi):
    """Seconds each request admitted inside [lo, hi] waited for a slot."""
    return [s.duration() for s in _spans(store)
            if s.name == QUEUE and lo <= s.t1 <= hi]


def percentile_ms(seconds, q):
    if not seconds:
        return None
    return 1e3 * loadgen.percentile(seconds, q)


def leaf(name):
    return lambda row: row.get(name, 0.0)


# metric -> (what is taken of each tick, the percentile over the ticks)
TICK_METRICS = {
    "decode_tick.ms_p50": (leaf(TICK), 50),
    "decode_tick.host_ms_p50": (
        lambda r: r[TICK] - r.get("decode.sync", 0.0), 50),
    "decode_tick.emit_ms_p50": (leaf("decode.emit"), 50),
    "decode_tick.launch_ms_p50": (
        lambda r: sum(r.get(n, 0.0) for n in LEAVES[1:4]), 50),
    "decode_tick.admit_ms_p95": (leaf("decode.admit"), 95),
    # not metrics of their own: the sync for `sync_over_device`, and the
    # tail tick for tools/ticks.py (is the p95 token a tick that admits?)
    "decode_sync.ms_p50": (leaf("decode.sync"), 50),
    "decode_tick.ms_p95": (leaf(TICK), 95),
}


def reduce(rows, metric):
    of, q = TICK_METRICS[metric]
    return percentile_ms([of(r) for r in rows], q)


def default_store():
    from bigdl_tpu.observability import tracing
    return tracing.get_tracer().store


def read(ctx, metric):
    """One tick metric over the ticks of the run's window, in ms."""
    return reduce(ticks(default_store(), *interval(ctx["probe"])), metric)


def read_queue_wait(ctx):
    return percentile_ms(
        queue_waits(default_store(), *interval(ctx["probe"])), 95)


def summary(store, lo, hi):
    """Every tick number of one interval, for tools/ticks.py: the
    metrics, each leaf's median, and how much of a tick the leaves
    cover."""
    rows = ticks(store, lo, hi)
    if not rows:
        return {"ticks": 0}
    covered = [sum(r.get(n, 0.0) for n in LEAVES) for r in rows]
    out = {"ticks": len(rows)}
    out.update((m, reduce(rows, m)) for m in TICK_METRICS)
    out["serve_queue_wait_ms_p95"] = percentile_ms(
        queue_waits(store, lo, hi), 95)
    out["leaf_ms_p50"] = {n: percentile_ms([r.get(n, 0.0) for r in rows], 50)
                          for n in LEAVES}
    out["leaves_cover_median_tick"] = statistics.median(covered) \
        / statistics.median(r[TICK] for r in rows)
    out["leaves_cover_all_ticks"] = sum(covered) / sum(r[TICK] for r in rows)
    out.update(longest(store, lo, hi))
    return out
