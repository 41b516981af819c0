"""The readers of the decode tick's timeline over a synthetic span store
(known phases in, known percentiles out), the gap attribution with the
leaves on the profiler's timeline, and the seven readers over a toy run
of the serving cell."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import harness, xplane
from benchmarks.metrics import _ticks
from _bench_common import ROOT, SCALE

from bigdl_tpu.observability.context import TraceContext, trace_now
from bigdl_tpu.observability.tracing import Span, SpanStore, Tracer, set_tracer

MS = 1e-3
# a tick's leaves in ms, in order: admit, schedule, stage, dispatch, sync,
# emit; the tick itself is their sum and 0.1 ms that no leaf covers
PLAIN = (0.0, 0.2, 0.3, 0.5, 15.0, 2.0)
ADMITS = (9.0, 0.2, 0.3, 0.5, 15.0, 2.0)
SERVE = "olmo1b-serve-chat"
NEW = ["decode_tick.ms_p50", "decode_tick.host_ms_p50",
       "decode_tick.emit_ms_p50", "decode_tick.launch_ms_p50",
       "decode_tick.sync_over_device_ms_p50", "decode_tick.admit_ms_p95",
       "serve_queue_wait_ms_p95"]


class FakeProbe:
    """A window on perf_counter, as the harness's Probe keeps it."""

    def __init__(self, t_open, t_close, traced=None):
        self.t_open, self.t_close, self.traced = t_open, t_close, traced


def add_tick(store, n, t0, leaves):
    """One tick at trace-clock time t0 -> the time it ends."""
    tick_id = f"{n:015x}0"
    t = t0
    for i, (name, ms) in enumerate(zip(_ticks.LEAVES, leaves)):
        ctx = TraceContext("0" * 32, f"{n:015x}{i + 1:x}", tick_id)
        store.add(Span(name, ctx, t, t + ms * MS))
        t += ms * MS
    t += 0.1 * MS
    store.add(Span(_ticks.TICK, TraceContext("0" * 32, tick_id), t0, t,
                   args={"step": n}))
    return t


def fill(store, t0, n=40, admit_every=10):
    """n ticks back to back from t0, every tenth with an admission whose
    request waited (tick number) ms; returns the end of the last."""
    t = t0
    for i in range(n):
        admits = i % admit_every == 0
        t1 = add_tick(store, i, t, ADMITS if admits else PLAIN)
        if admits:
            ctx = TraceContext(f"{i + 1:032x}", f"{i:08x}ffffffff")
            store.add(Span(_ticks.QUEUE, ctx, t - i * MS, t))
        t = t1
    return t


@pytest.fixture
def window():
    """(ctx, store): a store filled from a window's opening, installed as
    the process default, and the reader's context over that window."""
    tracer = Tracer(capacity=4096)
    prev = set_tracer(tracer)
    off = _ticks.clock_offset()
    t_open = time.perf_counter()
    t_end = fill(tracer.store, t_open + off + MS)
    probe = FakeProbe(t_open, t_end - off + MS)
    ctx = {"probe": probe, "trace": {"modules": {
        "jit_fn(1)": [0.0137] * 9, "jit_fn(2)": [0.0094] * 2,
        "jit_other(3)": [1.0] * 20}}}
    yield ctx, tracer.store
    set_tracer(prev)


# 36 plain ticks of 18.1 ms, 4 admitting ones of 27.1 ms; the requests
# waited 0, 10, 20 and 30 ms
EXPECTED = {
    "decode_tick.ms_p50": 18.1,
    "decode_tick.host_ms_p50": 3.1,
    "decode_tick.emit_ms_p50": 2.0,
    "decode_tick.launch_ms_p50": 1.0,
    "decode_tick.sync_over_device_ms_p50": 15.0 - 13.7,
    # 4 of 40 ticks admit: position 37.05 of the sorted 0..39 lies
    # among them
    "decode_tick.admit_ms_p95": 9.0,
    "serve_queue_wait_ms_p95": 28.5,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_over_a_synthetic_store(window, metric):
    ctx, _ = window
    cell = harness.Cell(SERVE)
    entry = {m["name"]: m for m in cell.per_layer}[metric]
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    assert cell.reader(metric).read(ctx) == \
        pytest.approx(EXPECTED[metric], abs=1e-6)


def test_a_tick_outside_the_window_is_left_out(window):
    ctx, store = window
    lo, hi = _ticks.interval(ctx["probe"])
    assert len(_ticks.ticks(store, lo, hi)) == 40
    # one that began before the window opened, one that ends after it
    # closed, and the queue span of a request admitted after it
    add_tick(store, 100, lo - 5 * MS, (0.0, 0.0, 0.0, 0.0, 90.0, 0.0))
    add_tick(store, 101, hi - 5 * MS, (0.0, 0.0, 0.0, 0.0, 90.0, 0.0))
    store.add(Span(_ticks.QUEUE, TraceContext("9" * 32, "9" * 16),
                   hi, hi + 1.0))
    assert len(_ticks.ticks(store, lo, hi)) == 40
    cell = harness.Cell(SERVE)
    assert cell.reader("decode_tick.ms_p50").read(ctx) == \
        pytest.approx(18.1, abs=1e-6)
    assert cell.reader("serve_queue_wait_ms_p95").read(ctx) == \
        pytest.approx(28.5, abs=1e-6)


def test_the_clock_offset_is_applied(window, monkeypatch):
    ctx, store = window
    assert abs(_ticks.clock_offset()
               - (trace_now() - time.perf_counter())) < 1e-3
    lo, hi = _ticks.interval(ctx["probe"])
    # the same window on a perf_counter that runs an hour behind the
    # trace clock: the readers still find its ticks
    monkeypatch.setattr(_ticks, "clock_offset", lambda: 3600.0)
    shifted = FakeProbe(lo - 3600.0, hi - 3600.0)
    assert _ticks.interval(shifted) == pytest.approx((lo, hi))
    assert harness.Cell(SERVE).reader("decode_tick.ms_p50").read(
        {"probe": shifted}) == pytest.approx(18.1, abs=1e-6)
    monkeypatch.setattr(_ticks, "clock_offset", lambda: 0.0)
    assert _ticks.ticks(store, *_ticks.interval(shifted)) == []


def test_the_head_ends_where_the_profiler_started(window):
    ctx, store = window
    p = ctx["probe"]
    # the profiler had started after the tenth tick: one admitting tick
    # and nine plain ones lie before it
    started = p.t_open + MS + (27.1 + 9 * 18.1 + 1.0) * MS
    traced = FakeProbe(p.t_open, p.t_close, (started, started + 3.0))
    head = _ticks.summary(store, *_ticks.interval(traced, head=True))
    whole = _ticks.summary(store, *_ticks.interval(traced, head=False))
    assert (head["ticks"], whole["ticks"]) == (10, 40)
    assert whole["decode_tick.ms_p95"] == pytest.approx(27.1)
    # where a stall would show: the longest tick is the first admitting
    # one, with its leaves; then a tick of 90 ms that the loop was kept
    # from for 51 ms after the last one
    assert (whole["longest_tick_ms"], whole["longest_tick_step"]) == \
        (pytest.approx(27.1), 0)
    assert whole["longest_tick_leaves_ms"]["decode.admit"] == \
        pytest.approx(9.0)
    assert whole["longest_gap_between_ticks_ms"] == pytest.approx(0.0,
                                                                  abs=1e-6)
    lo, hi = _ticks.interval(traced, head=False)
    add_tick(store, 40, hi + 50 * MS, (0.0, 0.0, 0.0, 0.0, 90.0, 0.0))
    stalled = _ticks.longest(store, lo, hi + 1.0)
    assert (stalled["longest_tick_ms"], stalled["longest_tick_step"]) == \
        (pytest.approx(90.1), 40)
    assert stalled["longest_gap_after_step"] == 39
    assert stalled["longest_gap_between_ticks_ms"] == pytest.approx(51.0,
                                                                    abs=0.01)
    # the readers of a traced run take the head (PERF.md section 6): one
    # admitting tick among its ten, and two requests admitted by the
    # instant the profiler had started (they waited 0 and 10 ms)
    assert _ticks.HEAD_ONLY
    cell = harness.Cell(SERVE)
    got = {m: cell.reader(m).read(dict(ctx, probe=traced))
           for m in ("decode_tick.ms_p50", "decode_tick.admit_ms_p95",
                     "serve_queue_wait_ms_p95")}
    assert got == pytest.approx({"decode_tick.ms_p50": 18.1,
                                 "decode_tick.admit_ms_p95": 9.0 * 0.55,
                                 "serve_queue_wait_ms_p95": 9.5}, abs=1e-6)
    assert whole["leaves_cover_median_tick"] == pytest.approx(18.0 / 18.1)
    assert whole["leaf_ms_p50"]["decode.sync"] == pytest.approx(15.0)
    # an untraced run has no head: the whole window is read
    assert _ticks.interval(p, head=True) == pytest.approx(
        _ticks.interval(p, head=False))


def test_dropped_spans_are_an_error_not_a_median_of_the_tail():
    tracer = Tracer(capacity=70)
    prev = set_tracer(tracer)
    try:
        off = _ticks.clock_offset()
        t_open = time.perf_counter()
        t_end = fill(tracer.store, t_open + off + MS, n=12)
        assert tracer.store.dropped > 0
        ctx = {"probe": FakeProbe(t_open, t_end - off + MS)}
        cell = harness.Cell(SERVE)
        for metric in ("decode_tick.ms_p50", "serve_queue_wait_ms_p95"):
            with pytest.raises(harness.BenchmarkError, match="dropped"):
                cell.reader(metric).read(ctx)
    finally:
        set_tracer(prev)


def test_a_program_without_the_spans_gives_nothing_to_read():
    """The parent of the PR that brought the spans: every reader returns
    None, so the result line leaves the metric out."""
    prev = set_tracer(Tracer(capacity=16))
    try:
        ctx = {"probe": FakeProbe(0.0, 1e9),
               "trace": {"modules": {"jit_fn(1)": [0.0137]}}}
        cell = harness.Cell(SERVE)
        assert [cell.reader(m).read(ctx) for m in NEW] == [None] * 7
    finally:
        set_tracer(prev)


def test_a_gap_goes_to_the_leaf_not_to_jax_own_event():
    """On the profiler's timeline `decode.sync` holds JAX's own event for
    the copy back.  Both cover the gap wholly and the leaf started first,
    so the gap is the leaf's; an annotated `decode.tick` would take every
    gap of the tick, which is why the engine keeps it off the timeline."""
    us = 1000.0
    gap = (100 * us, 5100 * us)
    leaves = [("decode.dispatch", 0 * us, 150 * us),
              ("decode.sync", 160 * us, 5000 * us),
              ("np.asarray(jax.Array)", 170 * us, 4980 * us),
              ("decode.emit", 5170 * us, 900 * us)]
    got = xplane._attribute([gap], leaves)
    assert dict(got) == {"decode.sync": 5000 * us}
    # a short D2H tail inside a long gap no longer names the gap
    tail = leaves[:2] + [("np.asarray(jax.Array)", 4000 * us, 1100 * us),
                         leaves[3]]
    assert dict(xplane._attribute([gap], tail)) == \
        {"decode.sync": 5000 * us}
    swallowed = xplane._attribute(
        [gap], [("decode.tick", -50 * us, 6500 * us)] + leaves)
    assert dict(swallowed) == {"decode.tick": 5000 * us}


def test_every_reader_reads_a_toy_run_of_the_serving_cell():
    """The engine's own spans through the cell's runner at a toy size:
    all seven metrics are numbers, the leaves cover the tick, and the
    tick stands beside the client's gap between tokens."""
    import jax
    tracer = Tracer(capacity=60000)
    prev = set_tracer(tracer)
    try:
        cell = harness.Cell(SERVE)
        assert [m["name"] for m in cell.per_layer][-7:] == NEW
        probe = harness.Probe(time.perf_counter(), False, None)
        r = cell.runner().Runner(cell, 2 ** 31 + 9, 1.5, jax.devices()[:1],
                                 probe, SCALE[SERVE])
        r.run()
        out = r.results()
        r.release()
        assert out["failed"] == 0 and tracer.store.dropped == 0
        ctx = {"probe": probe, "facts": out["facts"],
               "trace": {"modules": {"jit_fn(1)": [1e-4] * 5}}}
        values = {m: cell.reader(m).read(ctx) for m in NEW}
        assert all(v is not None and v >= 0.0 for v in values.values()), \
            values
        s = _ticks.summary(tracer.store, *_ticks.interval(probe))
        assert s["ticks"] > 10 and s["leaves_cover_all_ticks"] > 0.9
        assert values["decode_tick.host_ms_p50"] <= \
            values["decode_tick.ms_p50"]
        # the runner's reading of the prefill span has not changed: the
        # sum of the window's `decode.prefill` intervals
        lo, hi = _ticks.interval(probe)
        prefills = [s.duration() for s in tracer.store.spans()
                    if s.name == "decode.prefill" and lo <= s.t0 <= hi]
        assert len(prefills) == out["facts"]["prefills"]
        assert sum(prefills) == pytest.approx(out["facts"]["prefill_s"],
                                              rel=1e-6)
    finally:
        set_tracer(prev)


def test_the_tool_counts_what_the_spans_cost(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "ticks.py"), "--synthetic", "200"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    # 200 ticks of seven spans, 50 of them with a queue and a prefill span
    assert row["synthetic_ticks"] == 200 and row["spans"] == 1500
    assert row["us_per_tick"] > 0
