"""The engine's spans on the device trace's clock (`metrics/_device_clock.py`)
over synthetic traces whose device plane runs a known offset ahead, the
readers of the idle shares, and `prefill_chunk.sync_ms_p50` over a span
store and over a toy engine's own spans."""
import collections
import time

import numpy as np
import pytest

from benchmarks import harness, xplane
from benchmarks.metrics import _device_clock, _ticks

from bigdl_tpu.observability.context import TraceContext
from bigdl_tpu.observability.tracing import Span, Tracer, set_tracer

US, MS = 1e3, 1e6                   # in ns, the trace's unit
DELTA = 2.4 * MS                    # the device plane runs this far ahead
SHARES = ["device_idle.launch_share", "device_idle.sync_share",
          "device_idle.emit_share"]
SERVE = "olmo1b-serve-chat"


def tick_trace(n=60, admit_every=10, delta=DELTA, shift=None,
               prefill_leaves=True):
    """(planes, idle): n ticks back to back, every `admit_every`-th with a
    prefill chunk in its `decode.admit`, the device plane `delta` ahead
    of the host's (`shift(i)` moves run i further), and the ns of device
    idle under each host leaf ("rest" under none), known by
    construction.  A run starts `launch` after its dispatch began and
    ends `overhang` before its sync ended, 50 us at the least."""
    host, ops, mods = [], [], []
    idle = collections.Counter()
    t = 0.0

    def leaf(name, d, idle_part=None):
        nonlocal t
        host.append((name, t, d))
        idle[name] += d if idle_part is None else idle_part
        t += d
        return t - d

    def run(name, start, end, i):
        s = start + delta + (shift(i) if shift else 0.0)
        mods.append((name, s, end - start))
        # two ops with a 5 us gap between them: too short to split
        half = (end - start) / 2
        ops.extend([("fusion.1", s, half - 2.5 * US),
                    ("fusion.2", s + half + 2.5 * US, half - 2.5 * US)])

    for i in range(n):
        launch = (50 + 100 * (i % 3)) * US
        overhang = (50 + 150 * (i % 4)) * US
        a0 = t
        if i % admit_every == 0:
            t += 20 * US                  # the admit's own head
            p0 = t
            if prefill_leaves:
                leaf("prefill.stage", 500 * US)
                r0 = leaf("prefill.dispatch", 300 * US, launch) + launch
                r1 = leaf("prefill.sync", 9 * MS, overhang) + 9 * MS \
                    - overhang
            else:
                r0, r1 = t + 500 * US + launch, t + 9.8 * MS - overhang
                idle["rest"] += 800 * US + launch + overhang
                t += 9.8 * MS
            host.append(("decode.prefill", p0, t - p0))
            run("jit_prefill_chunk(7)", r0, r1, i)
            t += 20 * US                  # and its tail
            idle["rest"] += 40 * US
        else:
            t += 10 * US
            idle["rest"] += 10 * US
        host.append(("decode.admit", a0, t - a0))
        leaf("decode.schedule", 100 * US)
        leaf("decode.stage", 1400 * US)
        r0 = leaf("decode.dispatch", 900 * US, launch) + launch
        r1 = leaf("decode.sync", 4800 * US, overhang) + 4800 * US - overhang
        leaf("decode.emit", 600 * US)
        run("jit_fn(3)", r0, r1, i)
    # the trace ends with the device's last op: the last tick's overhang
    # and emit lie past it; it begins with the host's first event, delta
    # before the device's clock reads it
    idle["decode.sync"] -= overhang
    idle["decode.emit"] -= 600 * US
    idle["rest"] += delta
    planes = [{"name": "/device:TPU:0", "lines": [
                  {"name": "XLA Ops", "events": ops},
                  {"name": "XLA Modules", "events": mods}]},
              {"name": "/host:CPU", "lines": [
                  {"name": "python", "events": host}]}]
    return planes, idle


def expected_shares(idle):
    total = sum(idle.values())
    return {part: 100.0 * sum(idle[n] for n in leaves) / total
            for part, leaves in _device_clock.PARTS.items()}


def read_all(found):
    """The three share readers over a run whose trace reduced to
    `found` (kept in the context as the readers keep it)."""
    cell = harness.Cell(SERVE)
    return [cell.reader(m).read({"device_clock": found}) for m in SHARES]


def test_the_offset_is_found_and_every_gap_split_by_overlap():
    planes, idle = tick_trace()
    found = _device_clock.reduce(planes)
    assert found["offset_ms"] == pytest.approx(DELTA / MS, abs=0.1)
    assert found["runs"] == {"decode": 60, "prefill": 6}
    assert found["runs_ordered"] == found["runs_paired"] == 66
    # the shortest launch and the shortest overhang (50 us) bound it
    assert found["offset_range_ms"] == pytest.approx([2.35, 2.45])
    # on that clock a decode run starts 50, 150 or 250 us after its
    # dispatch began and ends 50, 200, 350 or 500 us before its sync ends
    assert found["lags"]["decode"] == pytest.approx(
        {"dispatch_to_run_ms": 0.15, "run_to_sync_end_ms": 0.275})
    want = expected_shares(idle)
    for part in ("launch", "sync", "emit"):
        assert found["shares"][part] == pytest.approx(want[part], abs=0.01)
    assert sum(found["shares"].values()) == pytest.approx(100.0)
    # the ends of the range the anchors leave, 50 us either way of a
    # tick's 2.4 ms of idle, move about two points between the parts
    for ends in found["shares_at_range_ends"]:
        assert ends == pytest.approx(found["shares"], abs=2.5)
        assert ends != pytest.approx(found["shares"], abs=0.5)
    assert found["idle_s"] == pytest.approx(sum(idle.values()) / 1e9,
                                            rel=1e-9)
    for name, s in found["idle_by_leaf_s"].items():
        assert s == pytest.approx(idle[name] / 1e9, abs=2e-8), name
    # the readers say the same, and they are what the metric names say
    assert read_all(found) == [found["shares"][p]
                               for p in ("launch", "sync", "emit")]
    # the breakdown's rule, on the unshifted clocks, hands each gap whole
    # to the event covering most of it: the next tick's `decode.sync`,
    # where a tenth of the idle lies
    whole = xplane._attribute(_device_clock.idle_gaps(planes, planes[0]),
                              planes[1]["lines"][0]["events"])
    assert whole["decode.sync"] > 0.9 * sum(whole.values())
    assert found["shares"]["sync"] < 15.0


def test_a_gap_across_four_leaves_is_four_parts():
    """A 2.4 ms gap from the emit's start to the dispatch's head: four
    parts by overlap, where the breakdown's rule gives it all to
    `decode.stage`, the leaf that covers most of it."""
    leaves = [("decode.sync", 0, 5000 * US), ("decode.emit", 5000 * US,
              5600 * US), ("decode.schedule", 5600 * US, 5700 * US),
              ("decode.stage", 5700 * US, 7100 * US),
              ("decode.dispatch", 7100 * US, 8000 * US)]
    gap = (5000 * US + DELTA, 7400 * US + DELTA)
    short = (8100 * US + DELTA, 8110 * US + DELTA)
    by_leaf, idle = _device_clock.split_gaps([gap, short], leaves, DELTA)
    assert dict(by_leaf) == pytest.approx({
        "decode.emit": 600 * US, "decode.schedule": 100 * US,
        "decode.stage": 1400 * US, "decode.dispatch": 300 * US})
    assert idle == pytest.approx(2400 * US)
    assert dict(xplane._attribute([(5000 * US, 7400 * US)],
                                  [(n, s, e - s) for n, s, e in leaves])) \
        == {"decode.stage": 2400 * US}


def test_runs_no_single_offset_orders_give_nothing_to_read():
    rng = np.random.default_rng(3)
    moves = rng.uniform(-8 * MS, 8 * MS, 200)
    planes, _ = tick_trace(shift=lambda i: moves[i])
    found = _device_clock.reduce(planes)
    assert found["shares"] is None and "orders" in found["why"]
    assert found["runs_ordered"] < 0.95 * found["runs_paired"]
    assert read_all(found) == [None] * 3
    # a few runs out of place are outvoted; the offset holds
    planes, idle = tick_trace(shift=lambda i: 6 * MS if i == 7 else 0.0)
    found = _device_clock.reduce(planes)
    assert found["offset_ms"] == pytest.approx(DELTA / MS, abs=0.1)
    assert found["runs_ordered"] == found["runs_paired"] - 1


def test_a_program_without_the_prefill_leaves_gives_nothing_to_read():
    """The parent of the PR that brought `prefill.*`: where a prefill
    ran, its idle would fall to the remainder, so the readers say
    nothing; where none ran, the decode leaves are the whole story."""
    planes, _ = tick_trace(prefill_leaves=False)
    found = _device_clock.reduce(planes)
    assert found["shares"] is None and "leaves" in found["why"]
    assert read_all(found) == [None] * 3
    planes, idle = tick_trace(admit_every=1000)
    found = _device_clock.reduce(planes)
    assert found["runs"]["prefill"] == 1
    assert found["shares"]["launch"] == pytest.approx(
        expected_shares(idle)["launch"], abs=0.01)
    # no device plane, no trace, no file: nothing, and no error
    assert _device_clock.reduce(planes[1:]) is None

    class Probe:
        traced, trace_dir = None, None
    assert read_all(None) == [None] * 3
    assert _device_clock.summary({"probe": Probe}) is None


class FakeProbe:
    def __init__(self, t_open, t_close):
        self.t_open, self.t_close, self.traced = t_open, t_close, None


def test_the_chunk_sync_is_read_over_the_whole_window():
    tracer = Tracer(capacity=4096)
    prev = set_tracer(tracer)
    try:
        off = _ticks.clock_offset()
        t_open = time.perf_counter()
        t = t_open + off + 1e-3
        store = tracer.store

        def prefill(n, t, sync_ms, **args):
            p = TraceContext("a" * 32, f"{n:015x}0")
            store.add(Span("decode.prefill", p, t, t + 0.05, args=args))
            store.add(Span("prefill.sync", TraceContext(
                "a" * 32, f"{n:015x}1", p.span_id), t + 0.01,
                t + 0.01 + sync_ms * 1e-3))
        for i, ms in enumerate((30.0, 40.0, 41.0, 60.0, 35.0)):
            prefill(i, t + 0.1 * i, ms, slot=0, offset=512 * i)
        prefill(9, t + 0.7, 1.0, bucket=256)             # a ladder's
        probe = FakeProbe(t_open, t_open + 0.8)
        prefill(10, t + 0.9, 99.0, slot=0, offset=0)     # after the window
        reader = harness.Cell(SERVE).reader("prefill_chunk.sync_ms_p50")
        assert reader.read({"probe": probe}) == pytest.approx(40.0)
        # the traced seconds' head does not bound it: the whole window
        probe.traced = (t_open + 0.05, t_open + 0.1)
        assert reader.read({"probe": probe}) == pytest.approx(40.0)
        assert reader.read({"probe": FakeProbe(0.0, 1.0)}) is None
    finally:
        set_tracer(prev)


def test_a_toy_engine_leaves_what_the_chunk_reader_reads():
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.serving import DecodeEngine, ModelRegistry
    model = T.build("tiny", dropout=0.0, n_layers=2, max_len=64)
    model.ensure_initialized()
    reg = ModelRegistry()
    reg.register("lm", model)
    tracer = Tracer(capacity=8192)
    prev = set_tracer(tracer)
    eng = DecodeEngine(reg, "lm", slots=2, page_size=8, max_context=48,
                       max_prompt=40, prefill_chunk=8,
                       max_new_tokens=4).warmup()
    try:
        t_open = time.perf_counter()
        st = eng.stream("lm", np.arange(1, 30, dtype=np.int32),
                        max_new_tokens=3)
        assert len(list(st.tokens())) == 3
        probe = FakeProbe(t_open, time.perf_counter())
        got = harness.Cell(SERVE).reader("prefill_chunk.sync_ms_p50") \
            .read({"probe": probe})
    finally:
        eng.shutdown()
        set_tracer(prev)
    syncs = sorted(s.duration() for s in tracer.store.spans()
                   if s.name == "prefill.sync")
    assert len(syncs) == 4                  # 29 tokens in chunks of 8
    assert got == pytest.approx(1e3 * np.median(syncs))


def test_a_run_that_two_anchors_hold_counts_once():
    """A short run inside two long anchors: both hold it over ranges of
    the offset that meet, and it is still one run ordered of one."""
    runs = {"decode": [(10 * MS, 11 * MS)], "prefill": []}
    anchored = {"decode": [(7 * MS, 14 * MS), (8 * MS, 15 * MS)],
                "prefill": []}
    delta, ordered, paired, (lo, hi) = _device_clock.offset(runs, anchored)
    assert (ordered, paired) == (1, 1)
    assert (lo, hi) == (-4 * MS, 3 * MS) and delta == -0.5 * MS


def test_the_tool_counts_what_the_leaves_cost(tmp_path):
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "tools",
                                      "device_clock.py"), "--synthetic", "200"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["synthetic_prefills"] == 200
    assert row["us_per_prefill_span"] > 0 and "us_per_three_leaves" in row
