"""Observability subsystem: Recorder primitives, sinks, optimizer
telemetry wiring, DeviceLoader stall accounting, and the trace_summary
steps renderer (ISSUE 1 tentpole)."""
import json
import os
import sys
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from bigdl_tpu.observability import (InMemorySink, JsonlSink, Recorder,
                                     TensorBoardSink, get_recorder,
                                     null_recorder, set_recorder)
from bigdl_tpu.observability import collectives as acct
from bigdl_tpu.observability.sinks import read_jsonl


# --------------------------------------------------------------------- #
# Recorder primitives                                                   #
# --------------------------------------------------------------------- #
def test_counters_gauges_and_snapshot():
    rec = Recorder()
    assert rec.inc("a") == 1.0
    assert rec.inc("a", 2.5) == 3.5
    rec.gauge("q", 7)
    snap = rec.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["gauges"]["q"] == 7.0
    assert rec.gauge_value("q") == 7.0
    assert rec.counter_value("missing", -1.0) == -1.0


def test_spans_accumulate_into_step_record():
    mem = InMemorySink()
    rec = Recorder(sinks=[mem], annotate=False)
    rec.start_step(5)
    with rec.span("work"):
        time.sleep(0.01)
    with rec.span("work"):
        time.sleep(0.01)
    with rec.span("other"):
        pass
    r = rec.end_step()
    assert r["step"] == 5
    assert r["spans"]["work"] >= 0.02
    assert r["span_counts"]["work"] == 2
    assert "other" in r["spans"]
    assert r["dur"] >= r["spans"]["work"]
    assert mem.steps()[-1] is r
    # per-step state resets
    rec.start_step(6)
    r2 = rec.end_step()
    assert r2["spans"] == {}


def test_histograms_per_step():
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    rec.start_step(0)
    for v in (1.0, 2.0, 3.0):
        rec.observe("latency", v)
    r = rec.end_step()
    h = r["hist"]["latency"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
    assert abs(h["mean"] - 2.0) < 1e-9
    rec.start_step(1)
    assert "hist" not in rec.end_step()


def test_histogram_percentiles():
    rec = Recorder(annotate=False)
    for v in range(1, 101):            # 1..100
        rec.observe("lat", float(v))
    q = rec.hist_quantiles("lat")
    # numpy's linear-interpolation convention over 1..100
    assert abs(q["p50"] - np.percentile(np.arange(1, 101), 50)) < 1e-9
    assert abs(q["p95"] - np.percentile(np.arange(1, 101), 95)) < 1e-9
    assert abs(q["p99"] - np.percentile(np.arange(1, 101), 99)) < 1e-9
    s = rec.hist_summary("lat")
    assert s["count"] == 100 and s["p50"] == q["p50"]
    assert rec.hist_quantiles("missing") is None
    # percentiles fold into the step record and reset with it
    rec.start_step(0)
    rec.observe("lat2", 7.0)
    r = rec.end_step()
    assert r["hist"]["lat2"]["p99"] == 7.0
    assert rec.hist_quantiles("lat2") is None


def test_histogram_sample_window_is_bounded():
    rec = Recorder(annotate=False, hist_sample_cap=8)
    for v in range(100):
        rec.observe("lat", float(v))
    # moments stay exact over ALL observations ...
    s = rec.hist_summary("lat")
    assert s["count"] == 100 and s["min"] == 0.0 and s["max"] == 99.0
    # ... while quantiles cover the most recent window only
    assert rec.hist_quantiles("lat")["p50"] == 95.5


def test_disabled_recorder_is_noop_and_cheap():
    rec = Recorder(enabled=False)
    # all primitives are no-ops
    rec.inc("c")
    rec.gauge("g", 1)
    rec.observe("h", 1.0)
    with rec.span("s"):
        pass
    rec.start_step(0)
    assert rec.end_step() is None
    assert rec.snapshot() == {"counters": {}, "gauges": {}}
    # the shared span object means no per-call allocation
    assert rec.span("a") is rec.span("b")


def test_recorder_thread_safety():
    rec = Recorder(annotate=False)

    def worker():
        for _ in range(1000):
            rec.inc("n")

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert rec.counter_value("n") == 8000


def test_active_recorder_install_and_reset():
    rec = Recorder()
    prev = set_recorder(rec)
    try:
        assert get_recorder() is rec
    finally:
        set_recorder(prev if prev is not null_recorder() else None)
    assert get_recorder() is not rec


# --------------------------------------------------------------------- #
# sinks                                                                 #
# --------------------------------------------------------------------- #
def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = Recorder(sinks=[JsonlSink(path, flush_every=1)], annotate=False)
    for i in range(3):
        rec.start_step(i)
        rec.scalar("loss", float(10 - i))
        rec.inc("records_total", 4)
        rec.end_step()
    rec.close()
    recs = read_jsonl(path)
    assert len(recs) == 3
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert recs[-1]["counters"]["records_total"] == 12
    assert recs[0]["scalars"]["loss"] == 10.0


def test_jsonl_sink_handles_device_scalars(tmp_path):
    path = str(tmp_path / "d.jsonl")
    rec = Recorder(sinks=[JsonlSink(path, flush_every=1)], annotate=False)
    rec.start_step(0)
    rec.scalar("loss", jnp.float32(1.5))     # device scalar, not a float
    rec.end_step()
    rec.close()
    assert read_jsonl(path)[0]["scalars"]["loss"] == 1.5


def test_tensorboard_sink_roundtrip(tmp_path):
    from bigdl_tpu.visualization.event_writer import read_scalar
    d = str(tmp_path / "tb")
    sink = TensorBoardSink(d)
    rec = Recorder(sinks=[sink], annotate=False)
    rec.start_step(3)
    with rec.span("train_step"):
        pass
    rec.scalar("grad_norm", 0.25)
    rec.end_step()
    sink.close()
    vals = read_scalar(d, "telemetry/grad_norm")
    assert [(s, v) for s, v, _ in vals] == [(3, 0.25)]
    spans = read_scalar(d, "telemetry/span_ms/train_step")
    assert len(spans) == 1 and spans[0][0] == 3


# --------------------------------------------------------------------- #
# collective accounting                                                 #
# --------------------------------------------------------------------- #
def test_static_byte_accounting():
    tree = {"w": jnp.zeros((8, 4), jnp.float32), "b": jnp.zeros((4,),
                                                                jnp.float32)}
    assert acct.tree_bytes(tree) == (32 + 4) * 4
    assert acct.tree_bytes(tree, wire_itemsize=2) == (32 + 4) * 2
    assert acct.ring_allreduce_bytes(1024, 4) == 2 * 1024 * 3 / 4
    assert acct.ring_gather_bytes(1024, 4) == 1024 * 3 / 4
    assert acct.ring_allreduce_bytes(1024, 1) == 0.0
    assert acct.compressed_itemsize("bf16") == 2
    assert acct.compressed_itemsize(None) is None


def test_allreduce_accounts_to_active_recorder():
    from bigdl_tpu.parallel.allreduce import allreduce_gradients
    from bigdl_tpu.parallel import mesh as mesh_lib
    from jax.sharding import PartitionSpec as P

    mesh = mesh_lib.create_mesh({"dp": 4})
    rec = Recorder(annotate=False)
    prev = set_recorder(rec)
    try:
        def f(g):
            return allreduce_gradients({"w": g}, "dp",
                                       compress="bf16")["w"]
        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                                    out_specs=P(), check_vma=False))(
            jnp.ones((8, 4), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 1.0)
    finally:
        set_recorder(prev if prev is not null_recorder() else None)
    raw = rec.gauge_value("collective/allreduce_bytes")
    wire = rec.gauge_value("collective/allreduce_wire_bytes")
    assert raw == 2 * (8 * 4 * 4) * 3 / 4      # fp32 ring all-reduce
    assert wire == raw / 2                      # bf16 on the wire


def test_hlo_collective_parsing():
    hlo = """
  %ar = f32[64,4]{1,0} all-reduce(f32[64,4]{1,0} %x), replica_groups={{0,1,2,3}}
  %ag = f32[64,4]{1,0} all-gather(f32[16,4]{1,0} %y), replica_groups=[2,4]<=[8]
"""
    ops = acct.hlo_collective_ops(hlo, 8)
    assert [o for o, _, _ in ops] == ["all-reduce", "all-gather"]
    ar, ag = ops
    assert ar[1] == 64 * 4 * 4
    assert ar[2] == 2 * ar[1] * 3 / 4     # group size 4 from explicit groups
    assert ag[2] == ag[1] * 3 / 4         # group size 4 from iota form


# --------------------------------------------------------------------- #
# optimizer wiring                                                      #
# --------------------------------------------------------------------- #
def _tiny_problem(n=64, d=8, classes=3, seed=0):
    from bigdl_tpu import nn
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    y = (rng.randint(0, classes, n) + 1).astype(np.float32)
    model = nn.Sequential(nn.Linear(d, 16), nn.ReLU(),
                          nn.Linear(16, classes), nn.LogSoftMax())
    return model, x, y


def test_local_optimizer_telemetry(tmp_path):
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    model, x, y = _tiny_problem()
    mem = InMemorySink()
    path = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(sinks=[mem, JsonlSink(path, flush_every=1)],
                   annotate=False)
    try:
        opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                              batch_size=16)
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(2))
               .set_prefetch(2)
               .set_telemetry(rec))
        opt.optimize()
    finally:
        set_recorder(None)
    steps = mem.steps()
    assert len(steps) == 8              # 64/16 batches x 2 epochs
    first, last = steps[0], steps[-1]
    # per-step spans: fetch + h2d + the jitted step (compile on step 1)
    assert "data_fetch" in first["spans"]
    assert "train_step_compile" in first["spans"]
    assert first["scalars"]["recompile"] == 1.0
    assert "train_step" in steps[1]["spans"]
    assert "recompile" not in steps[1]["scalars"]
    # training-health scalars
    for k in ("loss", "grad_norm", "param_norm", "update_norm",
              "update_ratio", "learning_rate", "records_per_sec"):
        assert isinstance(first["scalars"][k], float), k
    assert first["scalars"]["update_ratio"] > 0
    # DeviceLoader counters flowed into the same recorder
    assert last["counters"]["dataloader/batches"] == 8
    assert last["counters"]["records_total"] == 128
    # JSONL sink recorded the same stream
    assert len([r for r in read_jsonl(path)
                if r.get("type") == "step"]) == 8


def test_local_optimizer_telemetry_with_grad_accum():
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    model, x, y = _tiny_problem()
    mem = InMemorySink()
    rec = Recorder(sinks=[mem], annotate=False)
    try:
        opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                              batch_size=32)
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(1))
               .set_gradient_accumulation(2)
               .set_telemetry(rec))
        opt.optimize()
    finally:
        set_recorder(None)
    assert all("grad_norm" in s["scalars"] for s in mem.steps())


def test_distri_optimizer_telemetry_collective_volume():
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import mesh as mesh_lib

    model, x, y = _tiny_problem(d=16)
    mesh = mesh_lib.create_mesh({"dp": 8})
    mem = InMemorySink()
    rec = Recorder(sinks=[mem], annotate=False)
    try:
        opt = (DistriOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                               batch_size=64, mesh=mesh, compress="bf16")
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(1))
               .set_telemetry(rec))
        opt.optimize()
    finally:
        set_recorder(None)
    last = mem.steps()[-1]
    grad_bytes = sum(int(np.prod(p.shape)) * 4
                     for p in jax.tree_util.tree_leaves(
                         model.init_params(0)[0]))
    raw = last["gauges"]["collective/allreduce_bytes"]
    assert raw == pytest.approx(2 * grad_bytes * 7 / 8)
    # bf16 compression halves the wire volume
    assert last["gauges"]["collective/allreduce_wire_bytes"] \
        == pytest.approx(raw / 2)
    assert last["counters"]["collective/wire_bytes_total"] \
        == pytest.approx(last["gauges"]["collective/wire_bytes_per_step"]
                         * len(mem.steps()))
    assert "grad_norm" in last["scalars"]


def test_distri_fsdp_telemetry_health_matches_dp():
    """Global grad-norm under FSDP (psum of shard contributions) must
    equal the replicated-dp value — same model, same data."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import mesh as mesh_lib

    norms = {}
    for fsdp in (False, True):
        model, x, y = _tiny_problem(d=16, seed=3)
        mesh = mesh_lib.create_mesh({"dp": 8})
        mem = InMemorySink()
        rec = Recorder(sinks=[mem], annotate=False)
        try:
            opt = (DistriOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                                   batch_size=64, mesh=mesh, fsdp=fsdp)
                   .set_optim_method(SGD(learning_rate=0.1))
                   .set_end_when(Trigger.max_epoch(1))
                   .set_telemetry(rec))
            opt.optimize()
        finally:
            set_recorder(None)
        norms[fsdp] = [s["scalars"]["grad_norm"] for s in mem.steps()]
    np.testing.assert_allclose(norms[True], norms[False], rtol=1e-4)


def test_telemetry_off_step_signature_unchanged():
    """Without a recorder the built step returns the 4-tuple — the
    no-telemetry path compiles the exact same program as before."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    model, x, y = _tiny_problem(n=8)
    method = SGD(learning_rate=0.1)
    params, state = model.init_params(0)
    step = make_train_step(model, nn.ClassNLLCriterion(), method)
    out = step(params, method.init_state(params), state,
               jnp.asarray(x[:8]), jnp.asarray(y[:8]),
               jax.random.PRNGKey(0))
    assert len(out) == 4
    step_t = make_train_step(model, nn.ClassNLLCriterion(), method,
                             telemetry=True)
    out_t = step_t(params, method.init_state(params), state,
                   jnp.asarray(x[:8]), jnp.asarray(y[:8]),
                   jax.random.PRNGKey(0))
    assert len(out_t) == 5
    assert float(out_t[3]) == pytest.approx(float(out[3]))
    assert set(out_t[4]) == {"grad_norm", "param_norm", "update_norm",
                             "update_ratio", "nonfinite_grads"}
    assert float(out_t[4]["nonfinite_grads"]) == 0.0   # clean step


def test_disabled_recorder_compiles_plain_step():
    """Attaching a DISABLED recorder must not grow the compiled program
    (no health norms) nor emit records — the no-op guarantee covers
    device work too."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    model, x, y = _tiny_problem()
    mem = InMemorySink()
    rec = Recorder(sinks=[mem], enabled=False, annotate=False)
    try:
        opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                              batch_size=16)
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(1))
               .set_telemetry(rec))
        assert opt._telemetry_active() is False
        opt.optimize()
    finally:
        set_recorder(None)
    assert mem.records == []


def test_ragged_last_batch_does_not_double_count_collectives():
    """A smaller last batch re-traces the jitted step; the trace-time
    collective accounting re-runs then, and the per-step gauges must be
    reset or every later step double-counts the volume."""
    from bigdl_tpu import nn
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import mesh as mesh_lib

    model, x, y = _tiny_problem(n=96)        # 64 + ragged 32
    ds = DataSet.minibatch_arrays(x, y, 64, shuffle=False, drop_last=False)
    mesh = mesh_lib.create_mesh({"dp": 8})
    mem = InMemorySink()
    rec = Recorder(sinks=[mem], annotate=False)
    try:
        opt = (DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                               batch_size=64, mesh=mesh)
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(1))
               .set_telemetry(rec))
        opt.optimize()
    finally:
        set_recorder(None)
    steps = mem.steps()
    assert len(steps) == 2
    assert steps[1]["scalars"].get("recompile") == 1.0   # ragged re-trace
    per_step = steps[0]["gauges"]["collective/bytes_per_step"]
    # grads are param-shaped: both steps move identical volume
    assert steps[1]["gauges"]["collective/bytes_per_step"] == per_step
    assert steps[1]["counters"]["collective/bytes_total"] == 2 * per_step


def test_trace_only_recorder_skips_health_and_scalars(tmp_path):
    """set_trace_every without set_telemetry must stay cheap: no health
    norms compiled into the step and no per-step loss host sync (the
    sink-less records would go nowhere)."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    model, x, y = _tiny_problem()
    try:
        opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                              batch_size=16)
               .set_optim_method(SGD(learning_rate=0.1))
               .set_end_when(Trigger.max_epoch(1))
               .set_trace_every(2, str(tmp_path / "trace")))
        assert opt._telemetry_active() is False
        opt.optimize()
    finally:
        set_recorder(None)


@pytest.mark.slow
def test_spmd_set_telemetry_mid_training_preserves_params():
    """Attaching a recorder after steps have run re-jits with the health
    signature WITHOUT resetting params/opt_state to a fresh init."""
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.parallel import mesh as mesh_lib
    from bigdl_tpu.parallel.spmd import SpmdTrainer

    mesh = mesh_lib.create_mesh({"dp": 2, "tp": 2, "sp": 2})
    tr = SpmdTrainer(T.build("tiny"), SGD(learning_rate=0.1),
                     mesh=mesh, seed=0).init()
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 256, (4, 65))
    tok, tgt = tok[:, :-1], tok[:, 1:]
    l0 = float(tr.step(tok, tgt))
    float(tr.step(tok, tgt))
    before = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
    mem = InMemorySink()
    try:
        tr.set_telemetry(Recorder(sinks=[mem], annotate=False))
        after = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
        assert np.array_equal(before, after)
        l2 = float(tr.step(tok, tgt))
    finally:
        set_recorder(None)
    assert l2 < l0
    rec0 = mem.steps()[0]
    assert "grad_norm" in rec0["scalars"]
    assert "train_step_compile" in rec0["spans"]


# --------------------------------------------------------------------- #
# DeviceLoader stall accounting                                         #
# --------------------------------------------------------------------- #
def test_device_loader_stall_counter_under_slow_producer():
    from bigdl_tpu.data.device_loader import DeviceLoader

    def slow_source():
        for i in range(4):
            time.sleep(0.05)       # starved consumer: stall accumulates
            yield i

    rec = Recorder(annotate=False)
    out = list(DeviceLoader(slow_source(), depth=2, recorder=rec))
    assert out == [0, 1, 2, 3]
    assert rec.counter_value("dataloader/batches") == 4
    assert rec.counter_value("dataloader/stall_seconds") >= 0.1
    assert "dataloader/queue_depth" in rec.snapshot()["gauges"]


def test_device_loader_producer_backpressure_counter():
    from bigdl_tpu.data.device_loader import DeviceLoader

    def fast_source():
        for i in range(6):
            yield i

    rec = Recorder(annotate=False)
    it = iter(DeviceLoader(fast_source(), depth=1, recorder=rec))
    first = next(it)
    time.sleep(0.3)                # consumer sits on the queue
    rest = list(it)
    assert [first] + rest == list(range(6))
    assert rec.counter_value("dataloader/producer_wait_seconds") >= 0.1


def test_device_loader_disabled_recorder_unchanged():
    from bigdl_tpu.data.device_loader import DeviceLoader
    out = list(DeviceLoader(iter(range(5)), depth=2,
                            recorder=Recorder(enabled=False)))
    assert out == [0, 1, 2, 3, 4]


# --------------------------------------------------------------------- #
# trace_summary steps renderer                                          #
# --------------------------------------------------------------------- #
def test_trace_summary_steps_table(tmp_path):
    from trace_summary import load_steps, summarize_steps

    path = str(tmp_path / "t.jsonl")
    rec = Recorder(sinks=[JsonlSink(path, flush_every=1)], annotate=False)
    for i in range(4):
        rec.start_step(i)
        with rec.span("train_step"):
            time.sleep(0.002)
        rec.scalar("loss", 2.0 - 0.1 * i)
        rec.scalar("records", 16)
        rec.inc("records_total", 16)
        rec.end_step()
    rec.close()
    steps, ck_summary = load_steps(path)
    assert len(steps) == 4
    assert ck_summary is None
    assert load_steps(path, last_n=2)[0][0]["step"] == 2
    lines = []
    summarize_steps(steps, out=lines.append)
    text = "\n".join(lines)
    assert "step-time breakdown" in text
    assert "train_step" in text
    assert "loss" in text and "records_per_sec" in text
    assert "records_total" in text


def test_trace_summary_checkpoint_split(tmp_path):
    """The steps table renders the blocking-copy vs async-write split,
    preferring the post-drain checkpoint_summary totals over the last
    step's mid-write counter snapshot."""
    from trace_summary import load_steps, summarize_steps

    path = str(tmp_path / "t.jsonl")
    rec = Recorder(sinks=[JsonlSink(path, flush_every=1)], annotate=False)
    rec.start_step(0)
    rec.add_span("checkpoint.blocking", 0.002)
    rec.scalar("records", 16)
    rec.end_step()
    # async commits land AFTER the last step record was cut
    rec.inc("checkpoint/write_seconds", 0.5)
    rec.inc("checkpoint/bytes_written", 4096)
    rec.inc("checkpoint/committed", 2)
    rec.emit_record("checkpoint_summary",
                    counters={k: v for k, v in
                              rec.snapshot()["counters"].items()
                              if k.startswith("checkpoint/")})
    rec.close()
    steps, ck_summary = load_steps(path)
    assert ck_summary is not None
    lines = []
    summarize_steps(steps, out=lines.append, ck_summary=ck_summary)
    text = "\n".join(lines)
    assert "blocking copy vs async write" in text
    assert "committed 2" in text
    assert "4.0 KB" in text


def test_trace_every_writes_xla_trace(tmp_path):
    """trace_every(n) captures a jax.profiler trace of every n-th step."""
    d = str(tmp_path / "trace")
    rec = Recorder(annotate=False).trace_every(2, d)
    for i in range(3):
        rec.start_step(i)
        float(jnp.sum(jnp.ones(8)))
        rec.end_step()
    # steps 0 and 2 traced; the profiler writes under <dir>/plugins/profile
    assert os.path.isdir(d)
    found = []
    for root, _, files in os.walk(d):
        found += [f for f in files if "xplane" in f or "trace" in f]
    assert found, "no profiler output written"
