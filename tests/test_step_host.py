"""The one copy of what a step host does around its step
(observability/host.py TelemetryHost; serving/queue.py
EngineIntrospection), held to the same contract from every host."""
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import transformer as T
from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
from bigdl_tpu.nn.module import Module
from bigdl_tpu.observability import (DivergenceError, InMemorySink,
                                     Recorder, set_recorder)
from bigdl_tpu.observability.collectives import account_collective
from bigdl_tpu.observability.host import TelemetryHost
from bigdl_tpu.optim import SGD
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel import mesh as mesh_lib
from bigdl_tpu.parallel.pipeline import PipelineLMTrainer
from bigdl_tpu.parallel.spmd import SpmdTrainer
from bigdl_tpu.serving import (DecodeEngine, EngineClosedError,
                               ModelRegistry, ServingEngine)


def _lm_batch(vocab, seq, batch=4):
    tok = np.random.RandomState(0).randint(0, vocab, (batch, seq))
    tok = tok.astype(np.int32)
    return tok, np.roll(tok, -1, axis=1).astype(np.int32)


def _two_steps_local(rec, prepare):
    rs = np.random.RandomState(0)
    x = rs.randn(32, 8).astype(np.float32)
    y = (rs.randint(0, 4, size=(32,)) + 1).astype(np.float32)
    model = nn.Sequential().add(nn.Linear(8, 4)).add(nn.LogSoftMax())
    opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                          batch_size=16)
           .set_optim_method(SGD(learning_rate=0.1))
           .set_end_when(Trigger.max_epoch(1))
           .set_telemetry(rec))
    prepare(opt)
    opt.optimize()
    return opt, 16


def _two_steps_spmd(rec, prepare):
    tr = SpmdTrainer(T.build("tiny", dropout=0.0), SGD(learning_rate=0.1),
                     mesh=mesh_lib.create_mesh({"dp": 2}))
    tr.set_telemetry(rec)
    prepare(tr)
    tok, tgt = _lm_batch(256, 16)
    for _ in range(2):
        tr.step(tok, tgt)
    return tr, tok.size


def _two_steps_pipeline(rec, prepare):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, d_ff=64, max_len=16, dropout=0.0)
    tr = PipelineLMTrainer(TransformerLM(cfg), SGD(learning_rate=0.1),
                           mesh_lib.create_mesh({"pp": 2}),
                           n_microbatches=2)
    tr.set_telemetry(rec)
    prepare(tr)
    tok, tgt = _lm_batch(64, 16)
    for _ in range(2):
        tr.step(tok, tgt)
    return tr, tok.size


_HOSTS = {"local": (_two_steps_local, "records_total"),
          "spmd": (_two_steps_spmd, "tokens_total"),
          "pipeline": (_two_steps_pipeline, "tokens_total")}


@pytest.mark.parametrize("host", sorted(_HOSTS))
def test_step_record_contract(host):
    """What the benchmark's runners and trace_summary read from a step
    record, from each of the three trainers: the first dispatch is
    ``train_step_compile`` with ``recompile`` 1.0, the second is
    ``train_step`` with none, both hold ``h2d``, ``loss`` and
    ``records``, and the host's items counter adds up."""
    run, counter = _HOSTS[host]
    sink = InMemorySink()
    try:
        trainer, items = run(Recorder(sinks=[sink], annotate=False),
                             lambda t: None)
    finally:
        set_recorder(None)
    assert isinstance(trainer, TelemetryHost)
    first, second = sink.steps()
    assert first["span_counts"]["train_step_compile"] == 1
    assert "train_step" not in first["spans"]
    assert first["scalars"]["recompile"] == 1.0
    assert second["span_counts"]["train_step"] == 1
    assert "train_step_compile" not in second["spans"]
    assert "recompile" not in second["scalars"]
    for i, rec in enumerate((first, second)):
        assert rec["spans"]["h2d"] >= 0.0
        assert np.isfinite(rec["scalars"]["loss"])
        assert rec["scalars"]["records"] == items
        assert rec["counters"][counter] == (i + 1) * items
        assert rec["scalars"]["grad_norm"] > 0      # health rode along
        assert rec["goodput"]["name"] == "train"    # one ledger for all


@pytest.mark.parametrize("host", sorted(_HOSTS))
def test_step_record_reaches_the_health_sentinels(host):
    """``set_health`` is one method for the three trainers and every
    record passes through ``check_record``: a grad-norm ceiling no step
    can meet raises from the first step."""
    run, _ = _HOSTS[host]
    try:
        with pytest.raises(DivergenceError):
            run(Recorder(annotate=False),
                lambda t: t.set_health(policy="raise", grad_norm_limit=1e-9,
                                       install_crash_hooks=False))
    finally:
        set_recorder(None)


class _Reports(Module):
    """Identity that accounts 100 bytes of collective at trace time."""

    def init(self, rng):
        return {}

    def apply(self, params, x, ctx):
        account_collective("all-to-all", 100, 100.0)
        return x


@pytest.mark.parametrize("host", ["local", "distri"])
def test_collective_gauges_hold_one_trace(host):
    """However often jit traces a step, its record holds what ONE trace
    accounts.  ``local``: the cost capture's lowering and the dispatch
    share one trace, and a reset between them must not wipe it.
    ``distri``: capture and dispatch trace twice and the second step, of
    the same signature, traces again (its inputs changed sharding type):
    neither may add up."""
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = (rs.randint(0, 4, size=(64,)) + 1).astype(np.float32)
    model = nn.Sequential().add(_Reports()).add(nn.Linear(8, 4)) \
        .add(nn.LogSoftMax())
    kw = {} if host == "local" else \
        {"mesh": mesh_lib.create_mesh({"dp": 8})}
    cls = LocalOptimizer if host == "local" else DistriOptimizer
    sink = InMemorySink()
    try:
        (cls(model, (x, y), nn.ClassNLLCriterion(), batch_size=32, **kw)
         .set_optim_method(SGD(learning_rate=0.1))
         .set_end_when(Trigger.max_epoch(1))
         .set_telemetry(Recorder(sinks=[sink], annotate=False))
         .optimize())
    finally:
        set_recorder(None)
    first, second = sink.steps()
    want = first["gauges"]["collective/bytes_per_step"]
    if host == "local":
        assert want == 100.0
    else:       # the module's bytes and the gradient all-reduce's
        assert want > 100.0
        assert first["gauges"]["comm/group.dp.wire_bytes_per_step"] > 0
    assert second["gauges"]["collective/bytes_per_step"] == want
    assert second["counters"]["collective/bytes_total"] == 2 * want


class _Scale(Module):
    def init(self, rng):
        return {self.name: {"weight": jnp.ones(())}}

    def apply(self, params, x, ctx):
        return x * params[self.name]["weight"]


def _serving_engine():
    reg = ModelRegistry()
    reg.register("m", _Scale(), input_shape=(4,))
    return ServingEngine(reg, max_batch=4)


def _decode_engine():
    model = T.build("tiny", dropout=0.0, n_layers=1, max_len=32)
    model.ensure_initialized()
    reg = ModelRegistry()
    reg.register("lm", model)
    return DecodeEngine(reg, "lm", slots=2, page_size=8, max_context=16,
                        max_prompt=8, max_new_tokens=4)


@pytest.mark.parametrize("make", [_serving_engine, _decode_engine],
                         ids=["serving", "decode"])
def test_engine_serve_metrics_reconfigures_and_closes(make):
    """serve_metrics twice leaves ONE live server (the first is stopped,
    no leaked thread or socket); shutdown() stops it, and a later call
    raises instead of handing out a server nobody will stop."""
    import urllib.request
    eng = make()
    assert isinstance(eng, (ServingEngine, DecodeEngine))
    try:
        first = eng.serve_metrics(port=0)
        second = eng.serve_metrics(port=0)
        assert eng._http_server is second
        assert first._thread is None or not first._thread.is_alive()
        with urllib.request.urlopen(second.url("/metrics"),
                                    timeout=10) as r:
            assert r.status == 200
        with pytest.raises(Exception):
            urllib.request.urlopen(first.url("/metrics"), timeout=2)
    finally:
        eng.shutdown()
    assert eng._http_server is None
    with pytest.raises(Exception):
        urllib.request.urlopen(second.url("/metrics"), timeout=2)
    with pytest.raises(EngineClosedError):
        eng.serve_metrics(port=0)
