"""ZeRO-1 sharded weight update + bucketed/compressed gradient exchange
on the virtual 8-device CPU mesh: parity with the unsharded dp path
(bit-identical for SGD, documented-tolerance for Adam), 1/N sharded
optimizer state, exact wire-byte accounting for fp16 compression, the
bucketer's pack/unpack round-trip, the sharding-coverage counters, and
the trace_summary comm renderer."""
import io
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from bigdl_tpu import nn
from bigdl_tpu.observability import Recorder, set_recorder
from bigdl_tpu.observability import InMemorySink
from bigdl_tpu.optim import SGD, Adam, Trigger
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib
from bigdl_tpu.parallel.bucketer import GradBucketer
from bigdl_tpu.parallel.zero import Zero1Layout


def make_data(n=256, d=12, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, 1).astype(np.float32)
    y = (x @ w + 0.01 * rng.randn(n, 1)).astype(np.float32)
    return x, y


def make_model(seed=0):
    # Linear(12, 8) weight (8, 12) is dim0-shardable over 8; the (8,)
    # bias shards too; Linear(8, 1)'s (1, 8) weight and (1,) bias land
    # in the padded flat bucket — both zero1 paths exercised
    m = nn.Sequential(nn.Linear(12, 8), nn.Tanh(), nn.Linear(8, 1))
    m.reset(seed)
    return m


def train_params(opt):
    model = opt.optimize()
    return jax.tree_util.tree_map(np.asarray, model._params)


def _distri(seed, epochs=3, optim=None, **kw):
    x, y = make_data()
    mesh = mesh_lib.create_mesh({"dp": 8})
    opt = (DistriOptimizer(make_model(seed), (x, y), nn.MSECriterion(),
                           batch_size=64, mesh=mesh, **kw)
           .set_optim_method(optim or SGD(learning_rate=0.05))
           .set_end_when(Trigger.max_epoch(epochs)))
    return opt


# --------------------------------------------------------------------- #
# ZeRO-1 parity                                                          #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("optim", [
    lambda: SGD(learning_rate=0.05),
    lambda: SGD(learning_rate=0.05, momentum=0.9),
], ids=["sgd", "sgd-momentum"])
def test_zero1_sgd_bit_identical_to_unsharded(optim):
    """Acceptance: psum_scatter/n -> shard update -> all_gather is the
    SAME floating-point program as pmean -> full update for elementwise
    SGD on XLA CPU — final params bit for bit after 3 epochs."""
    p0 = train_params(_distri(3, optim=optim()))
    p1 = train_params(_distri(3, optim=optim(), zero1=True))
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(a, b)


def test_zero1_adam_allclose_documented_tolerance():
    """Adam's division chain picks up ~1 ulp/step of FMA-contraction
    drift between the two program structures (same mechanism as the
    fused-kernel parity note in kernels/fused_optim.py) — measured
    ~7e-9 absolute after 12 steps; bound it at 1e-6."""
    p0 = train_params(_distri(3, optim=Adam(1e-2)))
    p1 = train_params(_distri(3, optim=Adam(1e-2), zero1=True))
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_zero1_grad_clipping_uses_global_norm():
    """Clipping under zero1 psums shard sums-of-squares — the clip scale
    is the GLOBAL norm's, so clipped runs track the unsharded path."""
    o0 = _distri(5, optim=SGD(learning_rate=0.05))
    o0.set_gradient_clipping_by_l2_norm(1.0)
    p0 = train_params(o0)
    o1 = _distri(5, optim=SGD(learning_rate=0.05), zero1=True)
    o1.set_gradient_clipping_by_l2_norm(1.0)
    p1 = train_params(o1)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_zero1_rejects_per_tensor_norm_optimizers():
    from bigdl_tpu.optim.optim_method import LARS
    opt = _distri(0, optim=LARS(learning_rate=0.1), zero1=True)
    params, _ = opt.model.init_params(0)
    with pytest.raises(ValueError, match="zero1 cannot shard"):
        opt._wrap_optim(params)


def test_zero1_fsdp_mutually_exclusive():
    x, y = make_data()
    mesh = mesh_lib.create_mesh({"dp": 8})
    with pytest.raises(ValueError, match="mutually exclusive"):
        DistriOptimizer(make_model(0), (x, y), nn.MSECriterion(),
                        batch_size=64, mesh=mesh, zero1=True, fsdp=True)


# --------------------------------------------------------------------- #
# ZeRO-1 memory: optimizer state is REALLY sharded 1/N                   #
# --------------------------------------------------------------------- #
def test_zero1_opt_state_sharded_one_over_n():
    """The moment leaves carry P('dp') sharding metadata: each device
    holds exactly total/8 bytes of every non-scalar optimizer-state
    leaf after a dispatched step (the memory half of arXiv:2004.13336,
    enforced by shardings — not convention)."""
    opt = _distri(3, optim=Adam(1e-2), zero1=True)
    params, model_state = opt.model.init_params(0)
    optim = opt._wrap_optim(params)
    step_fn, _ = opt._build_step(params, optim)
    opt_state = optim.init_state(params)
    x, y = make_data()
    xb = jnp.asarray(x[:64])
    yb = jnp.asarray(y[:64])
    out = step_fn(params, opt_state, model_state, xb, yb,
                  jax.random.PRNGKey(0))
    new_opt = out[1]
    n_dev = 8
    checked = 0
    for k in ("m", "v"):
        for leaf in jax.tree_util.tree_leaves(new_opt[k]):
            assert leaf.ndim > 0
            total = leaf.size * leaf.dtype.itemsize
            shard = leaf.addressable_shards[0].data
            assert shard.nbytes * n_dev == total, (leaf.shape, shard.shape)
            checked += 1
    assert checked >= 4          # 2 sharded leaves + >=1 flat bucket, x2
    # params come back REPLICATED (full copy per device): zero1 shards
    # the update and the state, not the weights
    for leaf in jax.tree_util.tree_leaves(out[0]):
        assert leaf.addressable_shards[0].data.shape == leaf.shape


def test_zero1_checkpoint_roundtrip(tmp_path):
    """The shard-space optimizer state (dict of LISTS of moment shards)
    survives a manifest checkpoint save/restore cycle structurally
    intact, and a resumed zero1 run continues from the restored
    iteration."""
    x, y = make_data()
    mesh = mesh_lib.create_mesh({"dp": 8})
    model = make_model(3)
    opt = (DistriOptimizer(model, (x, y), nn.MSECriterion(),
                           batch_size=64, mesh=mesh, zero1=True)
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(2))
           .set_checkpoint(str(tmp_path), trigger=Trigger.every_epoch()))
    opt.optimize()
    assert opt.state.iteration == 8

    # fresh optimizer over the SAME model (matching module names):
    # restore must hand back the shard-space opt state and keep going
    opt2 = (DistriOptimizer(model, (x, y), nn.MSECriterion(),
                            batch_size=64, mesh=mesh, zero1=True)
            .set_optim_method(Adam(1e-2))
            .set_checkpoint(str(tmp_path)))
    restored = opt2.load_checkpoint()
    assert restored is not None
    params, opt_state, _ = restored
    assert set(opt_state) == {"step", "m", "v"}
    for k in ("m", "v"):
        assert set(opt_state[k]) == {"leaves", "flat"}
        assert len(opt_state[k]["leaves"]) == 2      # 2 dim0-sharded
        assert len(opt_state[k]["flat"]) == 1        # 1 padded bucket
    assert int(np.asarray(opt_state["step"])) == 8
    opt3 = (DistriOptimizer(model, (x, y), nn.MSECriterion(),
                            batch_size=64, mesh=mesh, zero1=True)
            .set_optim_method(Adam(1e-2))
            .set_end_when(Trigger.max_epoch(4))
            .set_checkpoint(str(tmp_path)))
    opt3.optimize()
    assert opt3.state.iteration == 16


def test_zero1_layout_flat_bucket_plan():
    params = {"w1": jnp.zeros((16, 4)),        # dim0 shardable
              "b1": jnp.zeros((5,)),           # -> flat bucket
              "w2": jnp.zeros((3, 3)),         # -> flat bucket
              "s": jnp.zeros(())}              # scalar -> flat bucket
    z1 = Zero1Layout(params, 8)
    assert len(z1.sharded_idx) == 1
    assert len(z1.buckets) == 1
    dt, idxs, sizes, pad = z1.buckets[0]
    assert sorted(sizes) == [1, 5, 9]
    assert (sum(sizes) + pad) % 8 == 0
    gss = z1.global_shard_space(params)
    assert len(gss["leaves"]) == 1 and len(gss["flat"]) == 1
    assert gss["flat"][0].shape[0] == sum(sizes) + pad
    assert z1.spec_tree() == {"leaves": [P("dp")], "flat": [P("dp")]}
    assert "flat-bucketed" in z1.describe()

    # bucket_bytes splits the flat leaves into multiple buckets
    z2 = Zero1Layout(params, 8, bucket_bytes=24)
    assert len(z2.buckets) >= 2


# --------------------------------------------------------------------- #
# GradBucketer                                                           #
# --------------------------------------------------------------------- #
def test_bucketer_pack_unpack_roundtrip_bitwise():
    rng = np.random.RandomState(0)
    tree = {"a": jnp.asarray(rng.randn(33, 5).astype(np.float32)),
            "b": jnp.asarray(rng.randn(7).astype(np.float32)),
            "c": {"d": jnp.asarray(rng.randn(4, 4, 4).astype(np.float32)),
                  "e": jnp.asarray(rng.randn(2).astype(np.float32)).astype(jnp.bfloat16)}}
    for order in ("backward", "forward", "size"):
        bk = GradBucketer(tree, bucket_bytes=256, order=order)
        out = bk.unpack(bk.pack(tree))
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_bucketer_respects_bucket_bytes_and_dtype():
    rng = np.random.RandomState(1)
    tree = {"a": jnp.asarray(rng.randn(64).astype(np.float32)),   # 256 B
            "b": jnp.asarray(rng.randn(64).astype(np.float32)),
            "c": jnp.asarray(rng.randn(64).astype(np.float32)).astype(jnp.bfloat16)}
    bk = GradBucketer(tree, bucket_bytes=512)
    # a+b fit one 512-byte bucket; c's dtype forces its own bucket
    assert len(bk) == 2
    small = GradBucketer(tree, bucket_bytes=256)
    assert len(small) == 3
    # a leaf larger than bucket_bytes still gets (its own) bucket
    huge = GradBucketer({"x": jnp.zeros((1024,), jnp.float32)},
                        bucket_bytes=64)
    assert len(huge) == 1
    with pytest.raises(ValueError, match="unknown bucket order"):
        GradBucketer(tree, order="random")


def test_bucketed_allreduce_bitwise_matches_monolithic():
    """Per-bucket pmean over the same replicas is elementwise identical
    to the monolithic exchange — final params bit for bit."""
    p0 = train_params(_distri(5))
    p1 = train_params(_distri(5, bucket_bytes=256))
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------- #
# fp16 compression: exact bytes + bounded numerics                       #
# --------------------------------------------------------------------- #
def _train_with_recorder(seed, epochs=5, **kw):
    rec = Recorder(sinks=[InMemorySink()])
    opt = _distri(seed, epochs=epochs, **kw).set_telemetry(rec,
                                                           health=False)
    opt.optimize()
    set_recorder(None)
    steps = [r for r in rec.sinks[0].records if r.get("type") == "step"]
    return opt, steps


def test_fp16_wire_bytes_exactly_half_static_accounting():
    """compress='fp16' halves the accounted on-the-wire payload EXACTLY
    (2-byte elements over the same ring): asserted on the trace-time
    gauges for both the bucketed all-reduce and the zero1 scatter."""
    _, steps = _train_with_recorder(7, epochs=2, bucket_bytes=256,
                                    compress="fp16")
    g = steps[-1]["gauges"]
    assert g["collective/allreduce_wire_bytes"] * 2 == \
        g["collective/allreduce_bytes"]
    assert g["collective/buckets"] >= 2

    _, steps = _train_with_recorder(7, epochs=2, zero1=True,
                                    compress="fp16")
    g = steps[-1]["gauges"]
    assert g["collective/reduce_scatter_wire_bytes"] * 2 == \
        g["collective/reduce_scatter_bytes"]
    # the param all-gather is NOT compressed (weights keep full
    # precision on the fetch): raw == wire there
    assert g["collective/allgather_wire_bytes"] == \
        g["collective/allgather_bytes"]


def test_fp16_loss_curve_drift_bounded():
    """Documented tolerance, not hidden: the fp16-mean exchange drifts
    the loss curve by well under 1% relative per epoch on this config
    (pre-scaling by 1/n keeps the ring sum inside fp16 range)."""
    losses = {}
    for compress in (None, "fp16"):
        x, y = make_data(seed=2)
        mesh = mesh_lib.create_mesh({"dp": 8})
        opt = (DistriOptimizer(make_model(5), (x, y), nn.MSECriterion(),
                               batch_size=64, mesh=mesh,
                               bucket_bytes=256, compress=compress)
               .set_optim_method(SGD(learning_rate=0.05)))
        curve = []
        opt.set_end_when(Trigger.max_epoch(5))
        orig = opt._run_epoch

        def spy(*a, _orig=orig, _curve=curve, _opt=opt, **k):
            out = _orig(*a, **k)
            _curve.append(float(_opt.state.loss))
            return out

        opt._run_epoch = spy
        opt.optimize()
        losses[compress] = curve
    l32, l16 = losses[None], losses["fp16"]
    assert len(l32) == len(l16) == 5
    for a, b in zip(l32, l16):
        assert abs(a - b) / max(abs(a), 1e-9) < 1e-2, (l32, l16)


# --------------------------------------------------------------------- #
# HLO-accounted payload drop (the acceptance number)                     #
# --------------------------------------------------------------------- #
def _transformer_step_wire(**kw):
    from bigdl_tpu.observability.collectives import hlo_collective_ops
    import bigdl_tpu.models.transformer as T
    dp = 8
    mesh = mesh_lib.create_mesh({"dp": dp})
    model = T.build("tiny")
    B, S = dp * 2, 64
    x = np.zeros((B, S), np.int32)
    y = np.ones((B, S), np.int32)
    opt = DistriOptimizer(model, (x, y),
                          nn.CrossEntropyCriterion(zero_based_label=True),
                          batch_size=B, mesh=mesh, **kw)
    opt.set_optim_method(Adam(1e-3))
    params, _ = model.init_params(0)
    optim = opt._wrap_optim(params)
    step_fn, _ = opt._build_step(params, optim)
    opt_state = optim.init_state(params)
    lowered = step_fn.lower(params, opt_state, {}, jnp.asarray(x),
                            jnp.asarray(y), jax.random.PRNGKey(0))
    ops = hlo_collective_ops(lowered.compile().as_text(), dp)
    return sum(w for _, _, w in ops), ops


def test_bucketed_fp16_drops_hlo_wire_bytes_40pct_on_transformer():
    """Acceptance: on the tiny-transformer dryrun config the
    bucketed+fp16 step's HLO-accounted collective payload is >=40%
    below the monolithic fp32 baseline (measured: 50.0%, the fp16
    theoretical), and the zero1 step compiles to real reduce-scatter +
    all-gather collectives."""
    base, _ = _transformer_step_wire()
    buck, _ = _transformer_step_wire(bucket_bytes=1 << 20,
                                     compress="fp16")
    assert buck <= 0.6 * base, (buck, base)
    z1, z1_ops = _transformer_step_wire(zero1=True, compress="fp16")
    kinds = {op for op, _, _ in z1_ops}
    assert "reduce-scatter" in kinds and "all-gather" in kinds, kinds
    # scatter fp16 + gather fp32 = 75% of the all-reduce volume
    assert z1 <= 0.8 * base, (z1, base)


# --------------------------------------------------------------------- #
# sharding-coverage counters + comm renderer                             #
# --------------------------------------------------------------------- #
def test_unsharded_leaf_counter_and_log(caplog):
    import logging
    from bigdl_tpu.parallel.allreduce import (allgather_params,
                                              reduce_scatter_gradients)
    mesh = mesh_lib.create_mesh({"dp": 8})
    rec = Recorder(sinks=[InMemorySink()])
    set_recorder(rec)
    try:
        grads = {"even": jnp.ones((8, 4)), "odd": jnp.ones((5, 4))}

        def f(g):
            sc = reduce_scatter_gradients(g, "dp", mean=False)
            return allgather_params(sc, "dp", mask={"even": True,
                                                    "odd": False})

        with caplog.at_level(logging.DEBUG,
                             logger="bigdl_tpu.parallel.allreduce"):
            jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(),
                                  check_vma=False))(grads)
        snap = rec.snapshot()["counters"]
        assert snap.get("comm/unsharded_leaves") == 1.0     # 'odd'
        assert snap.get("comm/ungathered_leaves") == 1.0
        assert any("odd" in r.message for r in caplog.records)
    finally:
        set_recorder(None)


def test_trace_summary_comm_renders(tmp_path):
    import trace_summary as ts
    rec = {"type": "step", "step": 3,
           "gauges": {"collective/allreduce_bytes": 2048.0,
                      "collective/allreduce_wire_bytes": 1024.0,
                      "collective/bytes_per_step": 2048.0,
                      "collective/wire_bytes_per_step": 1024.0,
                      "collective/buckets": 4.0},
           "counters": {"collective/bytes_total": 6144.0,
                        "collective/wire_bytes_total": 3072.0,
                        "comm/unsharded_leaves": 2.0}}
    f = tmp_path / "t.jsonl"
    f.write_text(json.dumps(rec) + "\n")
    steps, _ = ts.load_steps(str(f))
    buf = io.StringIO()
    ts.summarize_comm(steps, out=lambda *a: print(*a, file=buf))
    text = buf.getvalue()
    assert "allreduce" in text and "0.50x" in text
    assert "gradient buckets/step: 4" in text
    assert "saved" in text and "50.0%" in text
    assert "comm/unsharded_leaves  2" in text
    # empty input degrades gracefully
    buf2 = io.StringIO()
    ts.summarize_comm([], out=lambda *a: print(*a, file=buf2))
    assert "no step records" in buf2.getvalue()
