"""CI proxy for the composed dp×fsdp×tp×pp(+ep) parallelism work
(ISSUE 14) while the hardware bench backend is down.

Runs the 8-device CPU dryruns of the composed-mesh configurations and
asserts the CPU-measurable claims:

  1. Composed pipeline mesh (dp4×pp2) with the FULL roofline stack —
     zero1 sharded update + bucketed fp16 dp collectives + fused SGD
     kernel + bubble-overlap gradient chunks — trains, and the
     taxonomy holds: zero1-only and bucketed-fp32-only are BITWISE
     equal to the plain pp×dp run; fp16/overlap are tight-allclose.
  2. The dp-group bucketed-fp16 exchange drops >= 40% of the dp-group
     HLO wire payload vs the fp32 monolithic exchange on the SAME
     composed mesh (measured two ways: exact trace-time
     comm/group.dp.* gauges AND the replica-group HLO attribution).
  3. zero1 over the dp axis of the pp-sharded model: optimizer moments
     live P(('pp','dp')) / P('dp') — 1/(pp·dp) and 1/dp per device by
     sharding METADATA.
  4. GSPMD zero1-by-annotation on dp4×tp2: 1/(dp·tp)-ish moment bytes
     per device, per-group HLO attribution splits dp from tp volume.
  5. MoE expert parallelism composed with the batch axes
     (dp2×fsdp2×ep2): trains with single-device parity, ep group
     accounted separately.
  6. Elastic: plan_mesh shrinks the CHEAPEST axis of the composed
     template (dp4×tp2 on 4 devices -> dp2×tp2, never dp4×tp1).

dp2×tp2×pp2 — pp with tp as an AUTO axis inside the partial-manual
shard_map — runs first: it trains on jax 0.9.0 (the PartitionId
lowering limit of jax 0.4 that an earlier round recorded as
``blocked_by_jax04_partition_id`` is gone), so a failure there is a
failure of this smoke.

Emits ONE parseable JSON line (last line) and writes no file in the
repo.  Every number is a count from a CPU run (bytes on the wire, bit
parity, sharding metadata) — none is a device number.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np
import jax

from bigdl_tpu.kernels import fused_optim
from bigdl_tpu.models import transformer as T
from bigdl_tpu.observability import Recorder
from bigdl_tpu.observability.collectives import hlo_group_breakdown
from bigdl_tpu.optim import Adam, SGD
from bigdl_tpu.parallel import mesh as mesh_lib
from bigdl_tpu.parallel.pipeline import PipelineLMTrainer
from bigdl_tpu.parallel.spmd import SpmdTrainer
from bigdl_tpu.elastic import plan_mesh

# CPU smoke: the fused optimizer kernels run through the Pallas
# interpreter (they lower through Mosaic unless told otherwise)
fused_optim._FORCE_INTERPRET = True

STEPS = 5


def _model(**kw):
    cfg = dict(dropout=0.0, n_layers=4, d_model=64, n_heads=2, d_ff=128,
               vocab_size=64, max_len=32)
    cfg.update(kw)
    return T.build("tiny", **cfg)


def _data(batch=16, seed=0):
    rng = np.random.RandomState(seed)
    tok = rng.randint(0, 64, (batch, 16)).astype(np.int32)
    return tok, np.roll(tok, -1, axis=1).astype(np.int32)


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _bitwise(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def _max_delta(a, b):
    return max(float(np.abs(x.astype(np.float64) - y).max())
               for x, y in zip(a, b))


def run_pipeline(axes, optim_fn, rec=None, **kw):
    tok, tgt = _data()
    mesh = mesh_lib.create_mesh(axes)
    tr = PipelineLMTrainer(_model(), optim_fn(), mesh, n_microbatches=4,
                           seed=3, **kw)
    if rec is not None:
        tr.set_telemetry(rec)
    tr.init()
    losses = [float(tr.step(tok, tgt)) for _ in range(STEPS)]
    return losses, tr


def pipeline_hlo_dp_wire(tr):
    """dp-group wire bytes of the compiled pipeline step, attributed by
    replica groups."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tok, tgt = _data()
    sh = NamedSharding(tr.mesh, P("dp"))
    tok = jax.device_put(np.asarray(tok), sh)
    tgt = jax.device_put(np.asarray(tgt), sh)
    hlo = tr._step_fn.lower(tr.params, tr.opt_state, tok,
                            tgt).compile().as_text()
    groups = hlo_group_breakdown(hlo, tr.mesh)
    return groups.get("dp", {}).get("wire_bytes", 0.0), groups


def main():
    out = {"bench": "compose_proxy_smoke", "platform": "cpu",
           "devices": 8, "configs": {}}

    # -- 0. the pp×tp composed mesh ------------------------------------ #
    tp_losses, _ = run_pipeline({"dp": 2, "tp": 2, "pp": 2}, lambda: SGD(
        learning_rate=0.1))
    assert tp_losses[-1] < tp_losses[0], tp_losses
    out["configs"]["dp2_tp2_pp2"] = {"status": "trained",
                                     "losses": tp_losses}
    print(f"[compose] dp2×tp2×pp2 pipeline trained: {tp_losses[0]:.4f}"
          f" -> {tp_losses[-1]:.4f}")

    # -- 1. composed pipeline mesh: parity taxonomy ------------------- #
    base_l, base_tr = run_pipeline({"dp": 4, "pp": 2},
                                   lambda: SGD(learning_rate=0.1))
    base_p = _leaves(base_tr.merge())
    # single-DEVICE parity: the same GPipe program on a pp1 mesh over
    # one device — dp/pp partition the reductions, so documented-ulp
    tok, tgt = _data()
    one = PipelineLMTrainer(
        _model(), SGD(learning_rate=0.1),
        mesh_lib.create_mesh({"pp": 1}, jax.devices()[:1]),
        n_microbatches=4, seed=3).init()
    one_l = [float(one.step(tok, tgt)) for _ in range(STEPS)]
    np.testing.assert_allclose(base_l, one_l, rtol=1e-4)
    d_one = _max_delta(base_p, _leaves(one.merge()))
    assert d_one < 1e-5, d_one
    out["configs"]["dp4_pp2_pipeline_vs_single_device"] = {
        "max_param_delta": d_one, "losses_8dev": base_l,
        "losses_1dev": one_l}
    print(f"[compose] dp4×pp2 vs single device: max|Δparam| "
          f"{d_one:.2e} after {STEPS} steps (documented-ulp class)")
    z1_l, z1_tr = run_pipeline({"dp": 4, "pp": 2},
                               lambda: SGD(learning_rate=0.1),
                               zero1=True)
    assert _bitwise(base_p, _leaves(z1_tr.merge())), \
        "zero1 SGD must be bitwise vs the plain pp×dp path"
    assert z1_l == base_l
    bk_l, bk_tr = run_pipeline({"dp": 4, "pp": 2},
                               lambda: SGD(learning_rate=0.1),
                               bucket_bytes=1 << 16)
    assert _bitwise(base_p, _leaves(bk_tr.merge())), \
        "bucketed fp32 must be bitwise vs the monolithic exchange"
    full_l, full_tr = run_pipeline(
        {"dp": 4, "pp": 2}, lambda: SGD(learning_rate=0.1), zero1=True,
        bucket_bytes=1 << 16, compress="fp16", fused_optim=True,
        overlap_grad_chunks=2)
    d_full = _max_delta(base_p, _leaves(full_tr.merge()))
    assert np.isfinite(full_l).all() and full_l[-1] < full_l[0]
    assert d_full < 5e-2, d_full      # fp16 wire + chunk reassociation
    out["configs"]["dp4_pp2_pipeline"] = {
        "zero1_sgd_bitwise": True, "bucketed_fp32_bitwise": True,
        "full_stack_losses": full_l, "full_stack_max_param_delta":
        d_full, "overlap_grad_chunks": 2}

    # -- 2. dp-group fp16 wire drop on the composed mesh -------------- #
    rec_plain = Recorder()
    _, tr_plain = run_pipeline({"dp": 4, "pp": 2},
                               lambda: SGD(learning_rate=0.1),
                               rec=rec_plain)
    rec_fp16 = Recorder()
    _, tr_fp16 = run_pipeline({"dp": 4, "pp": 2},
                              lambda: SGD(learning_rate=0.1),
                              rec=rec_fp16, bucket_bytes=1 << 16,
                              compress="fp16")
    g_plain = rec_plain.snapshot()["gauges"]
    g_fp16 = rec_fp16.snapshot()["gauges"]
    dp_plain = g_plain["comm/group.dp.wire_bytes_per_step"]
    dp_fp16 = g_fp16["comm/group.dp.wire_bytes_per_step"]
    drop_traced = 1.0 - dp_fp16 / dp_plain
    hlo_plain, _ = pipeline_hlo_dp_wire(tr_plain)
    hlo_fp16, groups_fp16 = pipeline_hlo_dp_wire(tr_fp16)
    drop_hlo = 1.0 - hlo_fp16 / hlo_plain
    print(f"[compose] dp-group wire/step: plain {dp_plain:.0f}B "
          f"-> fp16 {dp_fp16:.0f}B (traced drop {drop_traced:.1%}, "
          f"HLO drop {drop_hlo:.1%})")
    assert drop_traced >= 0.40, drop_traced
    assert drop_hlo >= 0.40, drop_hlo
    out["configs"]["dp4_pp2_fp16_drop"] = {
        "dp_wire_plain": dp_plain, "dp_wire_fp16": dp_fp16,
        "drop_traced": drop_traced, "drop_hlo": drop_hlo,
        "hlo_groups_fp16": {k: v["wire_bytes"]
                            for k, v in groups_fp16.items()},
        "pp_wire": g_fp16.get("comm/group.pp.wire_bytes_per_step")}

    # -- 3. zero1 shard-space moments: 1/(pp·dp) by METADATA ---------- #
    _, z1a_tr = run_pipeline({"dp": 4, "pp": 2}, lambda: Adam(1e-3),
                             zero1=True)
    blocks_tot = blocks_per = rest_tot = rest_per = 0
    for leaf in jax.tree_util.tree_leaves(z1a_tr.opt_state["blocks"]):
        if leaf.ndim == 0:
            continue
        blocks_tot += leaf.size * leaf.dtype.itemsize
        blocks_per += max(s.data.size for s in
                          leaf.addressable_shards) * leaf.dtype.itemsize
    for leaf in jax.tree_util.tree_leaves(z1a_tr.opt_state["rest"]):
        if leaf.ndim == 0:
            continue
        rest_tot += leaf.size * leaf.dtype.itemsize
        rest_per += max(s.data.size for s in
                        leaf.addressable_shards) * leaf.dtype.itemsize
    assert blocks_per * 8 == blocks_tot, (blocks_per, blocks_tot)
    assert rest_per * 4 == rest_tot, (rest_per, rest_tot)
    out["configs"]["dp4_pp2_zero1_opt_state"] = {
        "blocks_bytes_total": blocks_tot,
        "blocks_bytes_per_device": blocks_per,
        "rest_bytes_total": rest_tot,
        "rest_bytes_per_device": rest_per}
    print(f"[compose] zero1 moments: blocks {blocks_tot}B -> "
          f"{blocks_per}B/device (1/8), rest {rest_tot}B -> "
          f"{rest_per}B/device (1/4)")

    # -- 4. GSPMD zero1-by-annotation on dp4×tp2 ---------------------- #
    tok, tgt = _data(seed=1)
    tr_tp = SpmdTrainer(_model(n_layers=2), Adam(1e-3),
                        mesh=mesh_lib.create_mesh("dp4,tp2"),
                        fsdp=False, seed=0, zero1=True,
                        zero1_min_size=0)
    tr_tp.init()
    tp_l = [float(tr_tp.step(tok, tgt)) for _ in range(STEPS)]
    tot = per = 0
    for leaf in jax.tree_util.tree_leaves(tr_tp.opt_state):
        if leaf.ndim == 0:
            continue
        tot += leaf.size
        per += max(s.data.size for s in leaf.addressable_shards)
    ref_tp = SpmdTrainer(_model(n_layers=2), Adam(1e-3),
                         mesh=mesh_lib.create_mesh("dp4,tp2"),
                         fsdp=False, seed=0)
    ref_tp.init()
    ref_l = [float(ref_tp.step(tok, tgt)) for _ in range(STEPS)]
    np.testing.assert_allclose(tp_l, ref_l, rtol=1e-4)
    groups_tp = tr_tp.account_collectives(tok, tgt)["groups"]
    assert per / tot < 1 / 8 + 0.01
    assert groups_tp["dp"]["wire_bytes"] > 0
    assert groups_tp["tp"]["wire_bytes"] > 0
    out["configs"]["dp4_tp2_spmd_zero1"] = {
        "opt_moment_fraction_per_device": per / tot,
        "losses": tp_l,
        "hlo_groups": {k: v["wire_bytes"]
                       for k, v in groups_tp.items()}}
    print(f"[compose] spmd zero1 dp4×tp2: moments {per / tot:.4f} "
          f"per device (1/8 = {1 / 8:.4f}), groups "
          f"{sorted(groups_tp)}")
    tr_tp.detach()
    ref_tp.detach()

    # -- 5. MoE ep composed with the batch axes ----------------------- #
    moe = dict(n_layers=2, moe_experts=4, moe_top_k=1)
    tr_moe = SpmdTrainer(_model(**moe), Adam(1e-3),
                         mesh=mesh_lib.create_mesh("dp2,fsdp2,ep2"),
                         fsdp=True, min_fsdp_size=1024, seed=0)
    tr_moe.init()
    moe_l = [float(tr_moe.step(tok, tgt)) for _ in range(STEPS)]
    tr_one = SpmdTrainer(_model(**moe), Adam(1e-3),
                         mesh=mesh_lib.create_mesh(
                             {"dp": 1}, jax.devices()[:1]),
                         fsdp=False, seed=0)
    tr_one.init()
    one_l = [float(tr_one.step(tok, tgt)) for _ in range(STEPS)]
    np.testing.assert_allclose(moe_l, one_l, rtol=5e-4)
    d_moe = _max_delta(_leaves(tr_moe.params), _leaves(tr_one.params))
    assert d_moe < 1e-3, d_moe
    groups_moe = tr_moe.account_collectives(tok, tgt)["groups"]
    assert groups_moe.get("ep", {}).get("wire_bytes", 0) > 0, \
        "ep group must be separately attributed"
    out["configs"]["dp2_fsdp2_ep2_moe"] = {
        "losses": moe_l, "single_device_max_param_delta": d_moe,
        "hlo_groups": {k: v["wire_bytes"]
                       for k, v in groups_moe.items()}}
    print(f"[compose] MoE dp2×fsdp2×ep2: single-device parity "
          f"max|Δparam| {d_moe:.2e}, ep wire "
          f"{groups_moe['ep']['wire_bytes']:.0f}B/step")
    tr_moe.detach()
    tr_one.detach()

    # -- 6. elastic: the cheapest-axis shrink ------------------------- #
    assert plan_mesh(4, {"dp": 4, "tp": 2}) == {"dp": 2, "tp": 2}
    assert plan_mesh(8, {"dp": 2, "fsdp": 2, "tp": 2, "pp": 2}) == \
        {"dp": 1, "fsdp": 2, "tp": 2, "pp": 2}
    out["configs"]["elastic_cheapest_axis"] = {
        "dp4_tp2_on_4": plan_mesh(4, {"dp": 4, "tp": 2}),
        "dp2_fsdp2_tp2_pp2_on_8":
            plan_mesh(8, {"dp": 2, "fsdp": 2, "tp": 2, "pp": 2})}

    print("[compose] all composed-mesh assertions passed")
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
