"""CI smoke for the sharded embedding subsystem (ISSUE 18).

Two legs, both on CPU:

  1. **Train leg** — synthetic MovieLens ratings through the ragged-ID
     sharded pipeline into models/two_tower.py: eval loss decreases
     over 3 epochs, and a mid-epoch cursor snapshot replays the
     remaining batches bit-identically on a fresh dataset.
  2. **8-virtual-device dryrun** — ShardedEmbeddingBag forward AND
     backward bitwise-equal to the single-device dense-gather
     reference; the host dedup stage reduces the ids crossing the
     all-to-all (asserted on the exchanged-slot gauges); the
     partitioned HLO of the sharded lookup contains the two all-to-all
     legs.

Counts that repeat exactly on any backend: lookup-exchange bytes with
vs without dedup, int8 vs f32 serving-table bytes, touched-rows vs
dense gradient-update bytes.  Emits ONE parseable JSON line (last
line) and writes no file in the repo.
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
import jax.numpy as jnp

from bigdl_tpu.data import movielens as ml
from bigdl_tpu.embedding import (ShardedEmbeddingBag, dense_bag,
                                 reference_table, dedup_for_mesh,
                                 exchange_ids_without_dedup,
                                 SparseRowGrad, quantize_table,
                                 table_bytes, quantized_table_bytes)
from bigdl_tpu.models import two_tower
from bigdl_tpu.nn.criterion import BCECriterion
from bigdl_tpu.observability.collectives import hlo_collective_ops
from bigdl_tpu.observability.recorder import Recorder, set_recorder
from bigdl_tpu.optim.optimizer import Optimizer
from bigdl_tpu.optim.optim_method import SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel.mesh import create_mesh


def train_leg(out, tmp):
    ratings = ml._synthetic()
    train, _ = ml.leave_one_out(ratings)
    shards = ml.write_rating_shards(os.path.join(tmp, "ml"), train,
                                    n_files=4)
    model = two_tower.build(int(ratings[:, 0].max()),
                            int(ratings[:, 1].max()), 16)

    def eval_loss(params):
        ds = ml.sharded_rating_dataset(shards, batch_size=64,
                                       n_workers=2, seed=0)
        crit = BCECriterion()
        tot, n = 0.0, 0
        for x, y in ds.data(train=False, epoch=0):
            yhat, _ = model.run(params,
                                (jnp.asarray(x[0]), jnp.asarray(x[1])),
                                training=False)
            tot += float(crit.forward(yhat, jnp.asarray(y))) * len(y)
            n += len(y)
        return tot / n

    p0, _ = model.init_params(3)
    loss_before = eval_loss(p0)
    ds = ml.sharded_rating_dataset(shards, batch_size=64, n_workers=2,
                                   seed=7)
    opt = Optimizer(model, ds, BCECriterion(), seed=3)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(Trigger.max_epoch(3))
    trained = opt.optimize()
    loss_after = eval_loss(trained._params)
    assert loss_after < loss_before, (loss_before, loss_after)

    # mid-epoch cursor snapshot replays bit-identically
    mk = lambda: ml.sharded_rating_dataset(shards, batch_size=64,
                                           n_workers=2, seed=7)
    ds1 = mk()
    it = ds1.data(train=True, epoch=9)
    for _ in range(5):
        next(it)
    cursor = ds1.state()
    rest1 = list(it)
    ds2 = mk()
    ds2.restore(cursor)
    rest2 = list(ds2.data(train=True, epoch=9))
    assert len(rest1) == len(rest2) > 0
    for (xa, ya), (xb, yb) in zip(rest1, rest2):
        assert np.array_equal(xa[0], xb[0])
        assert np.array_equal(xa[1], xb[1])
        assert np.array_equal(ya, yb)

    out["two_tower"] = {"loss_before": loss_before,
                        "loss_after": loss_after, "epochs": 3,
                        "cursor_resume_batches": len(rest1),
                        "cursor_resume_bitwise": True}
    print(f"[rec] two-tower: loss {loss_before:.4f} -> {loss_after:.4f}, "
          f"cursor resume bitwise over {len(rest1)} batches")


def dryrun_leg(out):
    V, D, B, L = 100, 16, 32, 12
    mesh = create_mesh({"tp": 8})
    bag = ShardedEmbeddingBag(V, D, mesh=mesh, axis="tp")
    params, _ = bag.init_params(0)
    ids = np.random.RandomState(3).randint(0, 21, (B, L)).astype(np.int32)
    # hot batch: ids drawn from only 20 distinct values -> dedup bites

    # bitwise forward/backward vs the dense reference
    yd = dense_bag(reference_table(params, bag), jnp.asarray(ids))
    ys = jax.jit(lambda p: bag.run(p, jnp.asarray(ids))[0])(params)
    assert np.array_equal(np.asarray(ys), np.asarray(yd))
    gout = jnp.asarray(np.random.RandomState(7).randn(B, D)
                       .astype(np.float32))
    gs = jax.jit(jax.grad(lambda p: jnp.vdot(
        bag.run(p, jnp.asarray(ids))[0], gout)))(params)
    gd = jax.jit(jax.grad(lambda p: jnp.vdot(
        dense_bag(p[bag.name]["weight"][:V], jnp.asarray(ids)),
        gout)))(params)
    assert np.array_equal(np.asarray(gs[bag.name]["weight"])[:V],
                          np.asarray(gd[bag.name]["weight"])[:V])
    print("[rec] sharded bag fwd+bwd bitwise vs dense reference (tp8)")

    # all-to-all in the partitioned HLO
    hlo = (jax.jit(lambda p: bag.run(p, jnp.asarray(ids))[0])
           .lower(params).compile().as_text())
    a2a = [o for o, _, _ in hlo_collective_ops(hlo, 8)
           if o == "all-to-all"]
    assert len(a2a) >= 2, a2a

    # dedup reduces the exchanged ids AND the accounted wire bytes
    rec = Recorder(annotate=False)
    old = set_recorder(rec)
    try:
        bag.run(params, jnp.asarray(ids))
        plain_bytes = rec.gauge_value("embedding/lookup_exchange_bytes")
        plain_slots = rec.gauge_value("embedding/exchange_ids")
        rec.reset_gauges("embedding/")
        uniq, inv = dedup_for_mesh(ids, 8, recorder=rec)
        bag.run(params, (jnp.asarray(uniq), jnp.asarray(inv)))
        dedup_bytes = rec.gauge_value("embedding/lookup_exchange_bytes")
        dedup_slots = rec.gauge_value("embedding/exchange_ids")
        dedup_ratio = rec.gauge_value("embedding/dedup_ratio")
    finally:
        set_recorder(old)
    n_raw = exchange_ids_without_dedup(ids)
    n_uniq = int((uniq >= 0).sum())
    assert n_uniq < n_raw, (n_uniq, n_raw)
    assert dedup_bytes < plain_bytes, (dedup_bytes, plain_bytes)
    yu = bag.run(params, (jnp.asarray(uniq), jnp.asarray(inv)))[0]
    assert np.array_equal(np.asarray(yu), np.asarray(yd))
    print(f"[rec] dedup: {n_raw} ids -> {n_uniq} unique, exchange "
          f"{plain_bytes:.0f}B -> {dedup_bytes:.0f}B per step")

    # serving-table and sparse-grad byte proxies
    w = reference_table(params, bag)
    q, scale = quantize_table(w)
    f32_b, i8_b = table_bytes(w), quantized_table_bytes(q, scale)
    touched = SparseRowGrad.from_dense(
        np.asarray(gd[bag.name]["weight"])[:V],
        np.unique(ids[ids > 0]) - 1)
    sparse_b, dense_b = touched.wire_bytes(), V * D * 4
    assert i8_b < f32_b and sparse_b < dense_b

    out["lookup_exchange"] = {
        "hlo_all_to_all_ops": len(a2a),
        "plain_bytes_per_step": plain_bytes,
        "dedup_bytes_per_step": dedup_bytes,
        "plain_id_slots": plain_slots, "dedup_id_slots": dedup_slots,
        "raw_ids": n_raw, "unique_ids": n_uniq,
        "dedup_ratio": dedup_ratio,
        "bitwise_vs_dense": True}
    out["table_bytes"] = {"f32": f32_b, "int8": i8_b,
                          "ratio": f32_b / i8_b}
    out["grad_update_bytes"] = {"dense": dense_b,
                                "touched_rows": sparse_b,
                                "ratio": dense_b / sparse_b}
    print(f"[rec] table {f32_b}B f32 -> {i8_b}B int8 "
          f"({f32_b / i8_b:.2f}x); grad {dense_b}B dense -> "
          f"{sparse_b}B touched-rows ({dense_b / sparse_b:.2f}x)")


def main():
    import tempfile
    out = {"metric": "rec_smoke", "platform": "cpu", "rc": 0,
           "cmd": "python scripts/rec_smoke.py"}
    with tempfile.TemporaryDirectory() as tmp:
        train_leg(out, tmp)
    dryrun_leg(out)
    out["ok"] = True
    print("[rec] all sharded-embedding assertions passed")
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
