"""Bytes-on-wire analysis of the compiled DistriOptimizer step:
the partitioned HLO's collective traffic must
match the ring all-reduce theory 2*G*(n-1)/n that BASELINE.md's
scaling-efficiency row relies on."""
import os
import re
import sys

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from bigdl_tpu import nn
from bigdl_tpu.optim import SGD
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib


def _compiled_step(fsdp=False):
    from collective_volume import collective_bytes
    dp = 8
    mesh = mesh_lib.create_mesh({"dp": dp})
    model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 8), nn.LogSoftMax())
    x = np.zeros((dp * 4, 64), np.float32)
    y = np.ones((dp * 4,), np.float32)
    opt = DistriOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                          batch_size=dp * 4, mesh=mesh, fsdp=fsdp)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    params, _ = model.init_params(0)
    optim = opt._wrap_optim(params)
    step_fn, _ = opt._build_step(params, optim)
    opt_state = optim.init_state(params)
    lowered = step_fn.lower(params, opt_state, {}, jnp.asarray(x),
                            jnp.asarray(y), jax.random.PRNGKey(0))
    hlo = lowered.compile().as_text()
    grad_bytes = sum(int(np.prod(p.shape)) * 4
                     for p in jax.tree_util.tree_leaves(params))
    return collective_bytes(hlo, dp), grad_bytes, dp


def test_dp_allreduce_volume_matches_ring_theory():
    ops, grad_bytes, dp = _compiled_step(fsdp=False)
    assert any(op == "all-reduce" for op, _, _ in ops)
    wire = sum(w for _, _, w in ops)
    theory = 2 * grad_bytes * (dp - 1) / dp
    # XLA fuses the gradient all-reduce into few ops; the loss/BN pmean
    # adds a few scalar reduces, so allow a small overhead margin
    assert theory * 0.95 <= wire <= theory * 1.25, (wire, theory)


def test_fsdp_step_has_gather_and_scatter():
    ops, grad_bytes, dp = _compiled_step(fsdp=True)
    kinds = {op for op, _, _ in ops}
    # params ride all-gather; grads ride reduce-scatter (or an equivalent
    # all-reduce when XLA chooses); traffic must stay within ~2x of the
    # dp all-reduce volume (comm-equivalence of the partitioned scheme)
    assert "all-gather" in kinds, kinds
    wire = sum(w for _, _, w in ops)
    theory = 2 * grad_bytes * (dp - 1) / dp
    assert wire <= theory * 2.2, (wire, theory)


def test_flagship_spmd_step_collective_budget():
    """Layout regression guard: the tiny-preset SpmdTrainer step on the
    dp2 x fsdp2 x tp2 mesh, batch sharded the way step() shards it,
    compiles to a bounded set of collectives (snapshot on jax 0.9.0:
    40 all-gather + 16 all-reduce — fsdp's parameter gathers — and 2.8 MB
    on wire with replica-group-aware ring accounting; the same plan
    before and after attention became a shard_map island in PR 21).
    A silently broken pspec (e.g. losing the megatron pairing so GSPMD
    all-gathers activations everywhere) shows up here as a big jump."""
    import jax.numpy as jnp
    from collections import Counter
    from collective_volume import collective_bytes
    import bigdl_tpu.models.transformer as T
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    from bigdl_tpu.optim import SGD

    mesh = mesh_lib.create_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    tr = SpmdTrainer(T.build("tiny"), SGD(learning_rate=0.1), mesh=mesh,
                     fsdp=True, seed=0, min_fsdp_size=1).init()
    x = np.zeros((4, 64), np.int32)
    y = np.ones((4, 64), np.int32)
    sh = tr._batch_sharding()
    lowered = tr._step_fn.lower(
        tr.params, tr.opt_state, jax.device_put(jnp.asarray(x), sh),
        jax.device_put(jnp.asarray(y), sh), jax.random.PRNGKey(0))
    hlo = lowered.compile().as_text()
    ops = collective_bytes(hlo, 8)
    counts = Counter(op for op, _, _ in ops)
    wire = sum(w for _, _, w in ops)
    # snapshot is partitioner-version dependent; the guard's job is
    # catching order-of-magnitude jumps from a broken pspec, so the bound
    # sits above known-good snapshots
    assert counts["all-reduce"] <= 50, counts
    assert sum(counts.values()) <= 70, counts
    assert wire < 8e6, wire
