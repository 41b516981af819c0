"""Distributed training on the virtual 8-device CPU mesh
(≙ DistriOptimizerSpec.scala). Checks dp == local result, fsdp == dp,
and gradient compression sanity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.optim import SGD, Trigger, LocalOptimizer
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib


def make_data(n=256, d=12, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, 1).astype(np.float32)
    y = (x @ w + 0.01 * rng.randn(n, 1)).astype(np.float32)
    return x, y


def make_model(seed=0):
    m = nn.Sequential(nn.Linear(12, 8), nn.Tanh(), nn.Linear(8, 1))
    m.reset(seed)
    return m


def train_params(opt):
    model = opt.optimize()
    return jax.tree_util.tree_map(np.asarray, model._params)


def test_eight_virtual_devices():
    assert len(jax.devices()) >= 8


def test_distri_matches_local():
    x, y = make_data()
    mesh = mesh_lib.create_mesh({"dp": 8})

    m1 = make_model(3)
    local = (LocalOptimizer(m1, (x, y), nn.MSECriterion(), batch_size=64)
             .set_optim_method(SGD(learning_rate=0.05))
             .set_end_when(Trigger.max_epoch(3)))
    p_local = train_params(local)

    m2 = make_model(3)
    distri = (DistriOptimizer(m2, (x, y), nn.MSECriterion(), batch_size=64,
                              mesh=mesh)
              .set_optim_method(SGD(learning_rate=0.05))
              .set_end_when(Trigger.max_epoch(3)))
    p_distri = train_params(distri)

    for a, b in zip(jax.tree_util.tree_leaves(p_local),
                    jax.tree_util.tree_leaves(p_distri)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_fsdp_matches_dp():
    x, y = make_data(seed=1)
    mesh = mesh_lib.create_mesh({"dp": 8})

    m1 = make_model(7)
    dp = (DistriOptimizer(m1, (x, y), nn.MSECriterion(), batch_size=64,
                          mesh=mesh)
          .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
          .set_end_when(Trigger.max_epoch(2)))
    p_dp = train_params(dp)

    m2 = make_model(7)
    fsdp = (DistriOptimizer(m2, (x, y), nn.MSECriterion(), batch_size=64,
                            mesh=mesh, fsdp=True)
            .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
            .set_end_when(Trigger.max_epoch(2)))
    p_fsdp = train_params(fsdp)

    for a, b in zip(jax.tree_util.tree_leaves(p_dp),
                    jax.tree_util.tree_leaves(p_fsdp)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_compressed_gradients_still_converge():
    x, y = make_data(seed=2)
    mesh = mesh_lib.create_mesh({"dp": 8})
    m = make_model(5)
    opt = (DistriOptimizer(m, (x, y), nn.MSECriterion(), batch_size=64,
                           mesh=mesh, compress="bf16")
           .set_optim_method(SGD(learning_rate=0.05))
           .set_end_when(Trigger.max_epoch(5)))
    opt.optimize()
    assert opt.state.loss < 1.0


def test_allreduce_primitives():
    from bigdl_tpu.parallel.allreduce import (allreduce_gradients,
                                              reduce_scatter_gradients,
                                              allgather_params)
    from jax.sharding import PartitionSpec as P
    mesh = mesh_lib.create_mesh({"dp": 8})

    def wrap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    x = jnp.arange(8.0)

    def f(v):
        return allreduce_gradients({"g": v}, "dp", mean=False)["g"]

    out = jax.jit(wrap(f, P("dp"), P()))(x)
    np.testing.assert_allclose(np.asarray(out), 28.0)

    def g(v):
        sc = reduce_scatter_gradients({"g": v}, "dp", mean=False)["g"]
        return allgather_params({"g": sc}, "dp")["g"]

    x2 = jnp.ones((8, 16))
    out2 = jax.jit(wrap(g, P("dp"), P()))(x2)
    np.testing.assert_allclose(np.asarray(out2), 8.0)


def test_fsdp_opt_state_specs_by_tree_path():
    """Moments inherit their OWN param's sharding, derived by tree-path
    correspondence: a replicated param sharing shape+dtype with a sharded
    one must NOT get its moments dim-0-sharded."""
    from bigdl_tpu.optim.distri_optimizer import fsdp_opt_state_specs
    from bigdl_tpu.optim import SGD
    from jax.sharding import PartitionSpec as P

    params = {"a": {"weight": jnp.zeros((8, 4))},
              "b": {"weight": jnp.zeros((8, 4))}}
    # sharding policy keeps b replicated although it is shape+dtype
    # identical to the sharded a — only the tree path can tell them apart
    shardable = {"a": {"weight": True}, "b": {"weight": False}}
    specs = fsdp_opt_state_specs(params, shardable,
                                 SGD(learning_rate=0.1, momentum=0.9))
    assert specs["velocity"]["a"]["weight"] == P("dp")
    assert specs["velocity"]["b"]["weight"] == P()
    assert specs["step"] == P()

    class BufferSGD(SGD):
        """State carries a non-moment buffer that happens to match a
        sharded param's shape+dtype; it must stay replicated."""
        def init_state(self, params):
            st = super().init_state(params)
            st["extra"] = jnp.zeros((8, 4))
            return st

    specs = fsdp_opt_state_specs(params, shardable,
                                 BufferSGD(learning_rate=0.1, momentum=0.9))
    assert specs["extra"] == P()
    assert specs["velocity"]["a"]["weight"] == P("dp")


def test_param_tree_order_stable_across_uid_digit_boundary():
    """Auto-names are zero-padded so lexicographic pytree key order matches
    creation order even when a model's uids straddle 9->10, 99->100, ...;
    without this, two identical models built at different global-counter
    values flatten their leaves in different orders."""
    for _ in range(120):  # burn uids well past a digit boundary
        nn.Identity()
    m1 = make_model(0)
    for _ in range(37):
        nn.Identity()
    m2 = make_model(0)
    l1 = jax.tree_util.tree_leaves(m1._params)
    l2 = jax.tree_util.tree_leaves(m2._params)
    assert [a.shape for a in l1] == [b.shape for b in l2]
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(a, b)
