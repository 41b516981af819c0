"""Fused Pallas optimizer kernels (bigdl_tpu.kernels.fused_optim):
interpret-mode execution on CPU (through the explicit test hook that
tests/conftest.py sets), parity against the reference
``OptimMethod.update`` tree-map path, and the DistriOptimizer opt-in
flag.  Native (Mosaic) parity is ``chip_smoke.py``'s kernels stage."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.optim.optim_method import SGD, Adam, AdamW


def _tree(rng, dtype=np.float32):
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(dtype))
    return {"a": {"weight": mk(300, 7), "bias": mk(7)},
            "b": {"weight": mk(64, 64), "scalar": jnp.asarray(
                rng.randn(), dtype)}}


def _run_steps(method, params, grads, n=5):
    state = method.init_state(params)
    upd = jax.jit(method.update)
    for _ in range(n):
        params, state = upd(grads, params, state)
    return params, state


def _leaves(t):
    return jax.tree_util.tree_leaves(t)


def test_interpret_mode_only_through_the_test_hook(monkeypatch):
    """Nothing turns interpret mode on by itself: off the hook the
    kernels lower through Mosaic, which a CPU backend refuses loudly —
    never a quiet interpreter run on the chip, never a quiet skip here."""
    from bigdl_tpu.kernels import fused_optim
    assert fused_optim._interpret()            # conftest set the hook
    monkeypatch.setattr(fused_optim, "_FORCE_INTERPRET", False)
    assert not fused_optim._interpret()
    p = {"w": jnp.ones((8, 128), jnp.float32)}
    method = SGD(0.05, fused=True)
    with pytest.raises(Exception, match="(?i)interpret|cpu|mosaic|tpu"):
        jax.jit(method.update)(p, p, method.init_state(p))


@pytest.mark.parametrize("make", [
    lambda f: SGD(0.05, fused=f),
    lambda f: SGD(0.05, momentum=0.9, weight_decay=1e-4, fused=f),
    lambda f: SGD(0.05, momentum=0.9, nesterov=True, dampening=0, fused=f),
], ids=["plain", "momentum-wd", "nesterov"])
def test_fused_sgd_bitwise_in_process(make):
    """SGD's update chain has no division, so XLA's FMA choices agree
    across the kernel and tree-map program structures: bit-for-bit over
    5 jitted steps."""
    rng = np.random.RandomState(0)
    params = _tree(rng)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)
                              if p.shape else
                              np.float32(rng.randn())), params)
    p_r, s_r = _run_steps(make(False), params, grads)
    p_f, s_f = _run_steps(make(True), params, grads)
    for a, b in zip(_leaves((p_r, s_r)), _leaves((p_f, s_f))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("make", [
    lambda f: Adam(1e-3, fused=f),
    lambda f: AdamW(1e-3, weight_decay=0.01, fused=f),
], ids=["adam", "adamw"])
def test_fused_adam_parity_contract_in_process(make):
    """The parity the kernel has on THIS installation (jaxlib 0.9.0 CPU,
    interpret mode; the contract in kernels/fused_optim.py).  Same math,
    same op order, but XLA contracts the moment EMA ``b*m + (1-b)*g``
    into an FMA in one program structure and not the other, so from the
    second step on the two drift in the last place.  Measured over 5
    steps: moments within 3 ulps, params within 7.5e-9 absolute (1 ulp
    at the parameter's own magnitude).  The first step starts from zero
    moments, where the two roundings agree: bitwise.  (On the chip the
    native kernels are bitwise throughout — chip_smoke.py prints it.)"""
    rng = np.random.RandomState(0)
    params = _tree(rng)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)
                              if p.shape else
                              np.float32(rng.randn())), params)
    first_r = _run_steps(make(False), params, grads, n=1)
    first_f = _run_steps(make(True), params, grads, n=1)
    for a, b in zip(_leaves(first_r), _leaves(first_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p_r, s_r = _run_steps(make(False), params, grads)
    p_f, s_f = _run_steps(make(True), params, grads)
    for k in ("m", "v"):
        for a, b in zip(_leaves(s_r[k]), _leaves(s_f[k])):
            np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b),
                                            maxulp=8)
    for a, b in zip(_leaves(p_r), _leaves(p_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_fused_mixed_dtype_tree_falls_back_per_leaf():
    """Non-f32 leaves take the reference math inside the same update —
    same numerics, no crash, static per-leaf choice."""
    rng = np.random.RandomState(1)
    params = {"w32": jnp.asarray(rng.randn(40, 8).astype(np.float32)),
              "w16": jnp.asarray(rng.randn(40, 8).astype(np.float32)
                                 ).astype(jnp.bfloat16)}
    grads = {"w32": jnp.asarray(rng.randn(40, 8).astype(np.float32)),
             "w16": jnp.asarray(rng.randn(40, 8).astype(np.float32)
                                ).astype(jnp.bfloat16)}
    p_r, s_r = _run_steps(Adam(1e-3), params, grads, n=3)
    p_f, s_f = _run_steps(Adam(1e-3, fused=True), params, grads, n=3)
    assert p_f["w16"].dtype == jnp.bfloat16
    for a, b in zip(_leaves(p_r), _leaves(p_f)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=1e-6)


def test_fused_kernel_grid_blocking_large_leaf():
    """A leaf spanning multiple (256, 128) grid blocks updates
    identically to the reference (the block decomposition is pure
    plumbing)."""
    rng = np.random.RandomState(2)
    params = {"big": jnp.asarray(rng.randn(600, 130).astype(np.float32))}
    grads = {"big": jnp.asarray(rng.randn(600, 130).astype(np.float32))}
    p_r, _ = _run_steps(SGD(0.05, momentum=0.9), params, grads, n=3)
    p_f, _ = _run_steps(SGD(0.05, momentum=0.9, fused=True), params,
                        grads, n=3)
    np.testing.assert_array_equal(np.asarray(p_r["big"]),
                                  np.asarray(p_f["big"]))


def test_distri_optimizer_fused_flag():
    """DistriOptimizer(fused_optim=True) flips the method's fused flag at
    wrap time and rejects methods without a kernel."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.optim_method import Adagrad
    from bigdl_tpu.parallel import mesh as mesh_lib

    x = np.zeros((64, 12), np.float32)
    y = np.zeros((64, 1), np.float32)
    mesh = mesh_lib.create_mesh({"dp": 8})
    m = nn.Sequential(nn.Linear(12, 8), nn.Linear(8, 1))
    m.reset(0)
    opt = DistriOptimizer(m, (x, y), nn.MSECriterion(), batch_size=64,
                          mesh=mesh, fused_optim=True)
    user_optim = Adam(1e-3)
    opt.set_optim_method(user_optim)
    params, _ = m.init_params(0)
    wrapped = opt._wrap_optim(params)
    assert wrapped.fused
    # the USER'S instance is never mutated: reusing it in another
    # optimizer without the flag must keep the default unfused path
    assert not user_optim.fused

    opt2 = DistriOptimizer(m, (x, y), nn.MSECriterion(), batch_size=64,
                           mesh=mesh, fused_optim=True)
    opt2.set_optim_method(Adagrad(1e-3))
    with pytest.raises(ValueError, match="no.*fused kernel|fused"):
        opt2._wrap_optim(params)
