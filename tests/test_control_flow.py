"""nn.WhileLoop / nn.Cond — data-dependent control flow as modules
(≙ nn/tf/ControlOps.scala ControlNodes.whileLoop/switch/merge +
FrameManager's DynamicGraph runtime, compiled to lax.while_loop /
lax.cond)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Ctx
from bigdl_tpu.utils.table import T
from gradient_checker import FnModule


def test_while_loop_newton_sqrt():
    """Table-state loop: Newton iteration until |x^2 - target| small."""
    step = FnModule(lambda t: T(0.5 * (t[1] + t[2] / t[1]), t[2]))
    not_done = FnModule(lambda t: jnp.abs(t[1] * t[1] - t[2]) > 1e-5)
    wl = nn.WhileLoop(not_done, step)
    out = wl.forward(T(np.float32(1.0), np.float32(9.0)))
    assert abs(float(out[1]) - 3.0) < 1e-3


def test_while_loop_under_jit():
    wl = nn.WhileLoop(FnModule(lambda x: jnp.sum(x * x) < 100.0),
                      FnModule(lambda x: x * 2.0))
    params, state = wl.init_params(0)
    f = jax.jit(lambda p, a: wl.apply(p, a, Ctx(state=state)))
    y = np.asarray(f(params, np.ones((4,), np.float32)))
    assert float((y ** 2).sum()) >= 100.0
    assert y[0] == 8.0          # 1 -> 2 -> 4 -> 8 (4*64 >= 100)


def test_while_loop_with_parameterized_body():
    """Body with weights: iterate h = tanh(W h) a data-dependent number
    of times (norm decay threshold)."""
    body = nn.Sequential(nn.Linear(4, 4, with_bias=False), nn.Tanh())
    wl = nn.WhileLoop(FnModule(lambda h: jnp.sum(h * h) > 0.5), body)
    params, state = wl.init_params(2)
    x = jnp.asarray(np.random.RandomState(0).rand(1, 4).astype(np.float32)
                    + 1.0)
    y = wl.apply(params, x, Ctx(state=state))
    assert float(jnp.sum(y * y)) <= 0.5


def test_while_loop_scan_matches_while_forward():
    """max_iters=N (scan lowering) == unbounded lax.while_loop forward
    whenever the loop terminates within N."""
    cond = FnModule(lambda x: jnp.sum(x * x) < 100.0)
    body = FnModule(lambda x: x * 2.0)
    x = np.ones((4,), np.float32)
    y_while = np.asarray(nn.WhileLoop(cond, body).forward(x))
    y_scan = np.asarray(nn.WhileLoop(cond, body, max_iters=10).forward(x))
    np.testing.assert_array_equal(y_scan, y_while)
    assert y_scan[0] == 8.0


def test_while_loop_scan_gradient_matches_unrolled():
    """grad through WhileLoop(max_iters=N) == grad through the
    hand-unrolled loop (the trip count the data actually takes) —
    the DynamicGraph.generateBackward parity check
    (nn/DynamicGraph.scala:32,62)."""
    body = nn.Sequential(nn.Linear(4, 4, with_bias=False), nn.Tanh())
    thr = 0.2
    cond = FnModule(lambda h: jnp.sum(h * h) > thr)
    wl = nn.WhileLoop(cond, body, max_iters=12)
    params, st = wl.init_params(2)
    x = jnp.asarray(
        np.random.RandomState(0).rand(1, 4).astype(np.float32) + 1.0)

    # concrete trip count of this data
    w = np.asarray(params[body.children()[0].name]["weight"])
    h, n_iters = np.asarray(x), 0
    while (h * h).sum() > thr:
        h, n_iters = np.tanh(h @ w.T), n_iters + 1
    assert 0 < n_iters < 12

    y = np.asarray(wl.apply(params, x, Ctx(state=st)))
    np.testing.assert_allclose(y, h, rtol=1e-5, atol=1e-6)

    def loss_loop(p):
        return jnp.sum(wl.apply(p, x, Ctx(state=st)) ** 2)

    def loss_unrolled(p):
        h = x
        for _ in range(n_iters):
            h = body.apply(p, h, Ctx(state=st))
        return jnp.sum(h ** 2)

    g_loop = jax.grad(loss_loop)(params)
    g_unrolled = jax.grad(loss_unrolled)(params)
    for k in g_unrolled:
        np.testing.assert_allclose(
            np.asarray(g_loop[k]["weight"]),
            np.asarray(g_unrolled[k]["weight"]), rtol=1e-5, atol=1e-6)


def test_while_loop_scan_trains():
    """A model with a bounded loop inside takes a gradient step end to
    end (authored loops are trainable)."""
    body = nn.Sequential(nn.Linear(3, 3), nn.Tanh())
    m = nn.Sequential(
        nn.Linear(5, 3),
        nn.WhileLoop(FnModule(lambda h: jnp.sum(h * h) > 0.05), body,
                     max_iters=4),
        nn.Linear(3, 2))
    params, st = m.init_params(4)
    x = jnp.asarray(np.random.RandomState(3).randn(6, 5).astype(np.float32))

    def loss(p):
        return jnp.mean(m.apply(p, x, Ctx(state=st)) ** 2)

    g = jax.grad(loss)(params)
    total = sum(float(np.abs(np.asarray(v)).sum())
                for sub in g.values() for v in sub.values())
    assert np.isfinite(total) and total > 0


def test_while_loop_scan_no_nan_leak_from_frozen_body():
    """Once the loop freezes, the body would compute sqrt of a negative
    on the terminal state; the lax.cond freeze must keep both the
    forward AND the gradient finite (the 0*NaN=NaN where-grad trap)."""
    # h_{k+1} = sqrt(h_k) - 0.5: from h=1.0 -> 0.5 -> ~0.207 -> negative
    cond = FnModule(lambda h: h > 0.0)
    body = FnModule(lambda h: jnp.sqrt(h) - 0.5)
    wl = nn.WhileLoop(cond, body, max_iters=6)
    params, st = wl.init_params(0)

    def loss(h0):
        return wl.apply(params, h0, Ctx(state=st)) ** 2

    h0 = jnp.float32(1.0)
    y = float(loss(h0))
    g = float(jax.grad(loss)(h0))
    assert np.isfinite(y) and np.isfinite(g), (y, g)
    # parity with the honest python loop
    h = 1.0
    while h > 0.0:
        h = float(np.sqrt(h) - 0.5)
    np.testing.assert_allclose(
        float(wl.apply(params, h0, Ctx(state=st))), h, rtol=1e-6)


def test_cond_state_propagates():
    """BN running stats written INSIDE the taken branch reach the outer
    ctx (merged lax.cond carry); the untaken branch leaves them at the
    current value."""
    bn = nn.BatchNormalization(4, name="cond_bn")
    m = nn.Cond(FnModule(lambda x: jnp.sum(x) > 0), bn,
                FnModule(lambda x: x * 1.0))
    params, st = m.init_params(5)
    x = jnp.asarray(
        np.random.RandomState(4).rand(8, 4).astype(np.float32) + 2.0)

    ctx = Ctx(state=st, training=True, rng_key=jax.random.PRNGKey(0))
    m.apply(params, x, ctx)
    assert "cond_bn" in ctx.new_state
    rm_taken = np.asarray(ctx.new_state["cond_bn"]["running_mean"])
    assert np.abs(rm_taken).sum() > 0        # moved toward batch mean

    ctx2 = Ctx(state=st, training=True, rng_key=jax.random.PRNGKey(0))
    m.apply(params, -x, ctx2)                # pred false
    rm_untaken = np.asarray(ctx2.new_state["cond_bn"]["running_mean"])
    np.testing.assert_array_equal(
        rm_untaken, np.asarray(st["cond_bn"]["running_mean"]))


def test_cond_side_loss_propagates():
    """Side losses raised inside a branch surface in the outer ctx,
    zero-padded on the branch that raises none."""
    m = nn.Cond(FnModule(lambda x: jnp.sum(x) > 0),
                nn.ActivityRegularization(l1=1.0),
                FnModule(lambda x: x * 1.0))
    params, st = m.init_params(6)
    x = jnp.asarray(np.ones((2, 3), np.float32))

    ctx = Ctx(state=st)
    m.apply(params, x, ctx)
    assert len(ctx.side_losses) == 1
    np.testing.assert_allclose(float(ctx.side_losses[0]), 6.0, rtol=1e-6)

    ctx2 = Ctx(state=st)
    m.apply(params, -x, ctx2)                # untaken: zero-padded
    assert len(ctx2.side_losses) == 1
    assert float(ctx2.side_losses[0]) == 0.0


def test_cond_branches_and_gradient():
    pred = FnModule(lambda x: jnp.sum(x) > 0)
    m = nn.Cond(pred, nn.Linear(4, 3, name="cf_tb"),
                nn.Linear(4, 3, name="cf_fb"))
    params, st = m.init_params(1)

    for sign, taken, untaken in ((1.0, "cf_tb", "cf_fb"),
                                 (-1.0, "cf_fb", "cf_tb")):
        x = jnp.asarray(np.full((2, 4), sign, np.float32))
        g = jax.grad(lambda p: jnp.sum(
            m.apply(p, x, Ctx(state=st)) ** 2))(params)
        assert np.abs(np.asarray(g[taken]["weight"])).sum() > 0
        assert np.abs(np.asarray(g[untaken]["weight"])).sum() == 0


def test_cond_in_sequential():
    """Composes with ordinary layers inside a Sequential."""
    pred = FnModule(lambda x: jnp.mean(x) > 0.0)
    m = nn.Sequential(
        nn.Linear(5, 4),
        nn.Cond(pred, FnModule(lambda x: x * 2.0), FnModule(lambda x: -x)),
        nn.ReLU())
    m.reset(3)
    x = np.random.RandomState(1).randn(3, 5).astype(np.float32)
    y = np.asarray(m.forward(x))
    assert y.shape == (3, 4) and np.all(y >= 0)
