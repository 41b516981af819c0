"""Fused optimizer-update Pallas kernels (Adam / AdamW / SGD-momentum).

``optim_method.py`` expresses each update as ~10 ``tree_map`` HLO ops per
leaf (two moment EMAs, bias corrections, rsqrt, the axpy); XLA usually
fuses them, but every op still makes a scheduling decision and the fused
group re-reads params/moments from HBM when the fusion splits.  These
kernels do the whole update in ONE pass per leaf: a grid over
(rows, 128)-blocks held in VMEM, each block reading param/moment/grad
exactly once and writing the new param/moments exactly once — the
optimizer update becomes a pure HBM-bandwidth stream.

Contract:

  * **Same math, same op order** as the reference ``update()`` methods.
    What parity that buys depends on the compiler, not on the kernel:
    the two programs are mathematically identical but structured
    differently, and XLA decides FMA contraction per fusion.  Measured
    on this installation (jax/jaxlib 0.9.0, libtpu 0.0.34, PR 21):

      - **TPU v5e, native Mosaic**: Adam, AdamW and SGD-momentum bitwise
        equal to the jitted tree-map ``update()`` over 3 steps on a
        32000x1024 f32 leaf — ``chip_smoke.py`` re-checks and prints it.
      - **CPU, interpret mode**: SGD (no division chain) bitwise; Adam
        and AdamW drift from the second step on, because the moment EMA
        ``b*m + (1-b)*g`` contracts in one program and not the other.
        Over 5 steps: moments within 3 ulps, params within 7.5e-9
        absolute (1 ulp at the parameter's own magnitude).
        ``tests/test_fused_optim.py`` holds that line with bounds of
        8 ulps and rtol 1e-6 / atol 1e-7.

  * **Native unless a test says otherwise**: the kernels lower through
    Mosaic.  Interpret mode exists for CPU tests and CPU smokes only,
    through the explicit ``_FORCE_INTERPRET`` hook (tests/conftest.py
    sets it); nothing turns it on by itself, so a run on the chip can
    never quietly execute the interpreter.
  * Leaves the kernel cannot tile (non-f32 dtypes, empty leaves) take
    the reference math per leaf — identical numerics, no silent skips:
    the choice is static per leaf shape/dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook mirroring ops/flash_attention._INTERPRET: run the kernel
# bodies through the Pallas interpreter (the only way they run on CPU).
_FORCE_INTERPRET = False

_LANES = 128        # VPU lane width: last dim of every block
_SUBLANES = 8       # f32 sublane quantum
_BLOCK_ROWS = 256   # rows per grid step: 7 f32 operands ~ 0.9 MB VMEM


def _interpret() -> bool:
    return _FORCE_INTERPRET


def _leaf_ok(leaf) -> bool:
    """Static per-leaf eligibility: the kernel tiles f32 onto (8, 128)."""
    return (getattr(leaf, "size", 0) > 0
            and getattr(leaf, "dtype", None) == jnp.float32)


def _scalar(x):
    return jnp.asarray(x, jnp.float32).reshape(1)


def _unzip(tuple_tree, n):
    """Split a tree whose leaves are n-tuples into n same-structure
    trees (the per-leaf kernels return (new_p, new_m, ...) tuples)."""
    flat, treedef = jax.tree_util.tree_flatten(
        tuple_tree, is_leaf=lambda x: isinstance(x, tuple))
    return tuple(jax.tree_util.tree_unflatten(treedef, [t[i] for t in flat])
                 for i in range(n))


def _run_blocked(kernel, scalars, arrays, n_out):
    """Run an elementwise kernel over same-shape f32 arrays.

    Arrays are raveled, zero-padded to a whole number of
    ``(block_rows, 128)`` tiles and streamed block-by-block through VMEM;
    scalars ride SMEM.  Zero padding is safe for every optimizer update
    here (0 grads + 0 moments -> 0 update) and the pad region is sliced
    off before returning.
    """
    shape, dtype = arrays[0].shape, arrays[0].dtype
    size = arrays[0].size
    rows = -(-size // _LANES)
    rows = -(-rows // _SUBLANES) * _SUBLANES
    block_rows = min(rows, _BLOCK_ROWS)
    rows = -(-rows // block_rows) * block_rows
    pad = rows * _LANES - size

    def prep(a):
        return jnp.pad(a.ravel(), (0, pad)).reshape(rows, _LANES)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[smem] * len(scalars) + [vmem] * len(arrays),
        out_specs=[vmem] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), dtype)] * n_out,
        interpret=_interpret(),
    )(*[_scalar(s) for s in scalars], *[prep(a) for a in arrays])
    return [o.ravel()[:size].reshape(shape) for o in outs]


# --------------------------------------------------------------------- #
# Adam / AdamW                                                          #
# --------------------------------------------------------------------- #
def _adam_kernel(clr_ref, bc1_ref, bc2_ref, p_ref, m_ref, v_ref, g_ref,
                 np_ref, nm_ref, nv_ref, *, beta1, beta2, eps,
                 weight_decay):
    # op order mirrors optim_method.Adam.update exactly (bit parity)
    g = g_ref[...]
    p = p_ref[...]
    m = beta1 * m_ref[...] + (1 - beta1) * g
    v = beta2 * v_ref[...] + (1 - beta2) * g * g
    clr = clr_ref[0]
    upd = clr * (m / bc1_ref[0]) / (jnp.sqrt(v / bc2_ref[0]) + eps)
    new_p = p - upd
    if weight_decay:                 # AdamW's decoupled decay, post-update
        new_p = new_p - clr * weight_decay * p
    np_ref[...] = new_p
    nm_ref[...] = m
    nv_ref[...] = v


def fused_adam_update(params, grads, m, v, *, clr, bc1, bc2, beta1, beta2,
                      eps, weight_decay=0.0):
    """One-pass Adam(W) update over a pytree.

    ``clr``/``bc1``/``bc2`` are the (possibly traced) step-dependent
    scalars the caller already computed; ``weight_decay`` > 0 applies
    AdamW's decoupled decay inside the same pass.  Returns
    ``(new_params, new_m, new_v)``.
    """
    kernel = functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay)

    def upd(p, g, m_, v_):
        if _leaf_ok(p) and p.dtype == g.dtype == m_.dtype == v_.dtype:
            new_p, new_m, new_v = _run_blocked(
                kernel, (clr, bc1, bc2), (p, m_, v_, g), 3)
            return new_p, new_m, new_v
        # reference math, identical op order (non-f32 / empty leaves)
        new_m = beta1 * m_ + (1 - beta1) * g
        new_v = beta2 * v_ + (1 - beta2) * g * g
        new_p = p - (clr * (new_m / bc1)
                     / (jnp.sqrt(new_v / bc2) + eps)).astype(p.dtype)
        if weight_decay:
            new_p = new_p - clr * weight_decay * p
        return new_p, new_m, new_v

    return _unzip(jax.tree_util.tree_map(upd, params, grads, m, v), 3)


# --------------------------------------------------------------------- #
# SGD (momentum / nesterov / plain)                                     #
# --------------------------------------------------------------------- #
def _sgd_mom_kernel(clr_ref, p_ref, v_ref, g_ref, np_ref, nv_ref, *,
                    momentum, dampening, nesterov, weight_decay):
    g = g_ref[...]
    p = p_ref[...]
    if weight_decay > 0:
        g = g + weight_decay * p
    vel = momentum * v_ref[...] + (1.0 - dampening) * g
    step = g + momentum * vel if nesterov else vel
    np_ref[...] = p - clr_ref[0] * step
    nv_ref[...] = vel


def _sgd_plain_kernel(clr_ref, p_ref, g_ref, np_ref, *, weight_decay):
    g = g_ref[...]
    p = p_ref[...]
    if weight_decay > 0:
        g = g + weight_decay * p
    np_ref[...] = p - clr_ref[0] * g


def fused_sgd_update(params, grads, velocity=None, *, clr, momentum=0.0,
                     dampening=0.0, nesterov=False, weight_decay=0.0):
    """One-pass SGD update over a pytree; ``velocity=None`` selects the
    momentum-free kernel.  Returns ``(new_params, new_velocity)`` with
    ``new_velocity=None`` in the plain case."""
    if momentum > 0 and velocity is not None:
        kernel = functools.partial(
            _sgd_mom_kernel, momentum=momentum, dampening=dampening,
            nesterov=nesterov, weight_decay=weight_decay)

        def upd(p, g, v_):
            if _leaf_ok(p) and p.dtype == g.dtype == v_.dtype:
                new_p, new_v = _run_blocked(kernel, (clr,), (p, v_, g), 2)
                return new_p, new_v
            if weight_decay > 0:
                g = g + weight_decay * p
            vel = momentum * v_ + (1.0 - dampening) * g
            step = g + momentum * vel if nesterov else vel
            return p - clr * step.astype(p.dtype), vel

        return _unzip(jax.tree_util.tree_map(upd, params, grads, velocity),
                      2)

    kernel = functools.partial(_sgd_plain_kernel, weight_decay=weight_decay)

    def upd_plain(p, g):
        if _leaf_ok(p) and p.dtype == g.dtype:
            return _run_blocked(kernel, (clr,), (p, g), 1)[0]
        if weight_decay > 0:
            g = g + weight_decay * p
        return p - clr * g.astype(p.dtype)

    return jax.tree_util.tree_map(upd_plain, params, grads), None
