"""Flash attention: Pallas TPU kernel + blockwise-XLA route.

The reference framework has no fused attention (its RNN era predates it);
this kernel is the core primitive of our long-context flagship
(models/transformer.py) and of ring attention (parallel/ring_attention.py).

Design:
  * forward — Pallas kernel on TPU: grid over (batch*heads, q blocks),
    online-softmax ``fori_loop`` over key blocks held in VMEM; scores and
    accumulators in fp32 on the MXU, inputs may be bf16.
  * backward — two Pallas kernels (dk/dv over q blocks, dq over kv
    blocks) recomputing p from the saved (q, k, v, out, lse) residuals:
    flash-style O(seq * block) memory, no materialised (seq, seq) matrix.
  * blockwise route — the same math as a ``lax.scan`` over key blocks,
    forward and backward; what runs on CPU and for shapes the kernel
    does not tile.  :func:`attention_path` is the ONE place that decides
    which route a call takes and says why, so a benchmark (and
    ``chip_smoke.py``) can assert the kernel is what ran.

Both paths share masking logic: a key is attended iff
``k_pos < kv_len  and  (not causal or q_pos >= k_pos)`` where the position
vectors are *global* token indices — ring attention passes shifted
positions for its rotating key/value chunks.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

# Finite "minus infinity": keeps exp()/max() NaN-free for fully-masked rows
# (the same trick as jax.nn and the original flash kernels).
DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

# Test hook: when True, Pallas kernels run in interpret mode so the TPU
# code path itself (not the blockwise route) is exercised on CPU.
_INTERPRET = False

# VMEM the resident operands may take.  The forward and dq kernels keep
# the whole K and V of one head resident (the dkv kernel: Q and dO),
# double-buffered by the Mosaic pipeline, under a 16 MiB scoped-VMEM
# limit on a TPU v5e; past it the compile fails ("exceeded scoped vmem
# limit").  15 MiB leaves room for the blocked operands and the kernel's
# temporaries: measured on the chip at head_dim 128 (NOTES.md "Bring-up
# on the chip"), all three kernels compile and match the blockwise route
# at seq 15360 in bf16 and 7680 in f32, and the forward — the tightest
# of the three — stops compiling at 15872 / 8064.
_VMEM_RESIDENT_BYTES = 15 * 2 ** 20


class _Config(NamedTuple):
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    use_pallas: bool


# --------------------------------------------------------------------- #
# reference (quadratic) — used by tests and tiny shapes                 #
# --------------------------------------------------------------------- #
def attention_reference(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None):
    """Naive softmax(q k^T) v with optional causal mask. (B, H, S, D)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


# --------------------------------------------------------------------- #
# shared blockwise math                                                 #
# --------------------------------------------------------------------- #
def _mask(q_pos, k_pos, kv_len, causal):
    """(Sq, Sk) bool attend-mask from global positions."""
    valid = (k_pos < kv_len)[None, :]
    if causal:
        valid = valid & (q_pos[:, None] >= k_pos[None, :])
    return valid


def chunk_merge(q, k_chunk, v_chunk, acc, m, l, q_pos, k_pos, kv_len,
                sm_scale, causal):
    """Merge one key/value chunk into the online-softmax accumulators.

    q: (..., Sq, D); k_chunk/v_chunk: (..., Sk, D); acc: (..., Sq, D) fp32;
    m, l: (..., Sq) fp32 running max / normaliser. Returns updated
    (acc, m, l). This is the single primitive both the scan fallback and
    ring attention are built from.
    """
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                   k_chunk.astype(jnp.float32)) * sm_scale
    s = jnp.where(_mask(q_pos, k_pos, kv_len, causal), s, DEFAULT_MASK_VALUE)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = corr * l + p.sum(axis=-1)
    acc_new = corr[..., None] * acc + jnp.einsum(
        "...qk,...kd->...qd", p, v_chunk.astype(jnp.float32))
    return acc_new, m_new, l_new


def chunk_merge_blockwise(q, k_chunk, v_chunk, acc, m, l, q_pos, k_pos,
                          kv_len, sm_scale, causal, block_k=1024):
    """chunk_merge with the kv chunk processed in ``block_k`` sub-blocks:
    same online-softmax result, but peak score memory is
    (..., Sq, block_k) instead of (..., Sq, Sk) — the memory lever for
    ring attention over long local chunks."""
    sk = k_chunk.shape[-2]
    if sk <= block_k:
        return chunk_merge(q, k_chunk, v_chunk, acc, m, l, q_pos, k_pos,
                           kv_len, sm_scale, causal)
    nb = -(-sk // block_k)
    pad = nb * block_k - sk
    if pad:   # pad keys out past kv_len so the position mask drops them
        widths = [(0, 0)] * (k_chunk.ndim - 2) + [(0, pad), (0, 0)]
        k_chunk = jnp.pad(k_chunk, widths)
        v_chunk = jnp.pad(v_chunk, widths)
        k_pos = jnp.concatenate(
            [k_pos, jnp.full((pad,), kv_len, k_pos.dtype)])
    kb = jnp.moveaxis(
        k_chunk.reshape(k_chunk.shape[:-2] + (nb, block_k)
                        + k_chunk.shape[-1:]), -3, 0)
    vb = jnp.moveaxis(
        v_chunk.reshape(v_chunk.shape[:-2] + (nb, block_k)
                        + v_chunk.shape[-1:]), -3, 0)
    kp = k_pos.reshape(nb, block_k)

    def step(carry, blk):
        acc, m, l = carry
        k_b, v_b, kp_b = blk
        return chunk_merge(q, k_b, v_b, acc, m, l, q_pos, kp_b, kv_len,
                           sm_scale, causal), None

    (acc, m, l), _ = lax.scan(step, (acc, m, l), (kb, vb, kp))
    return acc, m, l


def finalize(acc, m, l):
    """(out, lse) from final accumulators; fully-masked rows yield 0."""
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe_l[..., None]
    lse = m + jnp.log(safe_l)
    return out, lse


def _fwd_blockwise(q, k, v, cfg: _Config):
    """lax.scan over key blocks. (B, H, S, D) -> (out, lse)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = min(cfg.block_k, sk)
    n_blocks = -(-sk // bk)
    pad = n_blocks * bk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # (n_blocks, B, H, bk, D) so scan walks the leading axis
    kb = jnp.moveaxis(k.reshape(b, h, n_blocks, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, n_blocks, bk, d), 2, 0)
    q_pos = jnp.arange(sq)

    def step(carry, blk):
        acc, m, l = carry
        k_c, v_c, j = blk

        def merge(carry):
            acc, m, l = carry
            k_pos = j * bk + jnp.arange(bk)
            return chunk_merge(q, k_c, v_c, acc, m, l, q_pos, k_pos,
                               sk, cfg.sm_scale, cfg.causal)

        if cfg.causal:
            # skip blocks entirely beyond the causal horizon (matters for
            # cross/decode attention where seq_k > seq_q)
            carry = lax.cond(j * bk > sq - 1, lambda c: c, merge, carry)
        else:
            carry = merge(carry)
        return carry, None

    init = (jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.full((b, h, sq), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32))
    (acc, m, l), _ = lax.scan(step, init, (kb, vb, jnp.arange(n_blocks)))
    out, lse = finalize(acc, m, l)
    return out.astype(q.dtype), lse


# --------------------------------------------------------------------- #
# Pallas kernels                                                        #
# --------------------------------------------------------------------- #
def _block_causal_mask(qi, j, block_q, block_k):
    """(block_q, block_k) bool mask for q block `qi` vs kv block `j`."""
    qp = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kp = j * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return qp >= kp


def _causal_hi(qi, block_q, block_k, n_kb):
    """First kv-block index past the causal horizon of q block `qi`."""
    hi = lax.div(qi * block_q + block_q - 1, block_k) + 1
    return jnp.minimum(hi, n_kb)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, block_q, block_k, seq_k):
    # m/l/lse are carried as (bq, 1) rather than (bq,): Mosaic tiles the
    # last two dims onto (sublane, lane), and a trailing singleton keeps
    # every ref block shape legal on hardware (interpret mode never checks
    # this — the r2 kernel only failed when first run on a real TPU).
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, d)
    d = q.shape[-1]
    n_kb = seq_k // block_k
    hi = _causal_hi(qi, block_q, block_k, n_kb) if causal else n_kb

    def body(j, carry):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            s = jnp.where(_block_causal_mask(qi, j, block_q, block_k),
                          s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))     # (bq, 1)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(axis=-1, keepdims=True)
        acc_new = corr * acc + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    init = (jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q, 1), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = lax.fori_loop(0, hi, body, init)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(safe_l)).astype(lse_ref.dtype)


def _fwd_pallas(q, k, v, cfg: _Config):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = cfg.block_q, cfg.block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    grid = (b * h, sq // bq)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=cfg.sm_scale, causal=cfg.causal,
        block_q=bq, block_k=bk, seq_k=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            # trailing singleton = lane-legal block (see _fwd_kernel note)
            pl.BlockSpec((1, bq, 1), lambda bh, i: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# --------------------------------------------------------------------- #
# Pallas backward kernels                                               #
# --------------------------------------------------------------------- #
def _bwd_kernel_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k,
                    seq_q):
    """One (batch*head, kv-block) program: accumulate dk/dv over q blocks.

    Flash-attention backward recomputes p = exp(s - lse) per block from the
    saved lse — no (seq, seq) matrix is ever materialised.
    """
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                      # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    n_qb = seq_q // block_q
    # under causality, q blocks strictly before this kv block see none of it
    lo = lax.div(ki * block_k, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lseb = lse_ref[0, pl.ds(i * block_q, block_q), :]      # (bq, 1)
        deltab = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jnp.dot(qb, k.T, preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lseb)                             # (bq, bk)
        if causal:
            p = jnp.where(_block_causal_mask(i, ki, block_q, block_k),
                          p, 0.0)
        dv = dv + jnp.dot(p.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        ds_ = p * (dp - deltab) * sm_scale
        dk = dk + jnp.dot(ds_.T, qb, preferred_element_type=jnp.float32)
        return dk, dv

    d = k.shape[-1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = lax.fori_loop(lo, n_qb, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_q, block_k, seq_k):
    """One (batch*head, q-block) program: accumulate dq over kv blocks."""
    qi = pl.program_id(1)
    qb = q_ref[0].astype(jnp.float32)                     # (bq, d)
    dob = do_ref[0].astype(jnp.float32)
    lseb = lse_ref[0]                                     # (bq, 1)
    deltab = delta_ref[0]
    n_kb = seq_k // block_k
    hi = _causal_hi(qi, block_q, block_k, n_kb) if causal else n_kb

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lseb)
        if causal:
            p = jnp.where(_block_causal_mask(qi, j, block_q, block_k),
                          p, 0.0)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds_ = p * (dp - deltab) * sm_scale
        return dq + jnp.dot(ds_, kb, preferred_element_type=jnp.float32)

    d = qb.shape[-1]
    dq = lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, cfg: _Config):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = cfg.block_q, cfg.block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = do.reshape(b * h, sq, d)
    lsef = lse.reshape(b * h, sq, 1)
    # delta_i = sum_d do_i * out_i; tiny elementwise reduce, leave it to XLA
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)
             ).sum(-1).reshape(b * h, sq, 1)

    kv_kernel = functools.partial(
        _bwd_kernel_dkv, sm_scale=cfg.sm_scale, causal=cfg.causal,
        block_q=bq, block_k=bk, seq_q=sq)
    dk, dv = pl.pallas_call(
        kv_kernel,
        grid=(b * h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, sq, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        interpret=_INTERPRET,
    )(qf, kf, vf, dof, lsef, delta)

    q_kernel = functools.partial(
        _bwd_kernel_dq, sm_scale=cfg.sm_scale, causal=cfg.causal,
        block_q=bq, block_k=bk, seq_k=sk)
    dq = pl.pallas_call(
        q_kernel,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i: (bh, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)],
        interpret=_INTERPRET,
    )(qf, kf, vf, dof, lsef, delta)[0]

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def attention_path(q_shape, k_shape, dtype, *, block_q: int = 128,
                   block_k: int = 128, use_pallas: bool = True,
                   backend: Optional[str] = None) -> Tuple[str, str]:
    """Which route ``flash_attention`` takes for these (B, H, S, D)
    shapes, and why: ``("pallas", reason)`` or ``("blockwise", reason)``.
    Forward and backward always take the same route.  ``backend``
    defaults to ``jax.default_backend()``."""
    if not use_pallas:
        return "blockwise", "use_pallas=False"
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and not _INTERPRET:
        return "blockwise", f"backend {backend!r} is not tpu"
    sq, d = int(q_shape[2]), int(q_shape[3])
    sk = int(k_shape[2])
    if d % 128:
        return "blockwise", (f"head_dim {d} is not a multiple of 128 "
                             "(one lane tile)")
    if sq % block_q or sk % block_k:
        return "blockwise", (f"seq_q {sq} / seq_k {sk} not multiples of "
                             f"block_q {block_q} / block_k {block_k}")
    # the resident pair (K, V in fwd/dq; Q, dO in dkv), double-buffered
    need = 4 * d * jnp.dtype(dtype).itemsize * max(sq, sk)
    if need > _VMEM_RESIDENT_BYTES:
        return "blockwise", (
            f"seq {max(sq, sk)} keeps {need / 2 ** 20:.1f} MiB resident "
            f"in VMEM, over the {_VMEM_RESIDENT_BYTES >> 20} MiB the "
            "kernel compiles under")
    return "pallas", ("interpret mode" if backend != "tpu"
                      else "tpu backend, shape tiles")


def _pallas_ok(q, k, cfg: _Config) -> bool:
    path, why = attention_path(q.shape, k.shape, q.dtype,
                               block_q=cfg.block_q, block_k=cfg.block_k,
                               use_pallas=cfg.use_pallas)
    if (path == "blockwise" and cfg.use_pallas
            and jax.default_backend() == "tpu"):
        # on the chip the scan route is never the intended one: say so
        # (once per call site — Python's default warning filter)
        warnings.warn(f"flash_attention takes the blockwise scan, not "
                      f"the Pallas kernel: {why}", stacklevel=2)
    return path == "pallas"


# --------------------------------------------------------------------- #
# custom VJP                                                            #
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Config, q, k, v):
    out, _ = _flash_fwd(cfg, q, k, v)
    return out


def _flash_fwd(cfg, q, k, v):
    if _pallas_ok(q, k, cfg):
        out, lse = _fwd_pallas(q, k, v, cfg)
    else:
        out, lse = _fwd_blockwise(q, k, v, cfg)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg, res, do):
    q, k, v, out, lse = res
    if _pallas_ok(q, k, cfg):
        return _bwd_pallas(q, k, v, out, lse, do, cfg)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = min(cfg.block_k, sk)
    n_blocks = -(-sk // bk)
    pad = n_blocks * bk - sk
    kp_ = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vp_ = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp_.reshape(b, h, n_blocks, bk, d), 2, 0)
    vb = jnp.moveaxis(vp_.reshape(b, h, n_blocks, bk, d), 2, 0)

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = (dof * out.astype(jnp.float32)).sum(-1)       # (B,H,Sq)
    q_pos = jnp.arange(sq)

    def step(dq, blk):
        k_c, v_c, j = blk
        k_pos = j * bk + jnp.arange(bk)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_c.astype(jnp.float32)
                       ) * cfg.sm_scale
        msk = _mask(q_pos, k_pos, sk, cfg.causal)
        p = jnp.where(msk, jnp.exp(s - lse[..., None]), 0.0)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_c.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * cfg.sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_c.astype(jnp.float32))
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_b, dv_b) = lax.scan(step, dq0, (kb, vb, jnp.arange(n_blocks)))
    dk = jnp.moveaxis(dk_b, 0, 2).reshape(b, h, n_blocks * bk, d)[:, :, :sk]
    dv = jnp.moveaxis(dv_b, 0, 2).reshape(b, h, n_blocks * bk, d)[:, :, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    use_pallas: bool = True):
    """Fused attention. q, k, v: (batch, heads, seq, head_dim).

    Pallas kernels on TPU, forward and backward; the blockwise lax.scan
    elsewhere and for shapes the kernel does not take —
    :func:`attention_path` says which and why.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    cfg = _Config(bool(causal), float(sm_scale), int(block_q), int(block_k),
                  bool(use_pallas))
    return _flash(cfg, q, k, v)
