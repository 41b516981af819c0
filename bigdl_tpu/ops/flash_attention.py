"""Flash attention: Pallas TPU kernel + blockwise-XLA route.

The reference framework has no fused attention (its RNN era predates it);
this kernel is the core primitive of our long-context flagship
(models/transformer.py) and of ring attention (parallel/ring_attention.py).

Design:
  * forward — Pallas kernel on TPU: grid over (batch*heads, q blocks),
    online-softmax ``fori_loop`` over key blocks held in VMEM.  Every
    matmul feeds the MXU its operands in the dtype they come in (bf16
    from a bf16 model, fp32 from fp32 callers) and accumulates in fp32;
    scores, the running max and normaliser, lse and the accumulators are
    fp32, and p is cast to the operands' dtype only as it enters p @ v.
  * backward — two Pallas kernels (dk/dv over q blocks, dq over kv
    blocks) recomputing p from the saved (q, k, v, out, lse) residuals:
    flash-style O(seq * block) memory, no materialised (seq, seq) matrix;
    the same rule for operands, with p and ds cast as they enter a matmul.
  * no operand is transposed in a loop (``dot_general`` contracts on the
    dimension that is there), the causal mask is built only for the
    blocks the diagonal crosses, and :func:`flash_blocks` picks the
    blocks from the shape.
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
# The scoped limit itself, and the blocks :func:`flash_blocks` chooses
# from under it.  On a v5e at seq 2048, head_dim 128 (PERF.md, PR 29):
# 128 x 128 blocks spend the time on the loop, not the MXU (forward
# 3.5 ms a call, 1.04 at 512 x 512); 1024 gains nothing more and wastes
# more of the blocks the causal diagonal crosses.
_VMEM_LIMIT_BYTES = 16 * 2 ** 20
_BLOCKS = (128, 256, 512)


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
# dot_general dimension numbers for a @ b.T: the contraction runs over the
# dimension that is there, so no operand is transposed in a loop
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """MXU matmul of operands in their own dtype, accumulated in fp32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _block_causal_mask(qi, j, block_q, block_k, transposed=False):
    """(block_q, block_k) bool mask for q block `qi` vs kv block `j`;
    (block_k, block_q), keys down the rows, when ``transposed``."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    qp = qi * block_q + lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    kp = j * block_k + lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    return qp >= kp


def _kv_loop(body, qi, init, *, causal, block_q, block_k, n_kb):
    """``body(masked, j, carry)`` over the kv blocks q block `qi` sees:
    first those wholly at or under its first row, which need no mask,
    then those the diagonal crosses; blocks past it are skipped."""
    if not causal:
        return lax.fori_loop(0, n_kb, functools.partial(body, False), init)
    full = jnp.minimum(lax.div(qi * block_q + 1, block_k), n_kb)
    hi = jnp.minimum(lax.div(qi * block_q + block_q - 1, block_k) + 1, n_kb)
    carry = lax.fori_loop(0, full, functools.partial(body, False), init)
    return lax.fori_loop(full, hi, functools.partial(body, True), carry)


def _q_loop(body, ki, init, *, causal, block_q, block_k, n_qb):
    """``body(masked, i, carry)`` over the q blocks that see kv block
    `ki`: those the diagonal crosses, then those wholly at or past its
    last key, which need no mask; blocks before it are skipped."""
    if not causal:
        return lax.fori_loop(0, n_qb, functools.partial(body, False), init)
    lo = lax.div(ki * block_k, block_q)
    full = jnp.minimum(
        lax.div(ki * block_k + block_k - 1 + block_q - 1, block_q), n_qb)
    carry = lax.fori_loop(lo, full, functools.partial(body, True), init)
    return lax.fori_loop(full, n_qb, functools.partial(body, False), carry)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, block_q, block_k, seq_k):
    # m/l/lse are carried as (bq, 1) rather than (bq,): Mosaic tiles the
    # last two dims onto (sublane, lane), and a trailing singleton keeps
    # every ref block shape legal on hardware (interpret mode never checks
    # this — the r2 kernel only failed when first run on a real TPU).
    qi = pl.program_id(1)
    q = q_ref[0]                                          # (bq, d)
    d = q.shape[-1]

    def body(masked, j, carry):
        acc, m, l = carry
        start = pl.multiple_of(j * block_k, block_k)
        kb = k_ref[0, pl.ds(start, block_k), :]
        vb = v_ref[0, pl.ds(start, block_k), :]
        # the fp32 scores are scaled, not q: 1/sqrt(d) is no power of two
        s = _dot(q, kb, _NT) * sm_scale                   # (bq, bk) fp32
        if masked:
            s = jnp.where(_block_causal_mask(qi, j, block_q, block_k),
                          s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))     # (bq, 1)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(axis=-1, keepdims=True)
        acc_new = corr * acc + _dot(p.astype(vb.dtype), vb)
        return acc_new, m_new, l_new

    init = (jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q, 1), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = _kv_loop(body, qi, init, causal=causal, block_q=block_q,
                         block_k=block_k, n_kb=seq_k // block_k)
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
    saved lse — no (seq, seq) matrix is ever materialised.  The scores are
    computed transposed, keys down the rows, so that dv = p^T dO and
    dk = ds^T q are plain matmuls; lse and delta come as (1, seq) rows.
    """
    ki = pl.program_id(1)
    k = k_ref[0]                                          # (bk, d)
    v = v_ref[0]

    def body(masked, i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * block_q, block_q)
        qb = q_ref[0, pl.ds(start, block_q), :]
        dob = do_ref[0, pl.ds(start, block_q), :]
        lseb = lse_ref[0, :, pl.ds(start, block_q)]           # (1, bq)
        deltab = delta_ref[0, :, pl.ds(start, block_q)]
        p = jnp.exp(_dot(k, qb, _NT) * sm_scale - lseb)       # (bk, bq)
        if masked:
            p = jnp.where(_block_causal_mask(i, ki, block_q, block_k,
                                             transposed=True), p, 0.0)
        dv = dv + _dot(p.astype(dob.dtype), dob)
        ds_ = p * (_dot(v, dob, _NT) - deltab)
        dk = dk + _dot(ds_.astype(qb.dtype), qb)
        return dk, dv

    d = k.shape[-1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = _q_loop(body, ki, init, causal=causal, block_q=block_q,
                     block_k=block_k, n_qb=seq_q // block_q)
    # ds = p * (dp - delta) * sm_scale: the scale goes on once, here
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_q, block_k, seq_k):
    """One (batch*head, q-block) program: accumulate dq over kv blocks."""
    qi = pl.program_id(1)
    qb = q_ref[0]                                         # (bq, d)
    dob = do_ref[0]
    lseb = lse_ref[0]                                     # (bq, 1)
    deltab = delta_ref[0]

    def body(masked, j, dq):
        start = pl.multiple_of(j * block_k, block_k)
        kb = k_ref[0, pl.ds(start, block_k), :]
        vb = v_ref[0, pl.ds(start, block_k), :]
        p = jnp.exp(_dot(qb, kb, _NT) * sm_scale - lseb)
        if masked:
            p = jnp.where(_block_causal_mask(qi, j, block_q, block_k),
                          p, 0.0)
        ds_ = p * (_dot(dob, vb, _NT) - deltab)
        return dq + _dot(ds_.astype(kb.dtype), kb)

    d = qb.shape[-1]
    dq = _kv_loop(body, qi, jnp.zeros((block_q, d), jnp.float32),
                  causal=causal, block_q=block_q, block_k=block_k,
                  n_kb=seq_k // block_k)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, cfg: _Config):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = cfg.block_q, cfg.block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = do.reshape(b * h, sq, d)
    # delta_i = sum_d do_i * out_i; tiny elementwise reduce, leave it to XLA
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    # per-query columns for the dq kernel, lane-major rows for dk/dv
    lse_col, delta_col = (x.reshape(b * h, sq, 1) for x in (lse, delta))
    lse_row, delta_row = (x.reshape(b * h, 1, sq) for x in (lse, delta))

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
            pl.BlockSpec((1, 1, sq), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, j: (bh, 0, 0)),
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
    )(qf, kf, vf, dof, lse_row, delta_row)

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
    )(qf, kf, vf, dof, lse_col, delta_col)[0]

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _resident_bytes(seq_q, seq_k, head_dim, dtype) -> int:
    """The resident pair (K, V in fwd/dq; Q, dO in dkv), double-buffered."""
    return 4 * head_dim * jnp.dtype(dtype).itemsize * max(seq_q, seq_k)


def _vmem_bytes(block_q, block_k, seq_q, seq_k, head_dim, dtype) -> int:
    """What the forward, the tightest of the three kernels, is reckoned
    to hold in VMEM: the resident pair, three fp32 score tiles (scores,
    probabilities, one temporary), the q and o blocks double-buffered,
    and the fp32 accumulator.  Under _VMEM_LIMIT_BYTES this agrees with
    what Mosaic compiles for a v5e at every sequence tried (a multiple
    of 512 up to 15360 in bf16 and 7680 in f32;
    tests/test_paged_attention.py keeps four of them)."""
    return (_resident_bytes(seq_q, seq_k, head_dim, dtype)
            + 3 * 4 * block_q * block_k
            + 4 * block_q * head_dim * jnp.dtype(dtype).itemsize
            + 4 * block_q * head_dim)


def flash_blocks(seq_q, seq_k, head_dim, dtype) -> Tuple[int, int]:
    """``(block_q, block_k)`` the kernels take where the caller names
    none: the largest of _BLOCKS that divide the sequences and whose
    :func:`_vmem_bytes` fit the scoped limit; one lane tile, (128, 128),
    where nothing larger does."""
    fits = [(bq, bk) for bq in _BLOCKS for bk in _BLOCKS
            if seq_q % bq == 0 and seq_k % bk == 0
            and _vmem_bytes(bq, bk, seq_q, seq_k, head_dim, dtype)
            <= _VMEM_LIMIT_BYTES]
    return max(fits, key=lambda b: (b[0] * b[1], b[1]), default=(128, 128))


def attention_path(q_shape, k_shape, dtype, *,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None, use_pallas: bool = True,
                   backend: Optional[str] = None) -> Tuple[str, str]:
    """Which route ``flash_attention`` takes for these (B, H, S, D)
    shapes, and why: ``("pallas", reason)`` or ``("blockwise", reason)``.
    Forward and backward always take the same route.  ``backend``
    defaults to ``jax.default_backend()``; blocks left ``None`` are
    :func:`flash_blocks`'s, and the reason names them and the dtype the
    MXU is fed."""
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
    auto = flash_blocks(sq, sk, d, dtype)
    block_q = auto[0] if block_q is None else block_q
    block_k = auto[1] if block_k is None else block_k
    if sq % block_q or sk % block_k:
        return "blockwise", (f"seq_q {sq} / seq_k {sk} not multiples of "
                             f"block_q {block_q} / block_k {block_k}")
    need = _resident_bytes(sq, sk, d, dtype)
    if need > _VMEM_RESIDENT_BYTES:
        return "blockwise", (
            f"seq {max(sq, sk)} keeps {need / 2 ** 20:.1f} MiB resident "
            f"in VMEM, over the {_VMEM_RESIDENT_BYTES >> 20} MiB the "
            "kernel compiles under")
    return "pallas", (
        ("interpret mode" if backend != "tpu" else "tpu backend")
        + f", blocks {block_q} x {block_k}, {jnp.dtype(dtype).name} "
        "operands, float32 accumulation")


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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: bool = True):
    """Fused attention. q, k, v: (batch, heads, seq, head_dim).

    Pallas kernels on TPU, forward and backward; the blockwise lax.scan
    elsewhere and for shapes the kernel does not take —
    :func:`attention_path` says which and why.  The kernels feed the MXU
    q, k, v and dO in the dtype they come in and accumulate in fp32.
    Blocks left ``None`` are :func:`flash_blocks`'s for the kernels and
    128 for the scan.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    if block_q is None or block_k is None:
        kernels = attention_path(q.shape, k.shape, q.dtype, block_q=block_q,
                                 block_k=block_k, use_pallas=use_pallas)[0]
        auto = (flash_blocks(q.shape[2], k.shape[2], q.shape[3], q.dtype)
                if kernels == "pallas" else (128, 128))
        block_q = auto[0] if block_q is None else block_q
        block_k = auto[1] if block_k is None else block_k
    cfg = _Config(bool(causal), float(sm_scale), int(block_q), int(block_k),
                  bool(use_pallas))
    return _flash(cfg, q, k, v)
