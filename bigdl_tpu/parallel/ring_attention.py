"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

The reference framework scales sequence models only by unrolling RNNs
(nn/Recurrent.scala); long-context attention is beyond its scale.  Here the
sequence dimension is sharded over the mesh ``sp`` axis and full (exact)
attention is computed by rotating key/value chunks around the ring with
``lax.ppermute`` — each hop rides a single ICI neighbour link while the
local chunk's flash-attention block computes, and the online-softmax
accumulators (acc, m, l) merge chunks in any arrival order.

Must be called *inside* ``shard_map`` (or pmap) with q, k, v sharded over
``axis_name`` on their sequence dimension.  Causal masking is handled with
global token positions derived from ``lax.axis_index``, so cross-chunk
causality is exact.  Differentiable: AD transposes the ppermute ring into
the reverse rotation (the backward ring pass of the ring-attention paper).

Use :func:`ring_attention_shmap` to call it on globally-sharded arrays from
inside a jit/GSPMD region.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import (chunk_merge, chunk_merge_blockwise,
                                   finalize, DEFAULT_MASK_VALUE)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   block_k: Optional[int] = 1024):
    """Exact attention with seq sharded over ``axis_name``.

    q, k, v: (batch, heads, seq_local, head_dim) — the local shard.
    Returns the local shard of the attention output, same shape as q.
    ``block_k`` caps the held chunk's score-matrix width (flash-style
    sub-blocking) so memory stays O(s_local * block_k) at long context;
    ``None`` merges each chunk in one piece.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    s_total = sp * s_local
    q_pos = idx * s_local + jnp.arange(s_local)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def merge(k_c, v_c, acc, m, l, k_pos):
        if block_k is None:
            return chunk_merge(q, k_c, v_c, acc, m, l, q_pos, k_pos,
                               s_total, sm_scale, causal)
        return chunk_merge_blockwise(q, k_c, v_c, acc, m, l, q_pos, k_pos,
                                     s_total, sm_scale, causal,
                                     block_k=block_k)

    def step(carry, t):
        k_c, v_c, acc, m, l = carry
        src = (idx - t) % sp                 # origin rank of the held chunk
        k_pos = src * s_local + jnp.arange(s_local)
        if causal:
            # a chunk strictly in this rank's future contributes nothing;
            # skip its FLOPs entirely (per-device scalar cond)
            acc, m, l = lax.cond(
                src > idx,
                lambda a, mm, ll: (a, mm, ll),
                lambda a, mm, ll: merge(k_c, v_c, a, mm, ll, k_pos),
                acc, m, l)
        else:
            acc, m, l = merge(k_c, v_c, acc, m, l, k_pos)
        # rotate while (in a real schedule, overlapping) the next compute;
        # after sp hops k/v are home again, which keeps AD symmetric.
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c, acc, m, l), None

    init = (k, v,
            jnp.zeros((b, h, s_local, d), jnp.float32),
            jnp.full((b, h, s_local), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((b, h, s_local), jnp.float32))
    (_, _, acc, m, l), _ = lax.scan(step, init, jnp.arange(sp))
    out, _ = finalize(acc, m, l)
    return out.astype(q.dtype)


def ring_attention_shmap(q, k, v, mesh: Mesh, causal: bool = False,
                         sm_scale: Optional[float] = None,
                         batch_axis: Optional[str] = "dp",
                         head_axis: Optional[str] = "tp",
                         seq_axis: str = "sp",
                         block_k: Optional[int] = 1024):
    """shard_map wrapper: (B, H, S, D) global arrays, batch over ``dp``,
    heads over ``tp``, sequence over ``sp``.  Heads are embarrassingly
    parallel, so tensor parallelism needs no collective here; only the
    sp ring communicates."""
    if seq_axis not in mesh.axis_names:
        raise ValueError(
            f"ring_attention_shmap: seq_axis {seq_axis!r} is not a mesh "
            f"axis {mesh.axis_names}; for unsharded sequences use "
            "ops.flash_attention instead")

    def ax(name):
        return name if name and name in mesh.axis_names else None

    spec = P(ax(batch_axis), ax(head_axis), ax(seq_axis), None)
    fn = partial(ring_attention, axis_name=seq_axis, causal=causal,
                 sm_scale=sm_scale, block_k=block_k)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
