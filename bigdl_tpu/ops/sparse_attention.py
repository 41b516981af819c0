"""Grouped-head attention with a learned top-k selection of the keys.

The plain-XLA math shared by the three entry points of
``MultiHeadAttention`` (full forward, prefill through a cache, one-token
decode) and by the paged cache's chunk and decode routes:

  * grouped heads: ``n_heads`` query heads share ``n_kv_heads`` key/value
    heads, ``n_heads // n_kv_heads`` to one; K and V are never repeated,
  * the indexer: a small scorer beside the attention, ``I[t, s] = sum_j
    w[t, j] * ReLU(qI[t, j] . kI[s])`` over its own heads ``j`` against
    ONE index key a token, in float32; query ``t`` attends only the
    ``top_k`` keys ``s <= t`` with the largest ``I[t, s]`` (all of them
    while there are no more than ``top_k``).

Selection here is a mask at the ``top_k``-th largest score of each query
(:func:`kth_largest_key`, exact), which suits a block of queries against one
window of keys; the decode step, which has one query a slot, turns its
mask into the positions themselves and gathers those rows
(``ops/paged_attention.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .flash_attention import DEFAULT_MASK_VALUE

# Score elements a head in one block of queries against a long window:
# the float32 scores of 32 heads x 64 queries x 28,672 keys are 0.23 GB,
# and a few arrays of that size live at once.
_BLOCK_ELEMENTS = 1 << 21


def index_scores(qi, ki, w):
    """qi (B, S, Hi, Di), ki (B, L, Di), w (B, S, Hi) -> I (B, S, L)
    float32: ``sum_j w_j Hi^-1/2 ReLU(qi_j . ki) Di^-1/2``."""
    hi, di = qi.shape[-2], qi.shape[-1]
    s = jnp.einsum("bshd,bld->bshl", qi, ki,
                   preferred_element_type=jnp.float32)
    s = jax.nn.relu(s) * (di ** -0.5)
    # float32 all through: at the default precision a TPU would round the
    # scores to bfloat16 on their way into this sum, and keys near the
    # top_k-th score would change places for no reason
    return jnp.einsum("bshl,bsh->bsl", s,
                      w.astype(jnp.float32) * (hi ** -0.5),
                      precision=lax.Precision.HIGHEST)


def sort_key(x):
    """float32 -> uint32 with the same order (NaN aside)."""
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def select_mask(scores, visible, top_k: int):
    """bool (.., S, L): the ``top_k`` highest-scoring visible keys of each
    query, equal scores taken from the lowest position up (the order of
    ``lax.top_k``); every visible key where there are no more than
    ``top_k``."""
    key = jnp.where(visible, sort_key(scores), jnp.uint32(0))
    kth = kth_largest_key(key, top_k)[..., None]
    above = key > kth
    tie = visible & (key == kth)
    room = top_k - above.sum(-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def kth_largest_key(key, k: int):
    """The k-th largest of uint32 ``key`` along the last axis, exact: a
    radix select, 32 passes of compare-and-count and no sort.  With fewer
    than k keys above zero, zero comes back."""
    def body(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (key >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, prefix)

    return lax.fori_loop(0, 32, body,
                         jnp.zeros(key.shape[:-1], jnp.uint32))


def positions_of(chosen, k: int, block: int = 128):
    """The positions of the set entries of ``chosen`` (S, L) bool, in
    order, as (S, k) int32, ``-1`` past the last; L a multiple of
    ``block``.  No sort, no scatter and no search by gather (each is most
    of a decode step on a TPU): which block of ``block`` entries holds
    the j-th set entry comes from comparing j with the blocks' running
    counts, the block's own running count from a one-hot matmul (small
    integers, exact), and the place in the block from one more compare."""
    s, n = chosen.shape
    if n % block:
        block = n                  # a short window: one block
    nb = n // block
    c = chosen.reshape(s, nb, block)
    local = jnp.cumsum(c, axis=-1, dtype=jnp.int32)       # (S, nb, block)
    end = jnp.cumsum(local[..., -1], axis=-1)             # (S, nb)
    j = jnp.arange(k, dtype=jnp.int32)
    # blocks that end at or before j: the block the j-th set entry is in
    blk = (end[:, None, :] <= j[None, :, None]).sum(-1, dtype=jnp.int32)
    found = blk < nb
    blk = jnp.minimum(blk, nb - 1)
    onehot = (blk[..., None] == jnp.arange(nb)).astype(jnp.float32)
    pick = lambda a: jnp.einsum("skn,snb->skb", onehot,
                                a.astype(jnp.float32),
                                precision=lax.Precision.HIGHEST)
    before = jnp.take_along_axis(end - local[..., -1], blk, axis=1)
    rank = (j[None, :] - before + 1).astype(jnp.float32)  # 1-based, in block
    hit = (pick(local) == rank[..., None]) & (pick(c) > 0)
    within = (hit * jnp.arange(block)).sum(-1, dtype=jnp.int32)
    return jnp.where(found, blk * block + within, -1)


def masked_attention(q, k, v, mask):
    """q (B, H, S, Dh) against k / v (B, Hkv, L, Dh) under ``mask``
    (B, S, L) bool, H a multiple of Hkv.  Scores, softmax and the value
    sum in float32 (the sequence of ``MultiHeadAttention.apply_cached``);
    masked V rows are scrubbed, so a recycled page's non-finite rows
    cannot leak through a zero weight.  -> (B, H, S, Dh) in q's dtype."""
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, h // hkv, s, dh).astype(jnp.float32)
    s_ = jnp.einsum("bkgqd,bkld->bkgql", qg,
                    k.astype(jnp.float32)) / np.sqrt(dh)
    s_ = jnp.where(mask[:, None, None], s_, DEFAULT_MASK_VALUE)
    seen = mask.any(axis=1)                               # (B, L)
    v_ = jnp.where(seen[:, None, :, None], v.astype(jnp.float32), 0.0)
    # the softmax's denominator rides the value matmul as a column of
    # ones, so the scores are read twice (their maximum, then this) and
    # not three times: against a 28k-key window they are most of the time
    v_ = jnp.concatenate([v_, jnp.ones_like(v_[..., :1])], axis=-1)
    e_ = jnp.exp(s_ - lax.stop_gradient(s_.max(axis=-1, keepdims=True)))
    o = jnp.einsum("bkgql,bkld->bkgqd", e_, v_)
    o = o[..., :dh] / o[..., dh:]
    return o.reshape(b, h, s, dh).astype(q.dtype)


def ring_positions(top_page, n_cols: int, page_size: int):
    """The key position of every row of a page table whose ``n_cols``
    columns are a ring: logical page ``j`` of the sequence lies in column
    ``j % n_cols``, and a column holds the LATEST such page up to
    ``top_page`` (B,), the page of the newest row.  -> (B, n_cols *
    page_size) int32; negative where the column has held no page yet.  A
    table as wide as its sequences (no page ever recycled) reads
    ``arange`` up to the top page: the same formula serves both."""
    col = jnp.arange(n_cols, dtype=jnp.int32)
    top = top_page.astype(jnp.int32)[:, None]
    page = top - (top - col[None, :]) % n_cols            # (B, n_cols)
    pos = page[:, :, None] * page_size \
        + jnp.arange(page_size, dtype=jnp.int32)
    return pos.reshape(top.shape[0], n_cols * page_size)


def visible_keys(q_pos, kv_len, n_keys: int, index=None, top_k: int = 0,
                 window: int = 0, k_pos=None):
    """bool (B, S, L): the keys at positions ``arange(n_keys)`` that each
    query at global position ``q_pos`` (B, S) attends: ``k_pos <= q_pos``
    and ``k_pos < kv_len`` (B,), and of those, with ``index`` = (qi
    (B, S, Hi, Di), ki (B, L, Di), w (B, S, Hi)), the ``top_k`` the indexer
    scores highest.  ``window`` > 0 adds the lower bound of a
    sliding-window layer, ``k_pos > q_pos - window`` (``window`` keys, the
    query's own included).  ``k_pos`` (B, L) gives the keys' positions
    where the columns are not in order (a ring of pages,
    :func:`ring_positions`; negative: no key): the mask goes by position,
    never by column."""
    if k_pos is None:
        k_pos = jnp.arange(n_keys)
        visible = (k_pos[None, None, :] <= q_pos[:, :, None]) \
            & (k_pos[None, None, :] < kv_len[:, None, None])
        k_pos = k_pos[None] if window else None
    else:
        visible = (k_pos[:, None, :] <= q_pos[:, :, None]) \
            & (k_pos[:, None, :] < kv_len[:, None, None]) \
            & (k_pos[:, None, :] >= 0)
    if window:
        visible = visible & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    if index is not None:
        qi, ki, w = index
        visible = select_mask(index_scores(qi, ki, w), visible, top_k)
    return visible


def _in_query_blocks(fn, n_keys: int, xs, axes):
    """``fn(*xs)``, against a long window a block of queries at a time
    under ``lax.map`` (_BLOCK_ELEMENTS): ``xs[i]`` holds its queries on
    axis ``axes[i]``, as the result does on ``axes[0]``."""
    s = xs[0].shape[axes[0]]
    blk = 1 << max(4, (_BLOCK_ELEMENTS // n_keys).bit_length() - 1)
    if blk >= s or s % blk:
        return fn(*xs)

    def split(x, axis):
        shape = x.shape[:axis] + (s // blk, blk) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    out = lax.map(lambda a: fn(*a),
                  tuple(split(x, a) for x, a in zip(xs, axes)))
    out = jnp.moveaxis(out, 0, axes[0])
    return out.reshape(out.shape[:axes[0]] + (s,) + out.shape[axes[0] + 2:])


def attention_mask(q_pos, kv_len, n_keys: int, index=None, top_k: int = 0,
                   window: int = 0, k_pos=None):
    """:func:`visible_keys` as int8 (B, S, L), for a kernel that takes its
    mask whole: the selection still runs in :func:`attend`'s blocks of
    queries (the indexer's per-head scores of one block are what a few
    arrays of ``_BLOCK_ELEMENTS`` a head hold), and only the blocks' masks
    are kept."""
    if index is None:
        return visible_keys(q_pos, kv_len, n_keys, window=window,
                            k_pos=k_pos).astype(jnp.int8)
    qi, ki, w = index
    return _in_query_blocks(
        lambda q_pos, qi, w: visible_keys(
            q_pos, kv_len, n_keys, (qi, ki, w), top_k, window,
            k_pos).astype(jnp.int8),
        n_keys, (q_pos, qi, w), (1, 1, 1))


def attend(q, k, v, q_pos, kv_len, index=None, top_k: int = 0,
           window: int = 0, k_pos=None):
    """Causal attention of queries at global positions ``q_pos`` (B, S)
    over keys at positions ``arange(L)`` (or ``k_pos`` (B, L)) of which
    the first ``kv_len`` (B,) are written.  ``index`` = (qi (B, S, Hi, Di),
    ki (B, L, Di), w (B, S, Hi)) adds the learned selection of ``top_k``
    keys a query, ``window`` > 0 a sliding window's lower bound.  Long
    windows are walked a block of queries at a time."""
    n_keys = k.shape[2]

    def block(q, q_pos, qi=None, w=None):
        return masked_attention(q, k, v, visible_keys(
            q_pos, kv_len, n_keys,
            None if index is None else (qi, index[1], w), top_k, window,
            k_pos))

    xs = (q, q_pos) if index is None else (q, q_pos, index[0], index[2])
    return _in_query_blocks(block, n_keys, xs, (2, 1, 1, 1)[:len(xs)])


__all__ = ["attend", "attention_mask", "index_scores", "kth_largest_key",
           "masked_attention", "positions_of", "ring_positions", "select_mask",
           "sort_key", "visible_keys"]
