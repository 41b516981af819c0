"""Paged attention: Pallas TPU kernels + gathered routes.

Decode: one new token per slot attends to that slot's K and V where they
lie in the page pool (``serving/kvcache.py``: ``(n_pages, page_size,
kv_heads, head_dim)``, one pool a layer), up to the slot's length, in the
dtype they are stored in.  Prefill in chunks: the queries of one chunk of
one prompt attend to that slot's pages the same way
(:func:`paged_chunk_attention`, below the decode kernel).

  * kernel — one ``pallas_call`` a layer.  The pools stay in HBM; the
    page tables and lengths arrive by scalar prefetch.  A scalar
    prologue lists the work as (slot, block of pages) items, live slots
    only, and one double-buffered loop walks the list: while item ``i``
    is computed the pages of item ``i + 1`` (the next block of the same
    slot, or the first of the next live slot) are already in flight.
    One page is one contiguous copy for all heads.  Scores, softmax and
    both accumulations are float32; K and V enter the MXU as stored.
    Query heads may be grouped over fewer KV heads (K and V are never
    repeated), and a layer may have a sliding window: its table is then
    a RING of pages, the work list holds only the blocks with a page the
    window shows, and a row is masked by its POSITION
    (``sparse_attention.ring_positions``' rule), not by its column.  All
    of that is static: one algorithm, and with as many KV heads as query
    heads and no window the arithmetic it had before it learnt them.
  * gathered routes — what runs on CPU, for an int8 pool, for rows that
    do not tile, and what the kernel is tested against:
    :func:`attend_window` over a window gathered by
    ``PagedKVCache.gather_window``, and :func:`gathered_attention`
    (grouped heads, masks by position) for a cache with window layers.

:func:`paged_attention_path` is the one place that decides which route a
pool takes and says why (as ``ops.attention_path`` does for the flash
kernel); :func:`decode_blocks` chooses the pages a block from the page's
shape.  A cache with window layers calls :func:`_window_attend`, a jitted
entry under a name of its own, which picks the kernel or the gathered
math by the same function.

The kernel keeps the pool's token-major layout, so K of a block is
``(tokens * kv_heads, head_dim)`` after a free reshape (a row of 16 or 8
heads is whole sublane tiles, a row of 4 or 2 a tile of its own: the
pool's HBM tiles are the buffer's), and the scores of all heads come from
ONE matmul ``q (heads, head_dim) @ K^T -> (heads, tokens * kv_heads)`` of
which only the entries whose column's head is the row's own KV head are
kept: ``kv_heads`` times the useful FLOPs on an MXU that idles anyway,
and no relayout of K or V.  The masked probabilities are exactly zero, so
``p @ V`` over the same flat axis is the per-head value sum.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_MASK_VALUE
from .sparse_attention import (index_scores, masked_attention,
                               positions_of, ring_positions, select_mask)

# Test hook: when True the kernel runs in interpret mode, so the TPU
# code path itself (not the window route) is exercised on CPU.
_INTERPRET = False

def attend_window(q, k_win, v_win, positions):
    """Single-token attention of q (B, H, 1, Dh) against a gathered
    window k_win/v_win (B, Hkv, W, Dh), H a multiple of Hkv: the einsum /
    scale / mask-value / softmax sequence of
    ``MultiHeadAttention.apply_cached``, in float32.  ``positions`` (B,)
    is each row's token index; keys at ``k_pos > positions[b]``
    (unwritten, or a recycled page's stale rows) are masked out and their
    V rows scrubbed: masked weights are exactly 0, but 0 * NaN = NaN, and
    a recycled page can hold non-finite rows from a poisoned publication.
    Returns (B, H, 1, Dh) in q's dtype."""
    k_pos = jnp.arange(k_win.shape[2])
    # same semantics as _attn_mask(positions, k_pos, pos+1, True) per
    # row: causal (k <= q) subsumes the kv_len bound at s=1
    mask = k_pos[None, :] <= positions[:, None]          # (B, W)
    return masked_attention(q, k_win, v_win, mask[:, None, :])


# The two halves of the sparse decode route, each jitted under a name of
# its own: the compiled step's metadata then says which of its ops score
# and select and which gather and attend (a roofline share each).
@functools.partial(jax.jit, static_argnames=("top_k",))
def _index_topk(qi, w, ki_win, lengths, *, top_k):
    """qi (S, Hi, Di) and w (S, Hi) of each slot's new token against its
    index keys ki_win (S, W, Di): the positions (S, min(top_k, W)) of the
    ``top_k`` highest index scores among rows ``0..lengths[s]``, ``-1``
    where the slot has fewer."""
    n_rows = ki_win.shape[1]
    scores = index_scores(qi[:, None], ki_win, w[:, None])
    visible = jnp.arange(n_rows)[None, :] <= lengths[:, None]
    # a mask at the top_k-th score (exact, no sort), then the mask's set
    # positions, in order
    chosen = select_mask(scores, visible[:, None], top_k)[:, 0]
    return positions_of(chosen, min(top_k, n_rows))


@jax.jit
def _sparse_attend(q, k_pool, v_pool, tables, positions):
    """q (S, H, Dh) over the rows at ``positions`` (S, K; -1: none) of
    each slot, gathered from the pools through the page tables."""
    _, page_size, hkv, dh = k_pool.shape
    page = jnp.take_along_axis(tables, jnp.maximum(positions, 0) // page_size,
                               axis=1)
    ok = (positions >= 0) & (page >= 0)
    row = jnp.where(ok, page * page_size + positions % page_size, 0)

    def rows(pool):
        sel = jnp.take(pool.reshape(-1, hkv, dh), row, axis=0)
        return jnp.swapaxes(sel, 1, 2)                    # (S, Hkv, K, Dh)

    return masked_attention(q[:, :, None], rows(k_pool), rows(v_pool),
                            ok[:, None, :])[:, :, 0]


def sparse_paged_attention(q, qi, w, k_pool, v_pool, ki_win, tables,
                           lengths, top_k: int):
    """Decode attention with the selection inside: score each slot's live
    index keys, take the ``top_k`` best, gather those K and V rows
    wherever in the pages they lie, attend.  The row just written at
    ``lengths[s]`` is a candidate like any other.  -> (S, H, Dh)."""
    positions = _index_topk(qi, w, ki_win, lengths, top_k=int(top_k))
    return _sparse_attend(q, k_pool, v_pool, tables, positions)


def gathered_attention(q, k_pool, v_pool, tables, lengths, window):
    """:func:`_window_attend` in XLA: every slot's whole table gathered
    (``-1``: zeros), then masked by position.  What runs where the kernel
    does not, and what the kernel is tested against."""
    n_pages, page_size, hkv, dh = k_pool.shape
    s, h, _ = q.shape
    idx = jnp.where(tables < 0, n_pages, tables)
    k_pos = ring_positions(lengths // page_size, tables.shape[1], page_size)
    mask = (k_pos >= 0) & (k_pos <= lengths[:, None])
    if window:
        mask = mask & (k_pos > lengths[:, None] - window)

    def rows(pool):
        pages = jnp.take(pool, idx, axis=0, mode="fill", fill_value=0)
        return pages.reshape(s, -1, hkv, dh)              # (S, L, Hkv, Dh)

    k = rows(k_pool)
    v = jnp.where(mask[:, :, None, None], rows(v_pool), 0)
    # q meets K in the wider of their dtypes (an upcast is exact)
    qk = jnp.promote_types(q.dtype, k.dtype)
    qg = q.reshape(s, hkv, h // hkv, dh).astype(qk)
    sc = jnp.einsum("skgd,slkd->skgl", qg, k.astype(qk),
                    preferred_element_type=jnp.float32) * dh ** -0.5
    sc = jnp.where(mask[:, None, None, :], sc, DEFAULT_MASK_VALUE)
    e = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
    o = jnp.einsum("skgl,slkd->skgd", e.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / e.sum(axis=-1, keepdims=True)
    return o.reshape(s, h, dh).astype(q.dtype)


# The decode attention of a cache with sliding-window layers (its global
# layers too), jitted under a name of its own for the reason the two
# above are: the step's device trace shows every layer's attention apart
# from the rest, whichever route it takes.  The route is chosen here, while
# tracing, from what `paged_attention_path` can observe; the test hook
# `_INTERPRET` is read then too, so a test that turns it clears this
# function's cache.
@functools.partial(jax.jit, static_argnames=("window",))
def _window_attend(q, k_pool, v_pool, tables, lengths, *, window):
    """q (S, H, Dh) of each slot's new token, at position ``lengths[s]``,
    over the pages of ``tables`` (S, P; ``-1``: none): a table as wide as
    the longest sequence, or (``window`` > 0: a sliding-window layer) a
    RING of P pages in which logical page ``j`` lies in column ``j % P``.
    The mask goes by each row's position
    (:func:`~bigdl_tpu.ops.sparse_attention.ring_positions`), not by its
    column: ``lengths[s] - window < position <= lengths[s]``, so rows not
    yet written, and a recycled page's stale rows (a ring that holds
    ``window`` rows and a page more never shows one inside the window),
    are hidden, their K masked by a select and their V zeroed.  K and V
    enter the matmuls as stored, the probabilities in V's dtype; scores,
    softmax and both sums are float32.  On a TPU, where the rows tile
    (:func:`paged_attention_path`), the kernel reads the live slots'
    visible pages in place; elsewhere :func:`gathered_attention`.
    -> (S, H, Dh) in q's dtype."""
    _, page_size, hkv, dh = k_pool.shape
    route, _ = paged_attention_path(k_pool.dtype, hkv, dh,
                                    q_heads=q.shape[1], page_size=page_size)
    if route == "pallas":
        return paged_attention(q, k_pool, v_pool, tables, lengths,
                               window=window)
    return gathered_attention(q, k_pool, v_pool, tables, lengths, window)


# Latent attention: a token's ONE cached row (``rank`` columns of latent,
# which are the value too, then the rope key every head shares) under many
# query heads.  Both pieces gather the table's rows (a row is narrow: a
# slot's whole table is a few megabytes) and are jitted under names of
# their own, as the pieces above are, so that a device trace shows them
# apart; neither is a kernel yet.
@functools.partial(jax.jit, static_argnames=("rank", "sm_scale"))
def _latent_attend(q, new_rows, pool, tables, lengths, *, rank, sm_scale):
    """Absorbed decode attention: q (S, H, W) = ``[q_n W_uk^T ; q_r]`` of
    each slot's new token, at position ``lengths[s]``, over the rows
    BEFORE it in ``pool`` (n_pages, page_size, W) through ``tables`` (S,
    P; ``-1``: none), and over the token's own row ``new_rows`` (S, W),
    which need not be in the pool yet: the pool is read as the step was
    handed it (its rows' write may come after), and the result is that of
    write-then-attend.  Rows from the position on are masked and their
    values zeroed (a recycled page's stale rows).  Rows enter the matmuls
    as stored, the probabilities in their dtype; scores, softmax and both
    sums are float32.  One LIVE slot at a time, in a loop as long as
    there are slots with a page, its pages scored as they lie (never
    flattened to rows): a slot's table is a few megabytes, where all the
    slots' at once are five passes over a pool's worth (on a v5e, 24
    slots x 14,336 rows: 8.4 ms a layer at once; a slot at a time 2.1 ms
    with 24 live and 1.1 with 8; my chip runs, PR 34).  A dead slot reads
    zeros.  -> ``softmax . c`` (S, H, rank) in q's dtype."""
    _, page_size, _ = pool.shape
    s, h, _ = q.shape
    live = tables[:, 0] >= 0
    order = jnp.argsort(~live, stable=True)        # the live slots first
    k_pos = jnp.arange(tables.shape[1])[:, None] * page_size \
        + jnp.arange(page_size)[None, :]
    qk = jnp.promote_types(q.dtype, pool.dtype)

    def one(i, out):
        slot = order[i]
        q_s = lax.dynamic_index_in_dim(q, slot, 0, keepdims=False)
        own = lax.dynamic_index_in_dim(new_rows, slot, 0, keepdims=False)
        table = lax.dynamic_index_in_dim(tables, slot, 0, keepdims=False)
        pages = jnp.take(pool, jnp.maximum(table, 0), axis=0, mode="clip")
        # (a column with no page lies past the position: masked)
        mask = k_pos < lengths[slot]                       # (P, page)
        c = jnp.where(mask[:, :, None], pages[..., :rank], 0)
        sc = jnp.einsum("hd,pld->hpl", q_s.astype(qk), pages.astype(qk),
                        preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(mask[None], sc, DEFAULT_MASK_VALUE)
        sc_own = jnp.einsum("hd,d->h", q_s.astype(qk), own.astype(qk),
                            preferred_element_type=jnp.float32) * sm_scale
        top = jnp.maximum(sc.max(axis=(1, 2)), sc_own)
        e = jnp.exp(sc - top[:, None, None])
        e_own = jnp.exp(sc_own - top)
        o = jnp.einsum("hpl,plr->hr", e.astype(c.dtype), c,
                       preferred_element_type=jnp.float32) \
            + e_own.astype(c.dtype).astype(jnp.float32)[:, None] \
            * own[:rank].astype(jnp.float32)[None, :]
        den = e.sum(axis=(1, 2)) + e_own
        return lax.dynamic_update_index_in_dim(
            out, (o / den[:, None]).astype(q.dtype), slot, 0)

    return lax.fori_loop(0, live.sum(), one, jnp.zeros((s, h, rank), q.dtype))


# Heads a block of the chunk's attention: the float32 scores of one step
# of the walk are ``heads x chunk x keys x 4`` bytes (2 x 512 x 1,024: 4
# MB), never those of all the heads at once (128 x 512 x 1,024: 268 MB),
# nor those of the whole table (2 x 512 x 12,288: 50 MB).  On a v5e at 128
# heads x 512 queries x 12,288 keys, up-projected, the whole table scored
# in one step: 1 / 2 / 4 / 8 / 16 heads a step took 7.5 / 8.0 / 8.6 /
# 15.7 / 16.4 ms a call.
_LATENT_HEAD_BLOCK = 2
# Keys a step of the walk, at most (whole pages: 8 of 128 rows).
_LATENT_KEY_BLOCK = 1024
# Which formula a chunk takes (the same attention either way): absorbed
# scores the queries against the rows as they lie (2 x 512 x keys x 128 x
# 1,088 FLOPs); up-projected makes a block of heads' keys and values from
# the rows first (2 x keys x 512 x 32,768 + 2 x 512 x keys x 128 x 320:
# half the work).  At the shapes above: absorbed 12.2-12.3 ms a call at
# any block, up-projected 8.0 (59% of the chip's peak).
_LATENT_CHUNK_ABSORBED = False


def latent_chunk_formula() -> str:
    return "absorbed" if _LATENT_CHUNK_ABSORBED else "up-projected"


def latent_chunk_walk(start, chunk: int, page_size: int, n_pages: int):
    """``(steps, keys)`` of a chunk's latent walk over a table of
    ``n_pages`` pages: ``keys`` rows a step (whole pages, 1,024 rows in
    pages of 128, the table's at most) and the steps that reach the
    chunk's last row, ``cdiv(start + chunk, keys)`` (traced where
    ``start`` is)."""
    keys = max(1, min(n_pages, _LATENT_KEY_BLOCK // page_size)) * page_size
    return (start + chunk + keys - 1) // keys, keys


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "absorbed"))
def _latent_chunk_attend(q, w_uk, w_uv, pool, table, start, *, rank,
                         sm_scale, absorbed):
    """A prompt chunk's attention over its slot's latent rows: q (H, C,
    nope + rope) un-absorbed, at positions ``start + arange(C)``; W_uk
    (rank, H, nope) and W_uv (rank, H, v) the two halves of the
    up-projection; ``table`` (P,) the pages (``-1``: none).  Only the
    rows up to the chunk's end are scored: the table's rows are walked in
    steps of :func:`latent_chunk_walk`'s keys, as many as reach ``start +
    C`` (a traced count: one program for every offset), under a running
    maximum, denominator and sum, all float32.  A step's rows at or past
    ``start + C`` (a recycled page's stale ones) are zeroed before any
    matmul.  In blocks of ``_LATENT_HEAD_BLOCK`` heads, so that neither
    the scores nor the up-projected keys and values of all heads are ever
    whole; the blocks of heads are the outer loop and the walk the inner
    one, so that a block's accumulators (``heads x C x v``) are the only
    ones that live, and each block reads the weights once where a walk
    outside would read all of them every step (on a v5e at 128 heads x
    512 queries, two heads a block: 4.30 / 8.93 ms a call at 4,608 /
    11,776 keys, against 4.52 / 9.67 with the walk outside).
    ``absorbed`` picks the formula.  -> (H, C, v) in q's dtype."""
    n_pages, page_size, width = pool.shape
    h, chunk, _ = q.shape
    nope = w_uk.shape[-1]
    steps, kb = latent_chunk_walk(start, chunk, page_size, table.shape[0])
    # the table in whole steps, a page of -1 (or of the padding) read as
    # zeros; gathered once, a few megabytes, as the pool lies
    idx = jnp.where(table < 0, n_pages, table)
    idx = jnp.pad(idx, (0, -idx.shape[0] % (kb // page_size)),
                  constant_values=n_pages)
    rows = jnp.take(pool, idx, axis=0, mode="fill",
                    fill_value=0).reshape(-1, kb, width)
    q_pos = start + jnp.arange(chunk)
    hb = min(_LATENT_HEAD_BLOCK, h)
    assert h % hb == 0, (h, hb)

    def block(args):
        q_b, uk, uv = args          # (hb, C, .), (hb, rank, nope), (hb, rank, v)
        if absorbed:
            q_b = jnp.concatenate(
                [jnp.einsum("hcn,hrn->hcr", q_b[..., :nope], uk),
                 q_b[..., nope:]], -1)

        def step(i, carry):
            top, den, acc = carry
            k_pos = i * kb + jnp.arange(kb)
            r = jnp.where((k_pos < start + chunk)[:, None],
                          lax.dynamic_index_in_dim(rows, i, 0, False), 0)
            c = r[:, :rank]
            if absorbed:
                sc = jnp.einsum("hcd,ld->hcl", q_b, r,
                                preferred_element_type=jnp.float32)
            else:
                keys = jnp.concatenate(
                    [jnp.einsum("lr,hrn->hln", c, uk),
                     jnp.broadcast_to(r[:, rank:], (hb, kb, width - rank))],
                    -1)
                sc = jnp.einsum("hcd,hld->hcl", q_b, keys,
                                preferred_element_type=jnp.float32)
            sc = jnp.where((k_pos[None, :] <= q_pos[:, None])[None],
                           sc * sm_scale, DEFAULT_MASK_VALUE)
            new_top = jnp.maximum(top, sc.max(axis=-1))
            e = jnp.exp(sc - new_top[..., None])
            scale = jnp.exp(top - new_top)
            if absorbed:
                o = jnp.einsum("hcl,lr->hcr", e.astype(c.dtype), c,
                               preferred_element_type=jnp.float32)
            else:
                o = jnp.einsum("hcl,hlv->hcv", e.astype(c.dtype),
                               jnp.einsum("lr,hrv->hlv", c, uv),
                               preferred_element_type=jnp.float32)
            return (new_top, scale * den + e.sum(axis=-1),
                    scale[..., None] * acc + o)

        # (every query sees key 0, so the first step sets a finite max)
        _, den, acc = lax.fori_loop(
            0, steps, step,
            (jnp.full((hb, chunk), DEFAULT_MASK_VALUE, jnp.float32),
             jnp.zeros((hb, chunk), jnp.float32),
             jnp.zeros((hb, chunk, rank if absorbed else uv.shape[-1]),
                       jnp.float32)))
        o = acc / den[..., None]
        if absorbed:
            o = jnp.einsum("hcr,hrv->hcv", o.astype(uv.dtype), uv,
                           preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    by_block = lambda a: a.reshape((h // hb, hb) + a.shape[1:])
    out = lax.map(block, (by_block(q),
                          by_block(jnp.transpose(w_uk, (1, 0, 2))),
                          by_block(jnp.transpose(w_uv, (1, 0, 2)))))
    return out.reshape((h,) + out.shape[2:])


def paged_attention_path(pool_dtype, n_heads: int, head_dim: int, *,
                         q_heads: Optional[int] = None,
                         page_size: Optional[int] = None,
                         backend: Optional[str] = None) -> Tuple[str, str]:
    """Which route decode attention takes over a pool of this dtype and
    row geometry (``n_heads`` KV heads a row, ``q_heads`` query heads over
    them, as many by default), and why: ``("pallas", reason)`` or
    ``("gather", reason)``.  ``backend`` defaults to
    ``jax.default_backend()``."""
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and not _INTERPRET:
        return "gather", f"backend {backend!r} is not tpu"
    dtype = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return "gather", (f"pool dtype {dtype.name} is not a float: the "
                          "window route dequantizes")
    if head_dim % 128:
        return "gather", (f"head_dim {head_dim} is not a multiple of 128 "
                          "(one lane tile)")
    q_heads = q_heads or n_heads
    if q_heads % n_heads:
        return "gather", (f"{q_heads} query heads do not group over "
                          f"{n_heads} KV heads")
    # a page's rows as (page_size x heads, head_dim) have to be the page
    # as it lies: whole sublane tiles of heads, or a tile of just the
    # heads (4 or 2 of them, 32 bits a sublane at least)
    if n_heads % 8 and (8 % n_heads or n_heads * dtype.itemsize < 4):
        return "gather", (f"n_heads {n_heads} is not a multiple of 8 "
                          "(one sublane tile) nor a tile of its own")
    if page_size is not None and decode_blocks(
            page_size, n_heads, head_dim, dtype) * page_size * n_heads % 128:
        return "gather", (f"a block of pages of {page_size} rows of "
                          f"{n_heads} heads is no whole lane tile of keys")
    how = "rows tile" if q_heads == n_heads else \
        f"{q_heads // n_heads} query heads a KV head, rows tile"
    return "pallas", ("interpret mode" if backend != "tpu"
                      else "tpu backend") + f", float pool, {how}"


# K of one block of pages, at most: V takes as much, and both are
# double-buffered (4 MiB of VMEM in all); and the pages of one, at most.
# On a v5e at 28 / 4 heads x 128 over pages of 128 rows (PERF.md, PR 33):
# 2 / 4 / 8 / 16 pages a block take 173 / 143 / 136 / 152 us a call.
_BLOCK_BYTES = 2 ** 20
_BLOCK_MAX_PAGES = 8


def decode_blocks(page_size: int, kv_heads: int, head_dim: int,
                  dtype) -> int:
    """Pages the decode kernel fetches per wait, from the page's shape: 8
    pages of 16 rows x 16 heads x 128 in bf16 are 128 keys, 512 KiB each
    of K and V; 8 pages of 128 rows x 4 heads are 1,024 keys, 1 MiB."""
    page_bytes = page_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(_BLOCK_MAX_PAGES, _BLOCK_BYTES // page_bytes))


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            item_slot, item_blk, k_buf, v_buf, sem, *,
            page_size, max_pages, sm_scale, window):
    n_slots, n_heads, head_dim = q_ref.shape
    ppb, kv_heads = k_buf.shape[1], k_buf.shape[3]
    group = n_heads // kv_heads           # query heads a KV head
    block = ppb * page_size               # tokens a block
    flat = block * kv_heads               # the matmuls' flat key axis
    max_items = item_slot.shape[0]
    n_blocks = max_items // n_slots

    def live_pages(s):
        # a dead slot (no first page) has no work; a live one attends
        # the token just written too: ceil((length + 1) / page_size)
        return jnp.where(tables_ref[s * max_pages] >= 0,
                         lengths_ref[s] // page_size + 1, 0)

    def rows_seen(s, c):
        # [lo, hi) of the rows of table column `c` that slot `s`'s query
        # at t sees through a window: the column holds the LATEST logical
        # page `c (mod max_pages)` up to t's own (a table in order reads
        # `c` itself, or below 0 past t's page), and a row's position is
        # its page's and its own: t - window < position <= t
        t = lengths_ref[s]
        top = t // page_size
        page = top - lax.rem(
            top - jnp.minimum(c, max_pages - 1) + max_pages, max_pages)
        lo = jnp.maximum(t - window + 1 - page * page_size, 0)
        hi = jnp.minimum(t + 1 - page * page_size, page_size)
        return lo, jnp.where((c < max_pages) & (page >= 0), hi, 0)

    def list_slot(s, n):
        def put(b, n):
            item_slot[n] = s
            item_blk[n] = b
            return n + 1

        if not window:
            return lax.fori_loop(0, pl.cdiv(live_pages(s), ppb), put, n)

        # the pages a window shows lie in `seen` columns of the ring from
        # column `first` on, around its end: the blocks that hold one
        t = lengths_ref[s]
        oldest = jnp.maximum(t - window + 1, 0) // page_size
        seen = jnp.where(tables_ref[s * max_pages] >= 0,
                         t // page_size + 1 - oldest, 0)
        first = lax.rem(oldest, max_pages)

        def put_if_seen(b, n):
            c0, c1 = b * ppb, jnp.minimum((b + 1) * ppb, max_pages)
            hit = ((c0 < jnp.minimum(first + seen, max_pages)) & (c1 > first)
                   | (c0 < first + seen - max_pages)) & (seen > 0)
            return jnp.where(hit, put(b, n), n)
        return lax.fori_loop(0, n_blocks, put_if_seen, n)

    n_items = lax.fori_loop(0, n_slots, list_slot, 0)

    def copies(i, buf, act):
        s, b = item_slot[i], item_blk[i]

        def copy_page(j, _):
            pj = b * ppb + j
            page = tables_ref[s * max_pages + jnp.minimum(pj, max_pages - 1)]
            if window:
                lo, hi = rows_seen(s, pj)
                wanted = lo < hi
            else:
                wanted = pj < live_pages(s)

            # a -1 entry is never dereferenced
            @pl.when(wanted & (page >= 0))
            def _():
                for hbm, vmem, which in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    act(pltpu.make_async_copy(
                        hbm.at[page], vmem.at[buf, j], sem.at[which, buf]))

        # (a loop, not `for j in range(ppb)`: the kernel is traced in every
        # process that serves, and eight copies of this at each of its
        # three sites were most of a second of it)
        lax.fori_loop(0, ppb, copy_page, None)

    @pl.when(n_items > 0)
    def _():
        copies(0, 0, lambda c: c.start())

    o_ref[...] = jnp.zeros_like(o_ref)    # dead slots read finite zeros

    def body(i, carry):
        m, l, acc = carry
        buf = i % 2

        @pl.when(i + 1 < n_items)
        def _():
            copies(i + 1, 1 - buf, lambda c: c.start())

        copies(i, buf, lambda c: c.wait())
        s, b = item_slot[i], item_blk[i]
        # the slot's first block (block 0 of a table; of a ring the one
        # where the window's oldest page lies)
        first = (i == 0) | (item_slot[jnp.maximum(i - 1, 0)] != s)
        m = jnp.where(first, DEFAULT_MASK_VALUE, m)
        l = jnp.where(first, 0.0, l)
        acc = jnp.where(first, 0.0, acc)

        # which keys of the block the slot sees.  Masked probabilities are
        # exactly 0, but 0 * NaN = NaN: the V rows it does not see (a
        # recycled page's stale rows, or what an earlier item left in the
        # buffer) are scrubbed
        col = lax.broadcasted_iota(jnp.int32, (n_heads, flat), 1)
        if window:
            # a row's place in its page decides, page by page
            at = lax.broadcasted_iota(jnp.int32, (1, flat), 1)

            def page_rows(j, seen):
                lo, hi = rows_seen(s, b * ppb + j)

                @pl.when((lo > 0) | (hi < page_size))
                def _():
                    tok = lax.broadcasted_iota(jnp.int32, v_buf.shape[2:], 0)
                    v_buf[buf, j] = jnp.where((tok >= lo) & (tok < hi),
                                              v_buf[buf, j], 0)

                return jnp.where((at >= (j * page_size + lo) * kv_heads)
                                 & (at < (j * page_size + hi) * kv_heads),
                                 1, seen)

            seen = lax.fori_loop(0, ppb, page_rows,
                                 jnp.zeros((1, flat), jnp.int32)) > 0
        else:
            # the block's first `keys`; only the slot's last block has
            # rows past them
            keys = lengths_ref[s] + 1 - b * block
            seen = col < keys * kv_heads

            @pl.when(keys < block)
            def _():
                shape = v_buf.shape[1:]
                tok = (lax.broadcasted_iota(jnp.int32, shape, 0) * page_size
                       + lax.broadcasted_iota(jnp.int32, shape, 1))
                v_buf[buf] = jnp.where(tok < keys, v_buf[buf], 0)

        q = q_ref[s]                                      # (H, Dh)
        k = k_buf[buf].reshape(flat, head_dim)
        s_ = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * sm_scale
        # a column's KV head is the row's own
        row = lax.broadcasted_iota(jnp.int32, (n_heads, flat), 0)
        if group > 1:
            row = row // group
        s_ = jnp.where((lax.rem(col, kv_heads) == row) & seen, s_,
                       DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)

        v = v_buf[buf].reshape(flat, head_dim)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)

        @pl.when((i + 1 == n_items)
                 | (item_slot[jnp.minimum(i + 1, max_items - 1)] != s))
        def _():
            o_ref[s] = (acc / l).astype(o_ref.dtype)

        return m_new, l, acc

    lax.fori_loop(0, n_items, body, (
        jnp.full((n_heads, 1), DEFAULT_MASK_VALUE, jnp.float32),
        jnp.zeros((n_heads, 1), jnp.float32),
        jnp.zeros((n_heads, head_dim), jnp.float32)))


def paged_attention(q, k_pool, v_pool, tables, lengths, *, window: int = 0,
                    pages_per_block: Optional[int] = None):
    """Decode attention over a page pool, in place.

    q (slots, heads, head_dim); k_pool / v_pool (n_pages, page_size,
    kv_heads, head_dim) as ``PagedKVCache`` lays them out, ``heads`` a
    multiple of ``kv_heads``; tables (slots, max_pages) int32, ``-1``
    where no page is held; lengths (slots,) int32, each slot's length
    BEFORE the token just written, which is attended too.  ``window`` >
    0: a sliding-window layer, whose table is a ring (see
    :func:`_window_attend`) and whose query sees ``window`` keys.
    ``pages_per_block``: :func:`decode_blocks`'s where not given.
    Returns (slots, heads, head_dim) in q's dtype; a dead slot
    (``tables[s, 0] < 0``) reads zeros."""
    if pages_per_block is None:
        pages_per_block = decode_blocks(*k_pool.shape[1:], k_pool.dtype)
    return _paged_attention(q, k_pool, v_pool, tables, lengths,
                            window=int(window),
                            pages_per_block=int(pages_per_block),
                            interpret=_INTERPRET)


# jitted, so that a step which calls it once a layer traces the kernel
# and lowers it to Mosaic once: sixteen lowerings of the same kernel were
# 3 s of every DecodeEngine.warmup(), on a warm compile cache too
@functools.partial(jax.jit, static_argnames=("window", "pages_per_block",
                                             "interpret"))
def _paged_attention(q, k_pool, v_pool, tables, lengths, *, window,
                     pages_per_block, interpret):
    n_slots, n_heads, head_dim = q.shape
    _, page_size, kv_heads, _ = k_pool.shape
    max_pages = tables.shape[1]
    ppb = min(pages_per_block, max_pages)
    max_items = n_slots * -(-max_pages // ppb)
    buf = pltpu.VMEM((2, ppb, page_size, kv_heads, head_dim), k_pool.dtype)
    kernel = functools.partial(_kernel, page_size=page_size,
                               max_pages=max_pages,
                               sm_scale=head_dim ** -0.5, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((n_slots, n_heads, head_dim),
                             lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((n_slots, n_heads, head_dim),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.SMEM((max_items,), jnp.int32),
                pltpu.SMEM((max_items,), jnp.int32),
                buf, buf,
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)


# --------------------------------------------------------------------- #
# a prefill chunk against its slot's pages                              #
# --------------------------------------------------------------------- #
# Keys a grid step of the chunk kernel aims at, the pages it may take to
# get there (each page of K and of V is an operand of its own), and the
# VMEM the call asks for: the query heads of one KV head stay resident
# with their float32 accumulators while the keys stream by.  On a v5e at
# 8 heads x 512 queries x 128 against 28,672 keys (PERF.md, PR 31): 1.99
# ms a call at 1,024 keys a step, 3.23 at 512, 2.04 at 2,048; a step's
# fixed work (eight heads' running maximum, denominator and accumulator)
# is 11 us whatever its keys.  24 MiB of a v5e's 128 at those shapes.
_CHUNK_KEYS = 1024
_CHUNK_MAX_PAGES = 8
_CHUNK_VMEM_LIMIT_BYTES = 32 * 2 ** 20


def _chunk_vmem_bytes(heads, chunk, keys, kv_heads, head_dim, q_dtype,
                      pool_dtype):
    """What one grid step of the chunk kernel is reckoned to hold in VMEM
    with ``heads`` query heads resident against ``keys`` keys a step: q
    and o double-buffered, the float32 accumulator, the running maximum
    and denominator (a column each, which a lane tile pads to 128), the
    int8 mask double-buffered and once more as float32, the pages of K
    and V with all their KV heads double-buffered, the step's own head
    of them side by side, three float32 score tiles."""
    q_size, p_size = (jnp.dtype(d).itemsize for d in (q_dtype, pool_dtype))
    rows = heads * chunk
    return (4 * rows * head_dim * q_size
            + 4 * rows * head_dim + 2 * 4 * rows * 128
            + 2 * chunk * keys + 4 * chunk * keys
            + (4 * kv_heads * p_size + q_size + p_size) * keys * head_dim
            + 3 * 4 * chunk * keys)


def chunk_blocks(group, chunk, n_pages, page_size, kv_heads, head_dim,
                 q_dtype, pool_dtype) -> Optional[Tuple[int, int]]:
    """``(heads, pages)`` a grid step of the chunk kernel takes: how many
    of the ``group`` query heads of one KV head stay resident, and how
    many pages of the table's ``n_pages`` stream by at once.  The pages
    divide the table and make a key block the lanes tile (a multiple of
    128 keys), the nearest to ``_CHUNK_KEYS``; the heads are the most
    that :func:`_chunk_vmem_bytes` fits under ``_CHUNK_VMEM_LIMIT_BYTES``.
    ``None`` where nothing tiles or fits."""
    pages = [p for p in range(1, min(n_pages, _CHUNK_MAX_PAGES) + 1)
             if n_pages % p == 0 and p * page_size % 128 == 0]
    if not pages:
        return None
    ppb = min(pages, key=lambda p: (abs(p * page_size - _CHUNK_KEYS), p))
    for heads in range(group, 0, -1):
        if group % heads == 0 and _chunk_vmem_bytes(
                heads, chunk, ppb * page_size, kv_heads, head_dim, q_dtype,
                pool_dtype) <= _CHUNK_VMEM_LIMIT_BYTES:
            return heads, ppb
    return None


def paged_chunk_attention_path(pool_dtype, q_heads: int, kv_heads: int,
                               head_dim: int, page_size: int, chunk: int,
                               n_pages: int, *, q_dtype=None,
                               backend: Optional[str] = None
                               ) -> Tuple[str, str]:
    """Which route a prefill chunk's attention takes over a pool of this
    dtype and row geometry, ``chunk`` queries against a table of
    ``n_pages`` pages, and why: ``("pallas", reason)`` or ``("window",
    reason)`` (the window gathered, then :func:`~bigdl_tpu.ops.
    sparse_attention.attend`).  ``backend`` defaults to
    ``jax.default_backend()``, ``q_dtype`` to the pool's."""
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and not _INTERPRET:
        return "window", f"backend {backend!r} is not tpu"
    dtype = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return "window", (f"pool dtype {dtype.name} is not a float: the "
                          "window route dequantizes")
    if head_dim % 128:
        return "window", (f"head_dim {head_dim} is not a multiple of 128 "
                          "(one lane tile)")
    if q_heads % kv_heads:
        return "window", (f"{q_heads} query heads do not group over "
                          f"{kv_heads} KV heads")
    if kv_heads > 1 and dtype.itemsize != 4 and (
            dtype != jnp.bfloat16 or kv_heads % 2):
        return "window", (f"{kv_heads} KV heads of {dtype.name} a row: the "
                          "kernel takes one head's rows out of a page for "
                          "float32, and for bfloat16 heads in pairs")
    rows = 32 // dtype.itemsize
    if page_size % rows:
        return "window", (f"page_size {page_size} is not a multiple of "
                          f"{rows} (one sublane tile of {dtype.name})")
    if chunk % 32:
        return "window", (f"chunk {chunk} is not a multiple of 32 (one "
                          "sublane tile of the int8 mask)")
    blocks = chunk_blocks(q_heads // kv_heads, chunk, n_pages, page_size,
                          kv_heads, head_dim, q_dtype or dtype, dtype)
    if blocks is None:
        return "window", (f"a table of {n_pages} pages of {page_size} rows "
                          f"makes no key block of up to {_CHUNK_MAX_PAGES} "
                          "pages that the lanes tile and VMEM holds")
    return "pallas", (
        ("interpret mode" if backend != "tpu" else "tpu backend")
        + f", float pool, {blocks[0]} query heads x {chunk} queries "
        f"against {blocks[1] * page_size} keys a step")


def _head_rows(page_ref, g):
    """KV head ``g``'s rows ``(page_size, head_dim)`` of a page block
    ``(1, page_size, kv_heads, head_dim)`` as the pool lays it, the heads
    of one token side by side.  Seen as ``(page_size * kv_heads,
    head_dim)`` they are every ``kv_heads``-th row, which a strided load
    takes where rows are 32 bits wide; bfloat16 rows lie in pairs, two
    heads to a 32-bit word (the even head the low half), so the pair's
    words are loaded strided and the half wanted is widened to the
    float32 it is the top of (exact).  An index ``[:, g, :]`` compiles
    too, and takes a row at a time: 2.74 ms a call for 1.99 (PR 31)."""
    _, page_size, kv_heads, head_dim = page_ref.shape
    flat = page_ref.at[0].reshape(page_size * kv_heads, head_dim)
    if flat.dtype.itemsize == 4 or kv_heads == 1:
        return flat[pl.ds(g, page_size, stride=kv_heads), :]
    words = flat.bitcast(jnp.uint32)[
        pl.ds(g // 2, page_size, stride=kv_heads // 2), :]
    bits = jnp.where(g % 2 == 1, words & jnp.uint32(0xffff0000), words << 16)
    return pltpu.bitcast(bits, jnp.float32).astype(jnp.bfloat16)


def _chunk_kernel(table_ref, q_ref, mask_ref, *refs, pages, sm_scale):
    k_pages, v_pages = refs[:pages], refs[pages:2 * pages]
    o_ref, k_buf, v_buf, keep, m_ref, l_ref, acc_ref = refs[2 * pages:]
    heads = q_ref.shape[0]
    page_size = k_pages[0].shape[1]
    g, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the mask of this block of keys, once for all the heads; a -1 entry
    # of the table read page 0, so every column of it is hidden here
    col = lax.broadcasted_iota(jnp.int32, (1, keep.shape[1]), 1)
    held = jnp.zeros(col.shape, jnp.float32)
    for i in range(pages):
        end = jnp.where(table_ref[j * pages + i] >= 0, (i + 1) * page_size, 0)
        held = jnp.where((col >= i * page_size) & (col < end), 1.0, held)
    keep[...] = mask_ref[...].astype(jnp.float32) * held
    # masked probabilities are exactly 0, but 0 * NaN = NaN: zero the V
    # rows that no query of the chunk sees (rows past the chunk, a page
    # the table does not hold, a recycled page's stale rows).  Which keys
    # any query sees lies along the lanes; turned, down the rows of V
    seen = keep[...].max(axis=0, keepdims=True)            # (1, keys)
    seen = jnp.broadcast_to(seen, (128, seen.shape[1])).T[:, :1]
    # a page arrives whole, every KV head's rows side by side as the pool
    # lays them; this step's head is taken out of it here, on the chip
    for i in range(pages):
        rows = slice(i * page_size, (i + 1) * page_size)
        k_buf[rows, :] = _head_rows(k_pages[i], g).astype(k_buf.dtype)
        v_buf[rows, :] = jnp.where(seen[rows] > 0,
                                   _head_rows(v_pages[i], g), 0)

    def head(h, _):
        s = lax.dot_general(q_ref[h], k_buf[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        # a select, not a bias: the K rows of a masked key may be non-finite
        s = jnp.where(keep[...] > 0, s, DEFAULT_MASK_VALUE)
        m = m_ref[h]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p.astype(v_buf.dtype), v_buf[...],
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new
        return _

    lax.fori_loop(0, heads, head, None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_chunk_attention(q, k_pool, v_pool, table, mask, *,
                          blocks: Optional[Tuple[int, int]] = None):
    """A prefill chunk's attention over its slot's pages, in place.

    q (1, heads, chunk, head_dim); k_pool / v_pool (n_pages, page_size,
    kv_heads, head_dim) as ``PagedKVCache`` lays them out, ``heads`` a
    multiple of ``kv_heads``; table (pages,) int32, the slot's pages in
    order, ``-1`` where none is held; mask (chunk, pages * page_size)
    int8, non-zero where the query attends the key at that position of
    the table (causal bound, length and any selection already folded in;
    every row with a key of its own; the columns of a ``-1`` page are
    hidden here).  Flash-style: K and V enter the MXU as stored, q as it
    comes (the wider of the two dtypes), the probabilities in V's dtype;
    scores, running maximum, denominator and accumulator are float32 and
    stay in VMEM.  The grid walks every page of the table: the cost is
    the table's, whatever the mask leaves.  ``blocks`` = (query heads,
    pages) a grid step, :func:`chunk_blocks`'s where not given.  Returns
    (1, heads, chunk, head_dim) in q's dtype."""
    return _paged_chunk_attention(q, k_pool, v_pool, table, mask,
                                  blocks=blocks, interpret=_INTERPRET)


# jitted for the reason _paged_attention is: one trace and one Mosaic
# lowering for the six calls of a chunk program
@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _paged_chunk_attention(q, k_pool, v_pool, table, mask, *, blocks,
                           interpret):
    _, n_heads, chunk, head_dim = q.shape
    _, page_size, kv_heads, _ = k_pool.shape
    n_pages = table.shape[0]
    group = n_heads // kv_heads
    # q meets K in the wider of their dtypes (an upcast is exact)
    qk_dtype = jnp.promote_types(q.dtype, k_pool.dtype)
    heads, pages = blocks or chunk_blocks(
        group, chunk, n_pages, page_size, kv_heads, head_dim, qk_dtype,
        k_pool.dtype)
    keys = pages * page_size

    def page_spec(i):
        # a page as it lies in the pool, all its KV heads: the pool's
        # tiles hold the heads of one row, so one head's rows alone are
        # no block of it (and the pool seen as (pages, rows, heads x 128)
        # is a copy of the whole pool); a -1 entry reads page 0
        return pl.BlockSpec(
            (1, page_size, kv_heads, head_dim),
            lambda g, b, j, tab: (jnp.maximum(tab[j * pages + i], 0),
                                  0, 0, 0))

    q_spec = pl.BlockSpec((None, heads, chunk, head_dim),
                          lambda g, b, j, tab: (g, b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, pages=pages,
                          sm_scale=head_dim ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv_heads, group // heads, n_pages // pages),
            in_specs=[q_spec,
                      pl.BlockSpec((chunk, keys),
                                   lambda g, b, j, tab: (0, j))]
            + [page_spec(i) for i in range(pages)] * 2,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((keys, head_dim), qk_dtype),
                pltpu.VMEM((keys, head_dim), v_pool.dtype),
                pltpu.VMEM((chunk, keys), jnp.float32),
                pltpu.VMEM((heads, chunk, 1), jnp.float32),
                pltpu.VMEM((heads, chunk, 1), jnp.float32),
                pltpu.VMEM((heads, chunk, head_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(
            (kv_heads, group, chunk, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(table.astype(jnp.int32),
      q.astype(qk_dtype).reshape(kv_heads, group, chunk, head_dim),
      mask, *[k_pool] * pages, *[v_pool] * pages)
    return out.reshape(q.shape)


__all__ = ["paged_attention", "paged_attention_path", "attend_window",
           "decode_blocks", "gathered_attention",
           "sparse_paged_attention", "paged_chunk_attention",
           "paged_chunk_attention_path", "chunk_blocks"]
