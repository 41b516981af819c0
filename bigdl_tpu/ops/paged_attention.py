"""Paged attention: Pallas TPU kernels + gathered-window route.

Decode: one new token per slot attends to that slot's K and V where they
lie in the page pool (``serving/kvcache.py``: ``(n_pages, page_size,
n_heads, head_dim)``, one pool a layer), up to the slot's length, in the
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
  * window route — :func:`attend_window` over a window gathered by
    ``PagedKVCache.gather_window``: what runs on CPU, for an int8 pool,
    and what the kernel is tested against.

:func:`paged_attention_path` is the one place that decides which route a
pool takes and says why (as ``ops.attention_path`` does for the flash
kernel).

The kernel keeps the pool's token-major layout, so K of a block is
``(tokens * heads, head_dim)`` after a free reshape, and the scores of
all heads come from ONE matmul ``q (heads, head_dim) @ K^T -> (heads,
tokens * heads)`` of which only the entries whose column's head is the
row's own are kept: ``heads`` times the useful FLOPs on an MXU that idles
anyway, and no relayout of K or V.  The masked probabilities are exactly
zero, so ``p @ V`` over the same flat axis is the per-head value sum.
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

# Pages fetched per wait: 8 pages of 16 tokens are 128 tokens a block,
# 512 KiB each of K and V in bf16 at 16 heads x 128, double-buffered.
_PAGES_PER_BLOCK = 8


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


# The decode attention of grouped heads over a float pool with no index
# keys (what `PagedKVCache.attention_path` calls "gather"), jitted under a
# name of its own for the reason the two above are: the step's device
# trace shows the gather and the attention of every layer apart from the
# rest.  A Pallas kernel for grouped heads with a window bound would take
# this function's place (ROADMAP R1).
@functools.partial(jax.jit, static_argnames=("window",))
def _window_attend(q, k_pool, v_pool, tables, lengths, *, window):
    """q (S, H, Dh) of each slot's new token, at position ``lengths[s]``,
    over the pages of ``tables`` (S, P; ``-1``: none), gathered: a table
    as wide as the longest sequence, or (``window`` > 0: a sliding-window
    layer) a RING of P pages in which logical page ``j`` lies in column
    ``j % P``.  The mask goes by each row's position
    (:func:`~bigdl_tpu.ops.sparse_attention.ring_positions`), not by its
    column: ``lengths[s] - window < position <= lengths[s]``, so rows not
    yet written, and a recycled page's stale rows (a ring that holds
    ``window`` rows and a page more never shows one inside the window),
    are hidden, their K masked by a select and their V zeroed.  K and V
    enter the matmuls as stored, the probabilities in V's dtype; scores,
    softmax and both sums are float32.  -> (S, H, Dh) in q's dtype."""
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


def paged_attention_path(pool_dtype, n_heads: int, head_dim: int, *,
                         backend: Optional[str] = None) -> Tuple[str, str]:
    """Which route decode attention takes over a pool of this dtype and
    row geometry, and why: ``("pallas", reason)`` or ``("gather",
    reason)``.  ``backend`` defaults to ``jax.default_backend()``."""
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
    if n_heads % 8:
        return "gather", (f"n_heads {n_heads} is not a multiple of 8 "
                          "(one sublane tile)")
    return "pallas", ("interpret mode" if backend != "tpu"
                      else "tpu backend, float pool, rows tile")


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            item_slot, item_blk, k_buf, v_buf, sem, *,
            page_size, max_pages, sm_scale):
    n_slots, n_heads, head_dim = q_ref.shape
    ppb = k_buf.shape[1]
    block = ppb * page_size               # tokens a block
    flat = block * n_heads                # the matmuls' flat key axis
    max_items = item_slot.shape[0]

    def live_pages(s):
        # a dead slot (no first page) has no work; a live one attends
        # the token just written too: ceil((length + 1) / page_size)
        return jnp.where(tables_ref[s * max_pages] >= 0,
                         lengths_ref[s] // page_size + 1, 0)

    def list_slot(s, n):
        def put(b, n):
            item_slot[n] = s
            item_blk[n] = b
            return n + 1
        return lax.fori_loop(0, pl.cdiv(live_pages(s), ppb), put, n)

    n_items = lax.fori_loop(0, n_slots, list_slot, 0)

    def copies(i, buf, act):
        s, b = item_slot[i], item_blk[i]
        pages = live_pages(s)
        for j in range(ppb):
            pj = b * ppb + j
            page = tables_ref[s * max_pages + jnp.minimum(pj, max_pages - 1)]

            # a -1 entry is never dereferenced
            @pl.when((pj < pages) & (page >= 0))
            def _():
                for hbm, vmem, which in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    act(pltpu.make_async_copy(
                        hbm.at[page], vmem.at[buf, j], sem.at[which, buf]))

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
        # keys of this block that the slot attends: its first `keys`
        keys = lengths_ref[s] + 1 - b * block
        first = b == 0
        m = jnp.where(first, DEFAULT_MASK_VALUE, m)
        l = jnp.where(first, 0.0, l)
        acc = jnp.where(first, 0.0, acc)

        q = q_ref[s]                                      # (H, Dh)
        k = k_buf[buf].reshape(flat, head_dim)
        s_ = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * sm_scale
        col = lax.broadcasted_iota(jnp.int32, (n_heads, flat), 1)
        row = lax.broadcasted_iota(jnp.int32, (n_heads, flat), 0)
        mask = (lax.rem(col, n_heads) == row) & (col < keys * n_heads)
        s_ = jnp.where(mask, s_, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)

        # masked probabilities are exactly 0, but 0 * NaN = NaN: scrub
        # the V rows past the slot's length (a recycled page's stale
        # rows, or what an earlier item left in the buffer); only the
        # slot's last block has any
        @pl.when(keys < block)
        def _():
            shape = v_buf.shape[1:]
            tok = (lax.broadcasted_iota(jnp.int32, shape, 0) * page_size
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
            v_buf[buf] = jnp.where(tok < keys, v_buf[buf], 0)

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


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    pages_per_block: int = _PAGES_PER_BLOCK):
    """Decode attention over a page pool, in place.

    q (slots, heads, head_dim); k_pool / v_pool (n_pages, page_size,
    heads, head_dim) as ``PagedKVCache`` lays them out; tables (slots,
    max_pages) int32, ``-1`` where no page is held; lengths (slots,)
    int32, each slot's length BEFORE the token just written, which is
    attended too.  Returns (slots, heads, head_dim) in q's dtype; a
    dead slot (``tables[s, 0] < 0``) reads zeros."""
    return _paged_attention(q, k_pool, v_pool, tables, lengths,
                            pages_per_block=int(pages_per_block),
                            interpret=_INTERPRET)


# jitted, so that a step which calls it once a layer traces the kernel
# and lowers it to Mosaic once: sixteen lowerings of the same kernel were
# 3 s of every DecodeEngine.warmup(), on a warm compile cache too
@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def _paged_attention(q, k_pool, v_pool, tables, lengths, *, pages_per_block,
                     interpret):
    n_slots, n_heads, head_dim = q.shape
    _, page_size, _, _ = k_pool.shape
    max_pages = tables.shape[1]
    ppb = min(pages_per_block, max_pages)
    max_items = n_slots * -(-max_pages // ppb)
    buf = pltpu.VMEM((2, ppb, page_size, n_heads, head_dim), k_pool.dtype)
    kernel = functools.partial(_kernel, page_size=page_size,
                               max_pages=max_pages,
                               sm_scale=head_dim ** -0.5)
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
           "sparse_paged_attention", "paged_chunk_attention",
           "paged_chunk_attention_path", "chunk_blocks"]
