"""Paged KV cache for continuous-batching decode.

The contiguous ``TransformerLM.init_cache`` layout allocates
``max_len`` key/value rows per sequence up front — fine for a fixed
batch of equal-length generations, hopeless for a serving mix where a
12-token answer and a 900-token answer share the batch: the short
request strands ``max_len - 12`` rows of HBM for its whole lifetime.

Here the cache is a device-resident **pool of fixed-size pages**
(``page_size`` token rows each, one pool per attention layer) plus a
host-side allocator.  Each slot (one running request) owns an ordered
page table; a request's KV footprint is ``ceil(len / page_size)``
pages and grows one page at a time as it decodes.  The jitted decode
step never sees the allocator — it takes the page tables as a plain
``(slots, max_pages)`` int32 input and:

  * **writes** the new token's k/v rows at
    ``(table[len // page_size], len % page_size)`` — a fixed-shape
    scatter; dead slots carry table entries of ``-1``, whose writes
    XLA **drops** (out-of-bounds scatter, ``mode="drop"``),
  * **attends** the new token's q to the slot's pages
    (:meth:`PagedKVCache.attend`), by one of two routes that
    :func:`bigdl_tpu.ops.paged_attention_path` picks from what the code
    can observe.  On a TPU, for a float pool whose rows tile, a Pallas
    kernel reads the live pages where they lie, up to each slot's
    length, in the dtype they are stored in.  A pool that also holds
    **index keys** (``index_dim``: one narrow row a token beside K and
    V, in the same pages under the same tables) takes the **sparse**
    route: score the slot's live index keys, take the ``index_top_k``
    best, gather only those K and V rows.  Elsewhere (CPU, an int8
    pool, query heads grouped over fewer KV heads) the pages are
    **gathered** back into a contiguous window
    ``(slots, heads, max_pages * page_size, head_dim)`` — a fixed-shape
    gather; ``-1`` entries **fill** with zeros (``mode="fill"``),
    exactly the zero rows an unwritten contiguous cache would hold, so
    paged logits match the ``init_cache`` path to rounding
    (tests/test_decode.py pins this) — and attended in float32.

Page tables are data, not shapes: admissions, retirements and
evictions change *values* only, so one compiled decode program serves
every batch composition — the zero-recompile discipline of the PR-2
bucket ladder extended to the token-streaming path.

``int8=True`` stores the pool as int8 with a per-(page, position,
head) fp32 scale over the head_dim channel — the
:func:`bigdl_tpu.quantized.quantize_rows` per-channel quantizer run
inside the decode step — halving (vs bf16; 4x vs fp32) the KV bytes
each decode step streams from HBM.  Drift is bounded and measured,
never hidden (see docs/serving.md § Token streaming).

Telemetry (``kv/*`` family, registered in docs/observability.md):
``kv/page_allocs`` / ``kv/page_frees`` / ``kv/evictions`` counters,
``kv/pages_in_use`` / ``kv/pool_fill`` / ``kv/peak_fill`` gauges; the
decode engine counts ``kv/pages_read`` against ``kv/pages_window`` (what
a step's attention has to read of what the gathered window holds).
"""
from __future__ import annotations

import math
import threading
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import Recorder
from ..ops.paged_attention import (attend_window, paged_attention,
                                   paged_attention_path,
                                   paged_chunk_attention,
                                   paged_chunk_attention_path,
                                   sparse_paged_attention)
from ..ops.sparse_attention import attend as attend_rows
from ..ops.sparse_attention import attention_mask
from ..quantized import dequantize_rows, quantize_rows


class PagePoolError(RuntimeError):
    """Allocator invariant violation (double free, foreign page)."""


class PagedKVCache:
    """Device page pool + host allocator + the jitted write/attend fns.

    ``layer_names``   attention-module names (one k/v pool each)
    ``n_heads`` / ``head_dim``  per-layer KV row geometry (the KV heads;
                      ``q_heads`` query heads share them, default as many)
    ``index_dim`` / ``index_top_k``  > 0: every token also caches one
                      index key of that width, and attention reads only
                      the ``index_top_k`` rows it scores highest
    ``n_pages``       pool size, in pages, shared by all slots
    ``page_size``     token rows per page
    ``n_slots``       concurrent sequences (page-table rows)
    ``max_context``   longest sequence a slot may hold; rounded up to a
                      page multiple; fixes the page-table width
                      ``max_pages_per_slot`` (and the gathered window)
    ``dtype``         pool dtype for the fp path (int8 path stores
                      int8 + fp32 scales)
    ``int8``          quantize KV rows on write, dequantize on gather
                      (an int8 pool always attends by the gather route)

    The allocator side (``alloc_for`` / ``free_slot``) is guarded by
    one lock and keeps the invariant ``free + sum(owned) == n_pages``
    with every page owned by at most one slot — tests/test_decode.py
    asserts it across alloc/free/evict churn.
    """

    def __init__(self, layer_names: Sequence[str], *, n_heads: int,
                 head_dim: int, n_pages: int, page_size: int = 16,
                 n_slots: int = 8, max_context: int = 256,
                 dtype=jnp.float32, int8: bool = False,
                 q_heads: Optional[int] = None, index_dim: int = 0,
                 index_top_k: int = 0,
                 recorder: Optional[Recorder] = None):
        if page_size < 1 or n_pages < 1 or n_slots < 1:
            raise ValueError("page_size, n_pages and n_slots must be >= 1")
        if index_dim and (int8 or index_top_k < 1):
            raise ValueError("an index-key pool is a float pool with "
                             "index_top_k >= 1")
        self.layer_names = list(layer_names)
        self.n_heads = int(n_heads)
        self.q_heads = int(q_heads or n_heads)
        self.head_dim = int(head_dim)
        self.index_dim = int(index_dim)
        self.index_top_k = int(index_top_k)
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.n_slots = int(n_slots)
        self.max_pages_per_slot = math.ceil(max_context / page_size)
        self.max_context = self.max_pages_per_slot * self.page_size
        self.window = self.max_pages_per_slot * self.page_size
        self.dtype = jnp.dtype(dtype)
        self.int8 = bool(int8)
        self.recorder = recorder if recorder is not None else Recorder(
            annotate=False, enabled=False)
        self._lock = threading.Lock()
        # deterministic allocation order: lowest free page first
        self._free: List[int] = list(range(self.n_pages))
        self._owned: Dict[int, List[int]] = {s: [] for s in
                                             range(self.n_slots)}
        self.tables = np.full((self.n_slots, self.max_pages_per_slot),
                              -1, np.int32)

    # -- device pool ------------------------------------------------------ #
    def init_pool(self):
        """Zeroed device pool pytree: ``{layer: {"k", "v"[, "k_scale",
        "v_scale"][, "ki"]}}`` with pages laid out ``(n_pages, page_size,
        n_heads, head_dim)`` (scales ``(n_pages, page_size, n_heads,
        1)``, index keys ``(n_pages, page_size, index_dim)``).  Zero
        pages read back as the zero rows of a fresh contiguous cache."""
        shape = (self.n_pages, self.page_size, self.n_heads, self.head_dim)
        sshape = shape[:-1] + (1,)

        def one():
            if self.int8:
                return {"k": jnp.zeros(shape, jnp.int8),
                        "v": jnp.zeros(shape, jnp.int8),
                        "k_scale": jnp.zeros(sshape, jnp.float32),
                        "v_scale": jnp.zeros(sshape, jnp.float32)}
            out = {"k": jnp.zeros(shape, self.dtype),
                   "v": jnp.zeros(shape, self.dtype)}
            if self.index_dim:
                out["ki"] = jnp.zeros(shape[:2] + (self.index_dim,),
                                      self.dtype)
            return out

        return {name: one() for name in self.layer_names}

    def index_bytes(self) -> int:
        """Bytes the index keys take of the pool (``kv/index_bytes``)."""
        return len(self.layer_names) * self.n_pages * self.page_size \
            * self.index_dim * self.dtype.itemsize

    # -- host allocator --------------------------------------------------- #
    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(max(int(n_tokens), 0) / self.page_size)

    def can_fit(self, n_tokens: int) -> bool:
        with self._lock:
            return self.pages_for(n_tokens) <= len(self._free)

    def alloc_for(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``n_tokens`` token rows.
        All-or-nothing: returns False (allocating nothing) when the
        free list cannot cover the growth — the caller then evicts or
        backpressures."""
        need_pages = self.pages_for(n_tokens)
        if need_pages > self.max_pages_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens need {need_pages} pages "
                f"> max_pages_per_slot {self.max_pages_per_slot} "
                f"(max_context {self.max_context})")
        with self._lock:
            owned = self._owned[slot]
            grow = need_pages - len(owned)
            if grow <= 0:
                return True
            if grow > len(self._free):
                return False
            for _ in range(grow):
                page = self._free.pop(0)
                self.tables[slot, len(owned)] = page
                owned.append(page)
            self.recorder.inc("kv/page_allocs", grow)
            self._publish_gauges_locked()
            return True

    def free_slot(self, slot: int, evict: bool = False) -> int:
        """Return every page ``slot`` owns to the free list (retirement
        or eviction); the table row resets to ``-1`` so in-flight
        gathers read zeros and writes drop.  Returns the page count."""
        with self._lock:
            owned = self._owned[slot]
            for page in owned:
                if page in self._free:
                    raise PagePoolError(
                        f"double free: page {page} of slot {slot} is "
                        "already on the free list")
                self._free.append(page)
            n = len(owned)
            self._free.sort()
            self._owned[slot] = []
            self.tables[slot, :] = -1
            if n:
                self.recorder.inc("kv/page_frees", n)
            if evict:
                self.recorder.inc("kv/evictions")
            self._publish_gauges_locked()
            return n

    def pages_in_use(self) -> int:
        with self._lock:
            return self.n_pages - len(self._free)

    def fill(self) -> float:
        """Pool fill fraction in [0, 1] — the ``kv/pool_fill`` gauge."""
        with self._lock:
            return (self.n_pages - len(self._free)) / self.n_pages

    def check_invariants(self):
        """Every page owned at most once and free+owned == n_pages
        (test seam; raises :class:`PagePoolError` on violation)."""
        with self._lock:
            seen = list(self._free)
            for slot, owned in self._owned.items():
                seen += owned
                for i, page in enumerate(owned):
                    if self.tables[slot, i] != page:
                        raise PagePoolError(
                            f"table/ledger disagree at slot {slot}[{i}]")
            if sorted(seen) != list(range(self.n_pages)):
                raise PagePoolError(
                    f"page ledger broken: {sorted(seen)} != "
                    f"0..{self.n_pages - 1}")

    def _publish_gauges_locked(self):
        used = self.n_pages - len(self._free)
        rec = self.recorder
        rec.gauge("kv/pages_in_use", used)
        fill = used / self.n_pages
        rec.gauge("kv/pool_fill", fill)
        if fill > rec.gauge_value("kv/peak_fill", 0.0):
            rec.gauge("kv/peak_fill", fill)

    # -- jitted write/attend (fixed shapes, traced) ------------------------ #
    def _oob(self, idx):
        """Map the host tables' ``-1`` free markers to ``n_pages`` —
        genuinely out of bounds.  jax scatter/gather WRAP negative
        indices (numpy semantics) *before* the drop/fill bounds check,
        so a raw ``-1`` would silently alias the pool's LAST page: a
        dead slot's write clobbered whichever request owned it.  A
        positive out-of-range index is what ``mode="drop"`` /
        ``mode="fill"`` actually drop/fill."""
        return jnp.where(idx < 0, self.n_pages, idx)

    def gather_window(self, layer_pool, tables):
        """(k_win, v_win) each ``(slots, heads, window, head_dim)``
        gathered from ``layer_pool`` through ``tables`` (slots,
        max_pages); ``-1`` entries fill with zeros.  Pages concatenate
        in table order, so a slot's window is exactly the contiguous
        cache a ``init_cache``-path request would hold."""
        tables = self._oob(tables)

        def one(q, scale):
            pages = jnp.take(q, tables, axis=0, mode="fill",
                             fill_value=0)   # (S, P, page, H, Dh)
            if scale is not None:
                sc = jnp.take(scale, tables, axis=0, mode="fill",
                              fill_value=0)
                pages = dequantize_rows(pages, sc)
            s, p, pg, h, d = pages.shape
            return pages.transpose(0, 3, 1, 2, 4).reshape(s, h, p * pg, d)

        return (one(layer_pool["k"], layer_pool.get("k_scale")),
                one(layer_pool["v"], layer_pool.get("v_scale")))

    def gather_index(self, layer_pool, tables):
        """The index keys of ``tables`` (slots, pages) as ``(slots, pages *
        page_size, index_dim)``; ``-1`` entries fill with zeros."""
        pages = jnp.take(layer_pool["ki"], self._oob(tables), axis=0,
                         mode="fill", fill_value=0)
        return pages.reshape(tables.shape[0], -1, self.index_dim)

    def attention_path(self, backend: Optional[str] = None
                       ) -> Tuple[str, str]:
        """``(route, why)`` of :meth:`attend` for this pool: ``"sparse"``
        (index keys scored, the best rows gathered), ``"pallas"`` (pages
        read in place) or ``"gather"`` (window, then float32 math) —
        for the last two :func:`~bigdl_tpu.ops.paged_attention_path` over
        the pool's dtype and row geometry."""
        if self.index_dim:
            return "sparse", (f"the pool holds index keys: top "
                              f"{self.index_top_k} rows a slot")
        if self.q_heads != self.n_heads:
            return "gather", (f"{self.q_heads} query heads over "
                              f"{self.n_heads} KV heads: the kernel reads "
                              "one KV head a query head")
        return paged_attention_path(
            jnp.int8 if self.int8 else self.dtype, self.n_heads,
            self.head_dim, backend=backend)

    def chunk_attention_path(self, chunk: int,
                             n_pages: Optional[int] = None,
                             backend: Optional[str] = None
                             ) -> Tuple[str, str]:
        """``(route, why)`` of :meth:`attend_chunk` for ``chunk`` queries
        against a table of ``n_pages`` pages (a slot's whole table by
        default): ``"pallas"`` (the pages read in place, the scores kept
        on the chip) or ``"window"`` (the window gathered, then float32
        math in XLA), by :func:`~bigdl_tpu.ops.paged_attention.
        paged_chunk_attention_path` over the pool's dtype and geometry.
        An index-key pool takes the same routes: its selection reaches
        either as a mask."""
        return paged_chunk_attention_path(
            jnp.int8 if self.int8 else self.dtype, self.q_heads,
            self.n_heads, self.head_dim, self.page_size, chunk,
            self.max_pages_per_slot if n_pages is None else n_pages,
            backend=backend)

    def attend(self, layer_pool, tables, lengths, q, index=None):
        """Single-token attention of q ``(slots, heads, 1, head_dim)``
        over each slot's pages, the row :meth:`write_token` just wrote
        at ``lengths[s]`` included (write, then attend).  Keys past it
        are masked and their V rows zeroed, so a recycled page's stale
        or non-finite rows cannot leak; a dead slot reads zeros (its
        token is never emitted).  ``index`` = (qi ``(slots, index heads,
        index_dim)``, w ``(slots, index heads)``) of an index-key pool.
        Returns ``(slots, heads, 1, head_dim)`` in q's dtype."""
        route, why = self.attention_path()
        if route == "sparse":
            qi, w = index
            return sparse_paged_attention(
                q[:, :, 0], qi, w, layer_pool["k"], layer_pool["v"],
                self.gather_index(layer_pool, tables), tables, lengths,
                self.index_top_k)[:, :, None]
        if route == "pallas":
            return paged_attention(q[:, :, 0], layer_pool["k"],
                                   layer_pool["v"], tables,
                                   lengths)[:, :, None]
        if jax.default_backend() == "tpu" and not self.int8 \
                and self.q_heads == self.n_heads:
            # on the chip the window is never the intended route for a
            # float pool: say so (once per call site)
            warnings.warn("PagedKVCache.attend gathers every slot's "
                          f"whole window, not the Pallas kernel: {why}",
                          stacklevel=2)
        k_win, v_win = self.gather_window(layer_pool, tables)
        return attend_window(q, k_win, v_win, lengths)

    def write_token(self, layer_pool, tables, lengths, k_new, v_new,
                    ki_new=None):
        """Scatter one new k/v row per slot into the pool at
        ``(table[len // page], len % page)``.  k_new/v_new are
        ``(slots, heads, 1, head_dim)`` (the
        :meth:`~bigdl_tpu.models.transformer.MultiHeadAttention.project_qkv`
        output), ki_new ``(slots, index_dim)`` the index key of an
        index-key pool; dead slots' ``-1`` page indices drop."""
        pidx = self._oob(jnp.take_along_axis(
            tables, (lengths // self.page_size)[:, None], axis=1)[:, 0])
        off = lengths % self.page_size
        out = dict(layer_pool)
        for key, new in (("k", k_new), ("v", v_new)):
            row = new[:, :, 0, :]                     # (S, H, Dh)
            if self.int8:
                q, sc = quantize_rows(row, axis=-1)
                out[key] = layer_pool[key].at[pidx, off].set(
                    q, mode="drop")
                out[key + "_scale"] = layer_pool[key + "_scale"].at[
                    pidx, off].set(sc, mode="drop")
            else:
                out[key] = layer_pool[key].at[pidx, off].set(
                    row.astype(layer_pool[key].dtype), mode="drop")
        if ki_new is not None:
            out["ki"] = layer_pool["ki"].at[pidx, off].set(
                ki_new.astype(layer_pool["ki"].dtype), mode="drop")
        return out

    def write_prefill(self, layer_pool, table, k, v, ki=None):
        """Scatter a contiguous prefill's k/v ``(1, heads, Lb, head_dim)``
        (and index keys ki ``(1, Lb, index_dim)``) into the pages of
        ``table`` (``ceil(Lb / page_size)`` entries, ``-1``-padded past
        the slot's allocation — those pages hold only prompt-padding
        rows, which the per-slot attention mask never exposes, so
        dropping them is exact)."""
        pg = self.page_size
        table = self._oob(table)
        out = dict(layer_pool)
        for key, arr in (("k", k), ("v", v), ("ki", ki)):
            if arr is None:
                continue
            # (Lb, H, Dh); an index key is one row of no heads
            rows = arr[0] if key == "ki" \
                else jnp.transpose(arr[0], (1, 0, 2))
            lb = rows.shape[0]
            n_pages = math.ceil(lb / pg)
            if lb % pg:
                rows = jnp.concatenate(
                    [rows, jnp.zeros((n_pages * pg - lb,) + rows.shape[1:],
                                     rows.dtype)], axis=0)
            pages = rows.reshape((n_pages, pg) + rows.shape[1:])
            if self.int8:
                q, sc = quantize_rows(pages, axis=-1)
                out[key] = layer_pool[key].at[table].set(q, mode="drop")
                out[key + "_scale"] = layer_pool[key + "_scale"].at[
                    table].set(sc, mode="drop")
            else:
                out[key] = layer_pool[key].at[table].set(
                    pages.astype(layer_pool[key].dtype), mode="drop")
        return out

    def write_chunk(self, layer_pool, table, k, v, ki=None):
        """A prefill chunk's rows into the pages of ``table`` (the chunk's
        own, ``C / page_size`` entries): k/v ``(1, heads, C, head_dim)``,
        ki ``(1, C, index_dim)``.  Row by row, as :meth:`write_token`
        scatters: a scatter of whole pages has the compiler re-lay every
        layer's whole pool for it and back, which a pool of gigabytes
        cannot pay a chunk (a float pool only)."""
        n = k.shape[2]
        at = jnp.arange(n)
        pidx = jnp.take(self._oob(table), at // self.page_size)
        off = at % self.page_size
        out = dict(layer_pool)
        for key, rows in (("k", jnp.swapaxes(k[0], 0, 1)),
                          ("v", jnp.swapaxes(v[0], 0, 1)),
                          ("ki", None if ki is None else ki[0])):
            if rows is not None:
                out[key] = layer_pool[key].at[pidx, off].set(
                    rows.astype(layer_pool[key].dtype), mode="drop")
        return out

    def attend_chunk(self, layer_pool, table, start, q, index=None):
        """A prefill chunk's attention against the slot's own pages, the
        chunk's rows (written before, :meth:`write_chunk`) included: q
        ``(1, heads, C, head_dim)`` at positions ``start + arange(C)``,
        each over the keys up to its own position, with the
        top-``index_top_k`` selection of an index-key pool applied per
        query (``index`` = (qi ``(1, C, index heads, index_dim)``, w
        ``(1, C, index heads)``)).  ``table`` holds the pages of the
        longest prompt, and the whole of that window is scored whatever
        ``start`` is: a chunk costs the same wherever in its prompt it
        falls, so the gap it puts between two tokens of the live slots is
        one length.  (A ladder of shorter windows for early chunks served
        a fifth more requests a second, and put the gaps' 95th percentile
        on one rung or another, 53 or 99 ms, by the order the prompts came
        in; my chip runs, PR 28.  A cost that follows the offset spreads
        that percentile by 32% over twelve seeds of the long-document
        cell's traffic: ISSUE 31's simulation.)  Two routes,
        :meth:`chunk_attention_path`: on a TPU, for a float pool that
        tiles, one Pallas call walks every page of ``table`` in place
        under ONE int8 mask (causal bound, length and selection folded
        in) and skips none by the causal bound; elsewhere the window is
        gathered and :func:`~bigdl_tpu.ops.sparse_attention.attend` walks
        it.  Returns ``(1, heads, C, head_dim)``."""
        chunk = q.shape[2]
        tab = table[None]
        q_pos = (start + jnp.arange(chunk))[None]
        kv_len = jnp.asarray(start + chunk)[None]
        if index is not None:
            index = (index[0], self.gather_index(layer_pool, tab), index[1])
        n_pages = table.shape[0]
        route, why = self.chunk_attention_path(chunk, n_pages)
        if route == "pallas":
            mask = attention_mask(q_pos, kv_len, n_pages * self.page_size,
                                  index, self.index_top_k)[0]
            return paged_chunk_attention(q, layer_pool["k"],
                                         layer_pool["v"], table, mask)
        if jax.default_backend() == "tpu" and not self.int8:
            # as in attend: on the chip the window is never the intended
            # route for a float pool
            warnings.warn("PagedKVCache.attend_chunk gathers the whole "
                          f"window, not the Pallas kernel: {why}",
                          stacklevel=2)
        k_win, v_win = self.gather_window(layer_pool, tab)
        return attend_rows(q, k_win, v_win, q_pos, kv_len, index,
                           self.index_top_k)


__all__ = ["PagedKVCache", "PagePoolError"]
