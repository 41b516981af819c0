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
    (:meth:`PagedKVCache.attend`), by one of the routes that
    :func:`bigdl_tpu.ops.paged_attention_path` picks from what the code
    can observe.  On a TPU, for a float pool whose rows tile, a Pallas
    kernel reads the live slots' pages where they lie, up to each slot's
    length, in the dtype they are stored in: query heads grouped over
    fewer KV heads or not, and of a sliding-window layer only the pages
    its window shows.  A pool that also holds
    **index keys** (``index_dim``: one narrow row a token beside K and
    V, in the same pages under the same tables) takes the **sparse**
    route: score the slot's live index keys, take the ``index_top_k``
    best, gather only those K and V rows.  Elsewhere (CPU, an int8
    pool, rows that do not tile) the pages are
    **gathered** back into a contiguous window
    ``(slots, heads, max_pages * page_size, head_dim)`` — a fixed-shape
    gather; ``-1`` entries **fill** with zeros (``mode="fill"``),
    exactly the zero rows an unwritten contiguous cache would hold, so
    paged logits match the ``init_cache`` path to rounding
    (tests/test_decode.py pins this) — and attended in float32.

**Layers of two kinds.**  A model whose layers are not all alike (sliding
windows beside global attention) gives the cache ``windows``, one entry a
layer (0: global).  The layers of one kind share a page table and a free
list of their own (:class:`_TableKind`), and their pools have that kind's
number of pages: a global layer's table is ``max_context / page_size``
wide, a window layer's is a **ring** of ``window / page_size + ring_slack
/ page_size + 1`` pages in which logical page ``j`` of the sequence lies
in column ``j % ring``.  Once a slot holds its whole ring,
:meth:`PagedKVCache.alloc_for` recycles its pages in place as the slot
moves on (``kv/pages_recycled``, span ``kv.recycle``): the table does not
change, the rows of the oldest page are overwritten one by one, and what
is left of them is stale.  Every mask over a ring goes by each row's
POSITION (``ops/sparse_attention.py`` ``ring_positions``), which the
column and the slot's length give, so a stale row reads as the position
it is about to hold, past the newest, and is hidden like an unwritten one
(masked K, zeroed V): in the gathered math and in the decode kernel,
whose work list names only the ring's blocks that hold a page the window
shows.  One ``alloc_for`` grows every kind or none;
``free_slot`` and eviction return every kind's pages, and a readmission's
re-prefill and replay rebuild a ring as they rebuild a table.

**A latent pool.**  A model of latent attention (``latent_rank`` > 0)
caches ONE row a token a layer: ``latent_rank`` columns of normed latent,
which every head's key AND value are up-projected from, then the one
rotated key all heads share.  Its layer's pool is the single array
``{"latent": (n_pages, page_size, head_dim)}``: no V pool, no heads.  The
decode step scores ABSORBED queries against the rows as they lie
(``ops/paged_attention.py`` ``_latent_attend``) and a chunk hands over
its un-absorbed queries and the up-projection
(``_latent_chunk_attend``).  The allocator, the tables and :meth:`pack`
do not notice: a row is a row.

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
from ..ops.paged_attention import (_CHUNK_MAX_PAGES, _latent_attend,
                                   _latent_chunk_attend, _window_attend,
                                   attend_window, latent_chunk_formula,
                                   paged_attention,
                                   paged_attention_path,
                                   paged_chunk_attention,
                                   paged_chunk_attention_path,
                                   sparse_paged_attention)
from ..ops.sparse_attention import attend as attend_rows
from ..ops.sparse_attention import attention_mask, ring_positions
from ..quantized import dequantize_rows, quantize_rows


class PagePoolError(RuntimeError):
    """Allocator invariant violation (double free, foreign page)."""


class _TableKind:
    """The page table, free list and ledger that the layers of one kind
    share: ``window`` 0 for global attention (a table as wide as the
    longest sequence), > 0 for a sliding window (a ring, see the module's
    docstring).  Pages are numbered within the kind: page ``p`` is row
    ``p`` of the pool of each of the kind's layers."""

    def __init__(self, name: str, window: int, layers: List[str],
                 width: int, n_pages: int, n_slots: int):
        self.name, self.window, self.layers = name, int(window), layers
        self.ring = self.window > 0       # a ring of pages, not a table
        self.width, self.n_pages = int(width), int(n_pages)
        # deterministic allocation order: lowest free page first
        self.free: List[int] = list(range(self.n_pages))
        self.owned: Dict[int, List[int]] = {s: [] for s in range(n_slots)}
        # logical pages a slot has covered; a ring's may pass its width
        self.covered = np.zeros(n_slots, np.int64)
        self.tables = np.full((n_slots, self.width), -1, np.int32)

    def describe(self) -> Dict[str, int]:
        return {"layers": len(self.layers), "window": self.window,
                "pages_per_slot": self.width, "n_pages": self.n_pages}


class PagedKVCache:
    """Device page pool + host allocator + the jitted write/attend fns.

    ``layer_names``   attention-module names (one k/v pool each)
    ``n_heads`` / ``head_dim``  per-layer KV row geometry (the KV heads;
                      ``q_heads`` query heads share them, default as many)
    ``index_dim`` / ``index_top_k``  > 0: every token also caches one
                      index key of that width, and attention reads only
                      the ``index_top_k`` rows it scores highest
    ``n_pages``       pool size, in pages, shared by all slots; with
                      layers of more than one kind ``{kind: pages}``
                      (``"global"``, ``"window"``), a kind left out (or
                      ``None``) sized for every slot's whole table
    ``page_size``     token rows per page
    ``n_slots``       concurrent sequences (page-table rows)
    ``max_context``   longest sequence a slot may hold; rounded up to a
                      page multiple; fixes the page-table width
                      ``max_pages_per_slot`` (and the gathered window)
    ``dtype``         pool dtype for the fp path (int8 path stores
                      int8 + fp32 scales)
    ``int8``          quantize KV rows on write, dequantize on gather
                      (an int8 pool always attends by the gather route)
    ``windows``       one entry a layer: 0 for global attention, W > 0
                      for a sliding window of W keys, whose layers hold
                      a ring of ``ceil(W / page_size) + ring_slack /
                      page_size + 1`` pages a slot (never more than a
                      global table); ``None``: every layer global
    ``ring_slack``    rows a ring holds beyond its window: the longest
                      run of rows written before any of them is attended
                      (a prefill chunk)
    ``latent_rank`` / ``sm_scale``  > 0: a latent pool (the module's
                      docstring): ``n_heads`` 1, ``head_dim`` the row's
                      width, its first ``latent_rank`` columns the value,
                      scores scaled by the model's own ``sm_scale``

    The allocator side (``alloc_for`` / ``free_slot``) is guarded by
    one lock and keeps the invariant ``free + sum(owned) == n_pages`` for
    every kind, with every page owned by at most one slot —
    tests/test_decode.py and tests/test_window_moe.py assert it across
    alloc/recycle/free/evict churn.  ``tables``, ``n_pages`` and
    ``max_pages_per_slot`` are the first kind's (the global one where
    there is one): all there is for a model of one kind of layer.
    """

    def __init__(self, layer_names: Sequence[str], *, n_heads: int,
                 head_dim: int, n_pages=None, page_size: int = 16,
                 n_slots: int = 8, max_context: int = 256,
                 dtype=jnp.float32, int8: bool = False,
                 q_heads: Optional[int] = None, index_dim: int = 0,
                 index_top_k: int = 0,
                 windows: Optional[Sequence[int]] = None,
                 ring_slack: int = 0, latent_rank: int = 0,
                 sm_scale: Optional[float] = None,
                 recorder: Optional[Recorder] = None):
        if page_size < 1 or n_slots < 1:
            raise ValueError("page_size, n_pages and n_slots must be >= 1")
        if latent_rank and (int8 or index_dim or n_heads != 1
                            or windows is not None and any(windows)
                            or not 0 < latent_rank < head_dim
                            or sm_scale is None):
            raise ValueError("a latent pool is a float pool of one row a "
                             "token (n_heads 1, latent_rank < head_dim, "
                             "an sm_scale), global, with no index keys")
        self.latent_rank = int(latent_rank)
        self.sm_scale = None if sm_scale is None else float(sm_scale)
        if index_dim and (int8 or index_top_k < 1):
            raise ValueError("an index-key pool is a float pool with "
                             "index_top_k >= 1")
        self.layer_names = list(layer_names)
        self.n_heads = int(n_heads)
        self.q_heads = int(q_heads or n_heads)
        self.head_dim = int(head_dim)
        self.index_dim = int(index_dim)
        self.index_top_k = int(index_top_k)
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
        windows = [0] * len(self.layer_names) if windows is None \
            else [int(w) for w in windows]
        if len(windows) != len(self.layer_names) or min(windows) < 0:
            raise ValueError(f"windows {windows}: one entry >= 0 a layer")
        if any(windows) and (int8 or index_dim):
            raise ValueError("sliding-window layers take a float pool "
                             "with no index keys")
        self.kinds: List[_TableKind] = []
        sizes = sorted(set(windows))
        if len(sizes) - (0 in sizes) > 1:
            raise ValueError(f"windows {windows}: window layers of one "
                             "size")
        for w in sizes:
            name = "global" if w == 0 else "window"
            width = self.max_pages_per_slot if w == 0 else min(
                self.max_pages_per_slot,
                math.ceil(w / page_size) + math.ceil(ring_slack / page_size)
                + 1)
            if isinstance(n_pages, dict):
                pages = n_pages.get(name)
            elif n_pages is None or len(sizes) == 1:
                pages = n_pages
            else:
                raise ValueError("layers of more than one kind take "
                                 "n_pages as {kind: pages}")
            pages = self.n_slots * width if pages is None else int(pages)
            if pages < 1:
                raise ValueError("page_size, n_pages and n_slots must be "
                                 ">= 1")
            self.kinds.append(_TableKind(
                name, w, [n for n, lw in zip(self.layer_names, windows)
                          if lw == w], width, pages, self.n_slots))
        self._kind_of = {n: k for k in self.kinds for n in k.layers}
        self.windowed = any(k.ring for k in self.kinds)

    # the first kind's: all there is for a model of one kind of layer
    n_pages = property(lambda self: self.kinds[0].n_pages)
    tables = property(lambda self: self.kinds[0].tables)
    _free = property(lambda self: self.kinds[0].free)
    _owned = property(lambda self: self.kinds[0].owned)

    def kind_of(self, layer: Optional[str] = None) -> _TableKind:
        """The kind of ``layer``'s table (``None``: the first kind)."""
        return self.kinds[0] if layer is None else self._kind_of[layer]

    def pack(self, values: Sequence[object]):
        """One value a kind (a table, a shape), in the order of ``kinds``,
        as the programs take them: the one value itself for a cache of one
        kind (a decode tick of such a model builds no dict), else ``{kind:
        value}``."""
        if len(self.kinds) == 1:
            return values[0]
        return {k.name: v for k, v in zip(self.kinds, values)}

    def table_of(self, tables, layer: Optional[str] = None):
        """``layer``'s own of what :meth:`pack` made."""
        return tables[self.kind_of(layer).name] \
            if isinstance(tables, dict) else tables

    # -- device pool ------------------------------------------------------ #
    def init_pool(self):
        """Zeroed device pool pytree: ``{layer: {"k", "v"[, "k_scale",
        "v_scale"][, "ki"]}}`` with pages laid out ``(n_pages, page_size,
        n_heads, head_dim)`` (scales ``(n_pages, page_size, n_heads,
        1)``, index keys ``(n_pages, page_size, index_dim)``), ``n_pages``
        the layer's kind's; a latent pool ``{layer: {"latent": (n_pages,
        page_size, head_dim)}}`` and nothing else.  Zero pages read back
        as the zero rows of a fresh contiguous cache."""
        def one(n_pages):
            if self.latent_rank:
                return {"latent": jnp.zeros(
                    (n_pages, self.page_size, self.head_dim), self.dtype)}
            shape = (n_pages, self.page_size, self.n_heads, self.head_dim)
            sshape = shape[:-1] + (1,)
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

        return {name: one(self.kind_of(name).n_pages)
                for name in self.layer_names}

    def latent_row_bytes(self) -> int:
        """Bytes one token costs a latent layer (``kv/latent_row_bytes``;
        0 for a pool of K and V)."""
        return self.head_dim * self.dtype.itemsize if self.latent_rank else 0

    def latent_bytes(self) -> int:
        """Bytes of the latent pools, every layer (``kv/latent_bytes``)."""
        return len(self.layer_names) * self.n_pages * self.page_size \
            * self.latent_row_bytes()

    def index_bytes(self) -> int:
        """Bytes the index keys take of the pool (``kv/index_bytes``)."""
        return len(self.layer_names) * self.n_pages * self.page_size \
            * self.index_dim * self.dtype.itemsize

    # -- host allocator --------------------------------------------------- #
    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(max(int(n_tokens), 0) / self.page_size)

    def can_fit(self, n_tokens: int) -> bool:
        need = self.pages_for(n_tokens)
        with self._lock:
            return all(min(need, k.width) <= len(k.free)
                       for k in self.kinds)

    def fits_pool(self, n_tokens: int) -> bool:
        """Whether the pools, all free, could hold a slot of ``n_tokens``
        rows (of every kind: a ring never asks for more than its
        width)."""
        need = self.pages_for(n_tokens)
        return all(min(need, k.width) <= k.n_pages for k in self.kinds)

    def alloc_for(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s tables, of every kind, to cover ``n_tokens``
        token rows.  All-or-nothing: returns False (allocating nothing)
        when some kind's free list cannot cover its growth — the caller
        then evicts or backpressures.  A ring that is whole grows no
        more: the logical pages past it are RECYCLED in place, each onto
        the column of the page a ring's width behind it."""
        need_pages = self.pages_for(n_tokens)
        if need_pages > self.max_pages_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens need {need_pages} pages "
                f"> max_pages_per_slot {self.max_pages_per_slot} "
                f"(max_context {self.max_context})")
        with self._lock:
            # (a decode step asks this of every live slot: most of the
            # time the row fits the pages held, and nothing moves)
            for k in self.kinds:
                # (a ring has covered what it holds or has recycled)
                if need_pages > (k.covered[slot] if k.ring
                                 else len(k.owned[slot])):
                    break
            else:
                return True
            grow = [min(need_pages, k.width) - len(k.owned[slot])
                    for k in self.kinds]
            if any(g > len(k.free) for g, k in zip(grow, self.kinds)):
                return False
            for g, k in zip(grow, self.kinds):
                owned = k.owned[slot]
                for _ in range(g):
                    page = k.free.pop(0)
                    k.tables[slot, len(owned)] = page
                    owned.append(page)
                if k.ring and need_pages > k.covered[slot]:
                    self._recycle_locked(k, slot, need_pages)
            if max(grow) > 0:
                self.recorder.inc("kv/page_allocs",
                                  sum(g for g in grow if g > 0))
                self._publish_gauges_locked()
            return True

    def _recycle_locked(self, kind: _TableKind, slot: int, need_pages: int):
        """``slot`` moves on to logical page ``need_pages - 1`` of a ring:
        every page of it past the ring's width takes the column (and the
        device page) of the page a width behind it, whose rows fall out
        of every later query's window before the first of them is
        overwritten.  The table does not change: what changes is which
        position a column's rows are read as."""
        recycled = need_pages - max(int(kind.covered[slot]), kind.width)
        kind.covered[slot] = need_pages
        if recycled > 0:
            with self.recorder.span("kv.recycle", slot=slot,
                                    pages=int(recycled)):
                self.recorder.inc("kv/pages_recycled", int(recycled))

    def free_slot(self, slot: int, evict: bool = False) -> int:
        """Return every page ``slot`` owns, of every kind, to the free
        lists (retirement or eviction); the table rows reset to ``-1`` so
        in-flight gathers read zeros and writes drop.  Returns the page
        count."""
        with self._lock:
            n = 0
            for k in self.kinds:
                owned = k.owned[slot]
                for page in owned:
                    if page in k.free:
                        raise PagePoolError(
                            f"double free: page {page} of slot {slot} is "
                            "already on the free list")
                    k.free.append(page)
                n += len(owned)
                k.free.sort()
                k.owned[slot] = []
                k.covered[slot] = 0
                k.tables[slot, :] = -1
            if n:
                self.recorder.inc("kv/page_frees", n)
            if evict:
                self.recorder.inc("kv/evictions")
            self._publish_gauges_locked()
            return n

    def _used_locked(self) -> int:
        return sum(k.n_pages - len(k.free) for k in self.kinds)

    def pages_in_use(self) -> int:
        with self._lock:
            return self._used_locked()

    def fill(self) -> float:
        """Pool fill fraction in [0, 1] — the ``kv/pool_fill`` gauge."""
        with self._lock:
            return self._used_locked() / sum(k.n_pages for k in self.kinds)

    def check_invariants(self):
        """For every kind: every page owned at most once, free+owned ==
        n_pages, the table's columns the ledger's pages in order and no
        slot past its table's width (test seam; raises
        :class:`PagePoolError` on violation)."""
        with self._lock:
            for k in self.kinds:
                seen = list(k.free)
                for slot, owned in k.owned.items():
                    seen += owned
                    if len(owned) > k.width:
                        raise PagePoolError(
                            f"slot {slot} holds {len(owned)} {k.name} pages "
                            f"of a table {k.width} wide")
                    for i, page in enumerate(owned):
                        if k.tables[slot, i] != page:
                            raise PagePoolError(
                                f"table/ledger disagree at slot {slot}[{i}]")
                if sorted(seen) != list(range(k.n_pages)):
                    raise PagePoolError(
                        f"page ledger broken: {sorted(seen)} != "
                        f"0..{k.n_pages - 1}")

    def _publish_gauges_locked(self):
        used = self._used_locked()
        rec = self.recorder
        rec.gauge("kv/pages_in_use", used)
        if len(self.kinds) > 1:
            for k in self.kinds:
                rec.gauge(f"kv/pages_in_use_{k.name}",
                          k.n_pages - len(k.free))
        fill = used / sum(k.n_pages for k in self.kinds)
        rec.gauge("kv/pool_fill", fill)
        if fill > rec.gauge_value("kv/peak_fill", 0.0):
            rec.gauge("kv/peak_fill", fill)

    # -- jitted write/attend (fixed shapes, traced) ------------------------ #
    @staticmethod
    def _oob(idx, layer_pool):
        """Map the host tables' ``-1`` free markers to ``n_pages`` (the
        pool's own, which is its kind's) —
        genuinely out of bounds.  jax scatter/gather WRAP negative
        indices (numpy semantics) *before* the drop/fill bounds check,
        so a raw ``-1`` would silently alias the pool's LAST page: a
        dead slot's write clobbered whichever request owned it.  A
        positive out-of-range index is what ``mode="drop"`` /
        ``mode="fill"`` actually drop/fill."""
        pages = layer_pool["latent" if "latent" in layer_pool else "k"]
        return jnp.where(idx < 0, pages.shape[0], idx)

    def gather_window(self, layer_pool, tables):
        """(k_win, v_win) each ``(slots, heads, window, head_dim)``
        gathered from ``layer_pool`` through ``tables`` (slots,
        max_pages); ``-1`` entries fill with zeros.  Pages concatenate
        in table order, so a slot's window is exactly the contiguous
        cache a ``init_cache``-path request would hold."""
        tables = self._oob(tables, layer_pool)
        if self.latent_rank:
            # a latent pool's window is its rows: (slots, window, head_dim)
            return jnp.take(layer_pool["latent"], tables, axis=0,
                            mode="fill", fill_value=0).reshape(
                                tables.shape[0], -1, self.head_dim)

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
        pages = jnp.take(layer_pool["ki"], self._oob(tables, layer_pool), axis=0,
                         mode="fill", fill_value=0)
        return pages.reshape(tables.shape[0], -1, self.index_dim)

    def attention_path(self, backend: Optional[str] = None
                       ) -> Tuple[str, str]:
        """``(route, why)`` of :meth:`attend` for this pool: ``"sparse"``
        (index keys scored, the best rows gathered), ``"pallas"`` (the
        live slots' visible pages read in place: a float pool on a TPU,
        its query heads grouped over the KV heads or not, its layers
        windowed or not) or ``"gather"`` (every slot's whole table, then
        float32 math) — for the last two
        :func:`~bigdl_tpu.ops.paged_attention_path` over the pool's dtype
        and row geometry."""
        if self.index_dim:
            return "sparse", (f"the pool holds index keys: top "
                              f"{self.index_top_k} rows a slot")
        if self.latent_rank:
            return "latent", (f"one row of {self.head_dim} a token: "
                              "absorbed queries over each slot's gathered "
                              "table")
        return paged_attention_path(
            jnp.int8 if self.int8 else self.dtype, self.n_heads,
            self.head_dim, q_heads=self.q_heads, page_size=self.page_size,
            backend=backend)

    def chunk_table_width(self, kind: _TableKind,
                          n_pages: Optional[int] = None) -> int:
        """Columns of the table a chunk program walks for a layer of
        ``kind``: the ``n_pages`` of the longest prompt (a slot's whole
        table by default) for a global layer; a window layer's whole ring,
        filled up with columns that hold no page to a whole number of the
        chunk kernel's key blocks (a ring's width is what the window
        makes it, 37 at 4,096 keys in pages of 128, and a kernel that
        must divide it would take a page a step)."""
        if not kind.ring:
            return self.max_pages_per_slot if n_pages is None else n_pages
        return -(-kind.width // _CHUNK_MAX_PAGES) * _CHUNK_MAX_PAGES

    def chunk_attention_path(self, chunk: int,
                             n_pages: Optional[int] = None,
                             backend: Optional[str] = None,
                             layer: Optional[str] = None
                             ) -> Tuple[str, str]:
        """``(route, why)`` of :meth:`attend_chunk` for ``chunk`` queries
        against a table of ``n_pages`` pages (a slot's whole table by
        default): ``"pallas"`` (the pages read in place, the scores kept
        on the chip) or ``"window"`` (the window gathered, then float32
        math in XLA), by :func:`~bigdl_tpu.ops.paged_attention.
        paged_chunk_attention_path` over the pool's dtype and geometry.
        An index-key pool takes the same routes: its selection reaches
        either as a mask, and so does a window layer's lower bound
        (``layer``: whose kind's table; the first kind's by default)."""
        if self.latent_rank:
            return "latent", (f"one row of {self.head_dim} a token: the "
                              f"{latent_chunk_formula()} formula over the "
                              "gathered table up to the chunk's end, a "
                              "block of keys and of heads a step")
        return paged_chunk_attention_path(
            jnp.int8 if self.int8 else self.dtype, self.q_heads,
            self.n_heads, self.head_dim, self.page_size, chunk,
            self.chunk_table_width(self.kind_of(layer), n_pages),
            backend=backend)

    def attend(self, layer_pool, tables, lengths, q, index=None,
               layer: Optional[str] = None, rows=None):
        """Single-token attention of q ``(slots, heads, 1, head_dim)``
        over each slot's pages, the row :meth:`write_token` just wrote
        at ``lengths[s]`` included (write, then attend).  Keys past it
        are masked and their V rows zeroed, so a recycled page's stale
        or non-finite rows cannot leak; a dead slot reads zeros (its
        token is never emitted).  ``index`` = (qi ``(slots, index heads,
        index_dim)``, w ``(slots, index heads)``) of an index-key pool.
        ``layer`` says whose kind ``tables`` is (the first kind's by
        default): a window layer's is a ring, and the mask is by position.
        A latent pool takes q absorbed and the tokens' own ``rows``, and
        attends them beside the rows BEFORE each slot's position: its
        write comes after (the same result; ``_latent_attend`` says why).
        A cache with window layers attends every layer through
        ``ops/paged_attention.py`` ``_window_attend``, which picks the
        kernel or the gathered math as :meth:`attention_path` says (one
        name in the step's device trace for every layer's attention,
        whichever route it takes).
        Returns ``(slots, heads, 1, head_dim)`` in q's dtype."""
        route, why = self.attention_path()
        if route == "latent":
            # q (slots, heads, 1, head_dim) absorbed -> softmax . c
            # (slots, heads, 1, latent_rank); `rows` are the tokens' own
            # (slots, 1, head_dim), which the pool need not hold yet: a
            # latent pool is attended as it came in and written after
            # (see `_latent_attend`)
            return _latent_attend(
                q[:, :, 0], rows[:, 0], layer_pool["latent"], tables,
                lengths, rank=self.latent_rank,
                sm_scale=self.sm_scale)[:, :, None]
        if route == "sparse":
            qi, w = index
            return sparse_paged_attention(
                q[:, :, 0], qi, w, layer_pool["k"], layer_pool["v"],
                self.gather_index(layer_pool, tables), tables, lengths,
                self.index_top_k)[:, :, None]
        if jax.default_backend() == "tpu" and route == "gather" \
                and not self.int8:
            # on the chip the window is never the intended route for a
            # float pool: say so (once per call site)
            warnings.warn("PagedKVCache.attend gathers every slot's "
                          f"whole window, not the Pallas kernel: {why}",
                          stacklevel=2)
        if self.windowed:
            return _window_attend(
                q[:, :, 0], layer_pool["k"], layer_pool["v"], tables,
                lengths, window=self.kind_of(layer).window)[:, :, None]
        if route == "pallas":
            return paged_attention(q[:, :, 0], layer_pool["k"],
                                   layer_pool["v"], tables,
                                   lengths)[:, :, None]
        k_win, v_win = self.gather_window(layer_pool, tables)
        return attend_window(q, k_win, v_win, lengths)

    def write_token(self, layer_pool, tables, lengths, k_new, v_new=None,
                    ki_new=None, layer: Optional[str] = None):
        """Scatter one new k/v row per slot into the pool at
        ``(table[len // page], len % page)``.  k_new/v_new are
        ``(slots, heads, 1, head_dim)`` (the
        :meth:`~bigdl_tpu.models.transformer.MultiHeadAttention.project_qkv`
        output), ki_new ``(slots, index_dim)`` the index key of an
        index-key pool; dead slots' ``-1`` page indices drop.  In a ring
        (``layer``'s kind) the column is the page's index modulo the
        ring's width.  A latent pool takes the token's one row as
        ``k_new`` ``(slots, 1, head_dim)`` and nothing else
        (:func:`_write_rows`)."""
        col = lengths // self.page_size
        if self.kind_of(layer).ring:
            col = col % tables.shape[1]
        pidx = self._oob(jnp.take_along_axis(
            tables, col[:, None], axis=1)[:, 0], layer_pool)
        off = lengths % self.page_size
        if self.latent_rank:
            # k_new: the token's one row, (slots, 1, head_dim)
            return {"latent": _write_rows(layer_pool["latent"], pidx, off,
                                          k_new)}
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
        table = self._oob(table, layer_pool)
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

    def write_chunk(self, layer_pool, table, k, v=None, ki=None):
        """A prefill chunk's rows into the pages of ``table`` (the chunk's
        own, ``C / page_size`` entries): k/v ``(1, heads, C, head_dim)``,
        ki ``(1, C, index_dim)``.  Row by row, as :meth:`write_token`
        scatters: a scatter of whole pages has the compiler re-lay every
        layer's whole pool for it and back, which a pool of gigabytes
        cannot pay a chunk (a float pool only).  A latent pool takes the
        chunk's rows as ``k`` ``(1, C, head_dim)``: whole pages of them,
        an in-place update a page (:func:`_write_rows`)."""
        if self.latent_rank:
            # k: the chunk's rows, (1, C, head_dim): whole pages of them
            rows = k[0].reshape(-1, self.page_size, k.shape[-1])
            return {"latent": _write_rows(
                layer_pool["latent"], self._oob(table, layer_pool),
                jnp.zeros_like(table), rows)}
        n = k.shape[2]
        at = jnp.arange(n)
        pidx = jnp.take(self._oob(table, layer_pool), at // self.page_size)
        off = at % self.page_size
        out = dict(layer_pool)
        for key, rows in (("k", jnp.swapaxes(k[0], 0, 1)),
                          ("v", jnp.swapaxes(v[0], 0, 1)),
                          ("ki", None if ki is None else ki[0])):
            if rows is not None:
                out[key] = layer_pool[key].at[pidx, off].set(
                    rows.astype(layer_pool[key].dtype), mode="drop")
        return out

    def chunk_pages(self, table, start, chunk: int,
                    layer: Optional[str] = None):
        """The ``chunk / page_size`` entries of ``table`` that a chunk at
        ``start`` writes: consecutive columns of a table in order, the
        columns modulo the ring's width of a ring."""
        n, first = chunk // self.page_size, start // self.page_size
        kind = self.kind_of(layer)
        if kind.ring:
            return jnp.take(table, (first + jnp.arange(n)) % kind.width)
        return jax.lax.dynamic_slice_in_dim(table, first, n)

    def attend_chunk(self, layer_pool, table, start, q, index=None,
                     layer: Optional[str] = None, up=None):
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
        it.  A window layer (``layer``'s kind) hands its ring over as
        ``table``: the chunk's own rows lie in it already, every key a
        query of the chunk may see still does (the ring holds the window
        and a chunk more), each column's rows are read at the position
        the chunk's last page gives them, and the window's lower bound is
        one more term of the same mask; the ring costs the same whatever
        ``start`` is, as the longest prompt's table does.  Returns
        ``(1, heads, C, head_dim)``.  A latent pool takes q un-absorbed
        (``(1, heads, C, nope + rope)``) and ``up`` = (W_uk ``(rank,
        heads, nope)``, W_uv ``(rank, heads, v)``), and returns the heads'
        values ``(1, heads, C, v)``: ``_latent_chunk_attend``, the table
        gathered and walked a block of keys a step up to the chunk's end
        only (``latent_chunk_walk``), a block of heads at a time: unlike
        the routes above, a latent chunk costs more the later it falls in
        its prompt."""
        chunk = q.shape[2]
        if self.latent_rank:
            return _latent_chunk_attend(
                q[0], up[0], up[1], layer_pool["latent"], table, start,
                rank=self.latent_rank, sm_scale=self.sm_scale,
                absorbed=latent_chunk_formula() == "absorbed")[None]
        kind, bounds = self.kind_of(layer), {}
        if kind.ring:
            k_pos = ring_positions(
                jnp.asarray((start + chunk - 1) // self.page_size)[None],
                kind.width, self.page_size)
            pad = self.chunk_table_width(kind) - kind.width
            if pad:
                table = jnp.concatenate(
                    [table, jnp.full((pad,), -1, table.dtype)])
                k_pos = jnp.concatenate(
                    [k_pos, jnp.full((1, pad * self.page_size), -1,
                                     k_pos.dtype)], axis=1)
            bounds = {"window": kind.window, "k_pos": k_pos}
        tab = table[None]
        q_pos = (start + jnp.arange(chunk))[None]
        kv_len = jnp.asarray(start + chunk)[None]
        if index is not None:
            index = (index[0], self.gather_index(layer_pool, tab), index[1])
        n_pages = table.shape[0]
        route, why = self.chunk_attention_path(chunk, n_pages, layer=layer)
        if route == "pallas":
            mask = attention_mask(q_pos, kv_len, n_pages * self.page_size,
                                  index, self.index_top_k, **bounds)[0]
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
                           self.index_top_k, **bounds)


def _write_rows(pages, pidx, off, rows):
    """``rows[i]`` (n, r, width) into ``pages`` (n_pages, page_size,
    width) at ``(pidx[i], off[i])``, one in-place update a write; a
    ``pidx`` past the pool (a dead slot, a page the slot does not hold)
    writes back what lies at the clamped place.  A latent pool's writes:
    its rows are 576 wide, which the TPU lays out with the page's rows,
    not the row's columns, along the lanes, and a row scatter
    (``.at[pidx, off].set``) has the compiler re-lay the whole pool for
    it and back, 2.6 ms a layer a step at 396 MB (my chip runs, PR 34);
    an update of a slice keeps whatever layout the pool has."""
    rows = rows.astype(pages.dtype)

    def one(i, pages):
        new = jax.lax.dynamic_index_in_dim(rows, i, 0, keepdims=True)
        at = (jnp.minimum(pidx[i], pages.shape[0] - 1), off[i], 0)
        old = jax.lax.dynamic_slice(pages, at, new.shape)
        return jax.lax.dynamic_update_slice(
            pages, jnp.where(pidx[i] < pages.shape[0], new, old), at)

    return jax.lax.fori_loop(0, rows.shape[0], one, pages)


__all__ = ["PagedKVCache", "PagePoolError"]
