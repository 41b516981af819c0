"""The paged decode-attention kernel against the gathered routes.

The kernel (``ops/paged_attention.py``) runs here in Pallas interpret
mode through its ``_INTERPRET`` hook; ``PagedKVCache.gather_window`` +
``attend_window``, and for grouped heads and window layers
``gathered_attention``, are the reference.  Pinned:

  * kernel == reference over pool dtype, page size (8, 16, 128), query /
    KV heads (8 / 8, 8 / 2, 28 / 4) and ragged lengths: a table in order
    (a dead slot, 1 key, a page less one, a page, a page and one, the
    whole context) and a ring under a window (before the window is full,
    a page's last row, a dead slot between live ones, inside the first
    lap, past one lap and past several)
  * non-finite rows no query may see (the tail of a slot's last page, a
    page it holds but has not reached, a recycled page's rows behind the
    window and past the newest row, every free page) never leak
  * ``paged_attention_path`` picks the route from backend, pool dtype
    and row geometry (grouped heads and page size too), and says why;
    ``decode_blocks`` picks the pages a block from the page's shape
  * one ``DecodeEngine`` stream through the kernel emits the greedy
    tokens of the gather route, eviction and readmission included
  * the kernel compiles under Mosaic for a described v5e at the serve
    cell's widths and at the mixed cell's two kinds of layer (no chip
    needed)
  * the chunk kernel (``paged_chunk_attention``) == ``gather_window`` +
    ``sparse_attention.attend`` over grouped and equal heads, an index
    mask and the causal mask alone, the first, a middle and the last
    chunk of a table, ``-1`` at the table's tail, NaN pages behind
    ``kv_len``, both pool dtypes; its route and reason; its compile for
    a v5e at the long-document cell's widths, the pool handed over as
    it lies
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.models import transformer as T
from bigdl_tpu.ops import paged_attention_mod as pa
from bigdl_tpu.ops import paged_attention_path
from bigdl_tpu.ops import sparse_attention as sa
from bigdl_tpu.serving import DecodeEngine, ModelRegistry, PagedKVCache
from bigdl_tpu.serving import kvcache

H, D, CTX = 8, 128, 64
# query heads / KV heads: equal, grouped, and the mixed cell's 28 / 4
HEADS = [(H, H), (8, 2), (28, 4)]
RING = 5                 # a window of 3 pages, a page of slack and one more


@pytest.fixture()
def interpret(monkeypatch):
    """The kernels interpreted on the CPU.  `_window_attend` reads the
    hook while it traces, so its traces are dropped around the test."""
    pa._window_attend.clear_cache()
    monkeypatch.setattr(pa, "_INTERPRET", True)
    yield
    pa._window_attend.clear_cache()


def _lengths(page, kind="table"):
    # slot 0 is dead (no page, length 0); the rest are live
    if kind == "table":
        return [0, 0, 1, page - 1, page, page + 1, max(CTX, 4 * page) - 1]
    # a ring of RING pages, a window of three: before the window is full,
    # the last row of a page, a dead slot between live ones, inside the
    # first lap past the window, the lap's last row, past one lap (and at
    # a page's last row there), past several
    lap = RING * page
    return [0, 1, 3 * page - 2, 2 * page - 1, 0, 3 * page + 3, lap - 1,
            lap + 2, 2 * lap - page - 1, 3 * lap + 2 * page + 5]


def _case(dtype, page, poison=False, pages_per_block=2, heads=(H, H),
          kind="table"):
    """(kernel output, reference) for one pool: slot ``s`` holds
    ``_lengths(page, kind)[s]`` tokens and attends one more.  ``kind``
    ``"table"``: no window, a table as wide as the context; ``"ring"``: a
    window of three pages over a ring of RING.  ``poison``: every row no
    query may see is NaN in K and V."""
    lens = _lengths(page, kind)
    n_slots = len(lens)
    dead = [s for s, n in enumerate(lens) if n == 0 and s in (0, 4)]
    q_heads, kv_heads = heads
    window = 3 * page if kind == "ring" else 0
    ctx = max(lens) + 1
    width = RING if window else ctx // page
    kv = PagedKVCache(["a"], n_heads=kv_heads, q_heads=q_heads, head_dim=D,
                      n_pages=n_slots * width + 2, page_size=page,
                      n_slots=n_slots, max_context=ctx, dtype=dtype,
                      windows=[window], ring_slack=page)
    assert kv.kinds[0].width == width
    rng = np.random.default_rng(page)
    shape = (kv.n_pages, page, kv_heads, D)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    for s, n in enumerate(lens):
        if s not in dead:
            # slot 3 of a table also holds a page it has not reached yet
            assert kv.alloc_for(s, n + 1 + (page if s == 3 and not window
                                            else 0))
    tables = jnp.asarray(kv.tables)
    lengths = jnp.asarray(np.asarray(lens, np.int32))
    if poison:
        # by position, as the cache's masks go: rows past the newest, a
        # recycled page's rows behind the window, pages never reached
        pos = np.asarray(sa.ring_positions(lengths // page, kv.tables.shape[1],
                                           page)).reshape(n_slots, -1, page)
        at = np.asarray(lens)[:, None, None]
        hidden = (pos < 0) | (pos > at) | ((pos <= at - window) & (window > 0))
        for arr in (k, v):
            arr[kv._free] = np.nan                       # freed pages
            for s, c in zip(*np.nonzero(kv.tables >= 0)):
                arr[kv.tables[s, c], hidden[s, c]] = np.nan
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    q = jnp.asarray(rng.standard_normal((n_slots, q_heads, 1, D)), dtype)
    if heads == (H, H) and not window:
        k_win, v_win = kv.gather_window({"k": k, "v": v}, tables)
        ref = pa.attend_window(q, k_win, v_win, lengths)[:, :, 0]
    else:
        ref = pa.gathered_attention(q[:, :, 0], k, v, tables, lengths,
                                    window)
    out = pa.paged_attention(q[:, :, 0], k, v, tables, lengths,
                             window=window, pages_per_block=pages_per_block)
    return (np.asarray(out, np.float32), np.asarray(ref, np.float32))


_cached_case = functools.lru_cache(maxsize=None)(_case)
_SLOTS = [("table", s) for s in range(7)] + [("ring", s) for s in range(10)]


@pytest.mark.parametrize("kind,slot", _SLOTS)
@pytest.mark.parametrize("page,heads", [(p, h) for h in HEADS
                                        for p in (8, 16)] + [(128, (28, 4))])
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-6),
                                       ("bfloat16", 1.6e-2)])
def test_kernel_matches_gathered_window(interpret, dtype, tol, page, heads,
                                        kind, slot):
    out, ref = _cached_case(dtype, page, heads=heads, kind=kind)
    length = _lengths(page, kind)[slot]
    assert np.isfinite(out).all()
    if not length and slot in (0, 4):
        assert not out[slot].any()       # a dead slot reads zeros
    else:
        assert np.abs(out[slot] - ref[slot]).max() <= tol, f"length {length}"


@pytest.mark.parametrize("heads,kind", [((H, H), "table"), ((28, 4), "ring")])
@pytest.mark.parametrize("pages_per_block", [1, 3, 8])
def test_kernel_block_size_does_not_change_the_result(interpret,
                                                      pages_per_block, heads,
                                                      kind):
    out, ref = _case("float32", 8, pages_per_block=pages_per_block,
                     heads=heads, kind=kind)
    live = np.asarray(_lengths(8, kind)) > 0
    assert np.abs(out[live] - ref[live]).max() <= 3e-6
    # and the block the shape gives: pages of 16 rows of 16 heads in bf16
    # come eight at a time, pages of 128 rows of 4 heads too (1 MiB of K)
    assert pa.decode_blocks(16, 16, 128, "bfloat16") == 8
    assert pa.decode_blocks(128, 4, 128, "bfloat16") == 8
    assert pa.decode_blocks(128, 16, 128, "float32") == 1


@pytest.mark.parametrize("heads,kind", [((H, H), "table"), ((8, 2), "table"),
                                        ((8, 2), "ring"), ((28, 4), "ring")])
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-6),
                                       ("bfloat16", 1.6e-2)])
def test_non_finite_rows_past_the_length_do_not_leak(interpret, dtype, tol,
                                                     heads, kind):
    """NaN in the masked tail of a slot's last page, in a page the slot
    holds but has not reached, in a recycled page's rows behind the
    window and past the newest row, and in every free page: 0 * NaN
    would be NaN, so both routes scrub the masked V rows."""
    out, ref = _case(dtype, 8, poison=True, heads=heads, kind=kind)
    clean, _ = _cached_case(dtype, 8, heads=heads, kind=kind)
    live = np.asarray(_lengths(8, kind)) > 0
    assert np.isfinite(ref).all() and np.isfinite(out).all()
    assert np.abs(out[live] - ref[live]).max() <= tol
    assert np.array_equal(out, clean)    # the poison changed nothing


_GROUPED = dict(pool_dtype="bfloat16", n_heads=4, q_heads=28, head_dim=128,
                page_size=128, backend="tpu")


@pytest.mark.parametrize("kw,route,why", [
    (dict(pool_dtype="bfloat16", n_heads=16, head_dim=128, backend="cpu"),
     "gather", "backend 'cpu' is not tpu"),
    (dict(pool_dtype="int8", n_heads=16, head_dim=128, backend="tpu"),
     "gather", "pool dtype int8 is not a float"),
    (dict(pool_dtype="float32", n_heads=2, head_dim=64, backend="tpu"),
     "gather", "head_dim 64 is not a multiple of 128"),
    (dict(pool_dtype="bfloat16", n_heads=12, head_dim=128, backend="tpu"),
     "gather", "n_heads 12 is not a multiple of 8"),
    (dict(pool_dtype="bfloat16", n_heads=16, head_dim=128, backend="tpu"),
     "pallas", "tpu backend"),
    (dict(pool_dtype="float32", n_heads=8, head_dim=128, backend="tpu"),
     "pallas", "tpu backend"),
    # grouped heads: the mixed cell's 28 / 4 and chip_smoke.py's 14 / 2
    (_GROUPED, "pallas", "tpu backend, float pool, 7 query heads a KV head"),
    (dict(_GROUPED, n_heads=2, q_heads=14), "pallas",
     "7 query heads a KV head, rows tile"),
    (dict(_GROUPED, backend="cpu"), "gather", "backend 'cpu' is not tpu"),
    (dict(_GROUPED, pool_dtype="int8"), "gather", "int8 is not a float"),
    (dict(_GROUPED, q_heads=30), "gather",
     "30 query heads do not group over 4 KV heads"),
    # one bfloat16 head a row is half a sublane's 32 bits; one float32 is one
    (dict(_GROUPED, n_heads=1, q_heads=7), "gather",
     "n_heads 1 is not a multiple of 8 (one sublane tile) nor a tile"),
    (dict(_GROUPED, n_heads=1, q_heads=7, pool_dtype="float32"), "pallas",
     "7 query heads a KV head"),
    (dict(_GROUPED, n_heads=2, q_heads=8, page_size=4), "gather",
     "a block of pages of 4 rows of 2 heads is no whole lane tile"),
])
def test_paged_attention_path_says_which_route_and_why(kw, route, why):
    got, reason = paged_attention_path(**kw)
    assert got == route and why in reason, (got, reason)


def test_cache_routes_by_what_it_holds(interpret, monkeypatch):
    """``PagedKVCache.attention_path`` is ``paged_attention_path`` over
    the pool it built: the hook stands in for the TPU backend, an int8
    pool and the tiny preset's head_dim 64 stay on the window; grouped
    heads and window layers take the kernel where the rows tile, and a
    cache with window layers reaches it through ``_window_attend``."""
    mk = lambda **kw: PagedKVCache(["a"], n_pages=4, page_size=8,
                                   n_slots=2, max_context=16, **kw)
    assert mk(n_heads=H, head_dim=D).attention_path()[0] == "pallas"
    assert mk(n_heads=H, head_dim=D, int8=True).attention_path()[0] \
        == "gather"
    assert mk(n_heads=2, head_dim=64).attention_path()[0] == "gather"
    assert mk(n_heads=H, head_dim=D).attention_path(backend="tpu") \
        == paged_attention_path("float32", H, D, backend="tpu")
    grouped = mk(n_heads=4, q_heads=28, head_dim=D)
    ring = mk(n_heads=4, q_heads=28, head_dim=D, windows=[8], ring_slack=0)
    for kv in (grouped, ring):
        assert kv.attention_path() == paged_attention_path(
            "float32", 4, D, q_heads=28, page_size=8)
        assert kv.attention_path()[0] == "pallas"
    assert mk(n_heads=4, q_heads=28, head_dim=64, windows=[8]
              ).attention_path()[0] == "gather"
    # which entry `attend` calls, and what that entry runs
    calls = []
    for name in ("_window_attend", "paged_attention"):
        real = getattr(kvcache, name)
        monkeypatch.setattr(kvcache, name, lambda *a, _n=name, _f=real, **kw:
                            calls.append(_n) or _f(*a, **kw))
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.standard_normal((4, 8, 4, D)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((2, 28, 1, D)), jnp.float32)
    lengths = jnp.asarray([11, 5])
    for kv, entry, window in ((grouped, "paged_attention", 0),
                              (ring, "_window_attend", 8)):
        assert kv.alloc_for(0, 12) and kv.alloc_for(1, 6)
        tables = jnp.asarray(kv.tables)
        got = kv.attend({"k": k, "v": v}, tables, lengths, q, layer="a")
        assert calls == [entry]
        want = pa.gathered_attention(q[:, :, 0], k, v, tables, lengths,
                                     window)
        assert np.abs(np.asarray(got[:, :, 0]) - np.asarray(want)).max() \
            <= 3e-6
        calls.clear()
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: pa._window_attend(*a, window=8))(
            q[:, :, 0], k, v, tables, lengths))
    monkeypatch.setattr(pa, "_INTERPRET", False)
    pa._window_attend.clear_cache()
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: pa._window_attend(*a, window=8))(
            q[:, :, 0], k, v, tables, lengths))
    for kv in (grouped, ring):
        assert kv.attention_path() == ("gather", "backend 'cpu' is not tpu")
        assert kv.attention_path(backend="tpu")[0] == "pallas"


def _stream(lm, prompts, **kw):
    reg = ModelRegistry()
    reg.register("lm", lm)
    eng = DecodeEngine(reg, "lm", slots=4, page_size=8, max_context=32,
                       max_prompt=16, max_new_tokens=12, **kw).warmup()
    try:
        futs = [eng.submit("lm", p) for p in prompts]
        outs = [f.result(300) for f in futs]
        eng.kv.check_invariants()
        return outs, eng.stats(), eng.recorder
    finally:
        eng.shutdown()


def test_engine_stream_through_the_kernel_matches_the_gather_route(
        monkeypatch):
    """A pool of 6 pages under four requests evicts and readmits; the
    kernel route's greedy tokens are the gather route's, the route is
    what the engine reports, and the counters say what share of the
    window's pages the steps had to read."""
    lm = T.build("tiny", dropout=0.0, d_model=H * D, n_heads=H,
                 n_layers=1, d_ff=128, max_len=64)
    lm.ensure_initialized()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 256, (n,)) for n in (6, 10, 14, 8)]
    ref, ref_stats, ref_rec = _stream(lm, prompts, pool_pages=6)
    assert ref_stats["attn_route"] == "gather"
    assert ref_rec.gauge_value("decode/attn_route", -1.0) == 0.0

    monkeypatch.setattr(pa, "_INTERPRET", True)
    outs, stats, rec = _stream(lm, prompts, pool_pages=6)
    assert stats["attn_route"] == "pallas"
    assert rec.gauge_value("decode/attn_route", -1.0) == 1.0
    assert rec.counter_value("kv/evictions") > 0
    assert rec.counter_value("decode/readmissions") > 0
    for a, b in zip(ref, outs):
        assert np.array_equal(a, b)
    # 4 slots x 4 pages a window; the live pages are a part of it
    assert rec.counter_value("kv/pages_window") \
        == 16 * rec.counter_value("decode/steps")
    assert 0.0 < stats["kv_pages_read_share"] < 1.0
    # int8 stays on the window whatever the hook says
    _, q8_stats, _ = _stream(lm, prompts[:1], int8_kv=True)
    assert q8_stats["attn_route"] == "gather"


# -- a prefill chunk against its slot's pages ------------------------- #
C_PAGE, C_CHUNK, C_PAGES = 16, 32, 16        # a table of 256 keys
C_TOP_K = 24


@functools.lru_cache(maxsize=None)
def _chunk_case(dtype, heads, kv_heads, indexed, where, tail, poison):
    """(kernel output, window-route output) for one chunk of ``C_CHUNK``
    queries at the ``where`` of a prompt: the slot's pages lie scattered
    in a pool other slots share; ``tail``: the table's entries past the
    prompt are ``-1`` (else the slot holds the whole table); ``poison``:
    every row behind ``kv_len``, every page the slot does not hold and
    page 0 (what a ``-1`` entry is clamped to) are NaN in K and V."""
    n_keys = C_PAGES * C_PAGE
    start = {"first": 0, "middle": 3 * C_CHUNK,
             "last": n_keys - C_CHUNK}[where]
    kv_len = start + C_CHUNK
    kv = PagedKVCache(["a"], n_heads=kv_heads, q_heads=heads, head_dim=D,
                      n_pages=3 * C_PAGES, page_size=C_PAGE, n_slots=3,
                      max_context=n_keys, dtype=dtype,
                      index_dim=8 if indexed else 0,
                      index_top_k=C_TOP_K if indexed else 0)
    assert kv.alloc_for(0, 3 * C_PAGE)       # page 0 is another slot's
    for n in range(C_PAGE, (kv_len if tail else n_keys) + 1, C_PAGE):
        assert kv.alloc_for(1, n) and kv.alloc_for(2, n // 2)
    table = kv.tables[1]
    assert (table[-1] < 0) == (tail and where != "last")
    assert 0 not in table and list(table[:3]) != [3, 4, 5]
    rng = np.random.default_rng(heads + len(where))
    shape = (kv.n_pages, C_PAGE, kv_heads, D)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    if poison:
        held = np.zeros(kv.n_pages, bool)
        held[table[table >= 0]] = True
        for arr in (k, v):
            arr[~held] = np.nan
            for j, page in enumerate(table):
                if page >= 0:
                    arr[page, max(kv_len - j * C_PAGE, 0):] = np.nan
    pool = {"k": jnp.asarray(k, dtype), "v": jnp.asarray(v, dtype)}
    q = jnp.asarray(rng.standard_normal((1, heads, C_CHUNK, D)), dtype)
    index = None
    if indexed:
        pool["ki"] = jnp.asarray(
            rng.standard_normal((kv.n_pages, C_PAGE, 8)), dtype)
        index = (jnp.asarray(rng.standard_normal((1, C_CHUNK, 2, 8)), dtype),
                 jnp.asarray(rng.standard_normal((1, C_CHUNK, 2)), dtype))
    tab = jnp.asarray(table)
    out = kv.attend_chunk(pool, tab, jnp.int32(start), q, index)
    k_win, v_win = kv.gather_window(pool, tab[None])
    ref = sa.attend(
        q, k_win, v_win, (start + jnp.arange(C_CHUNK))[None],
        jnp.asarray([kv_len]),
        index and (index[0], kv.gather_index(pool, tab[None]), index[1]),
        C_TOP_K)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("poison", [False, True], ids=["clean", "nan"])
@pytest.mark.parametrize("tail", [False, True], ids=["full", "tail-1"])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("indexed", [False, True], ids=["causal", "index"])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (8, 8)])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 1.6e-2)])
def test_chunk_kernel_matches_the_window_route(interpret, dtype, tol, heads,
                                               kv_heads, indexed, where,
                                               tail, poison):
    kv_route = PagedKVCache(["a"], n_heads=kv_heads, q_heads=heads,
                            head_dim=D, n_pages=4, page_size=C_PAGE,
                            dtype=dtype, max_context=C_PAGES * C_PAGE)
    assert kv_route.chunk_attention_path(C_CHUNK)[0] == "pallas"
    out, ref = _chunk_case(dtype, heads, kv_heads, indexed, where, tail,
                           poison)
    assert np.isfinite(out).all() and np.isfinite(ref).all()
    assert np.abs(out - ref).max() <= tol
    if poison:       # the poison changed nothing
        clean, _ = _chunk_case(dtype, heads, kv_heads, indexed, where,
                               tail, False)
        assert np.array_equal(out, clean)


@pytest.mark.parametrize("blocks", [(1, 8), (4, 16), (2, 8)])
def test_chunk_kernel_blocks_do_not_change_the_result(interpret, blocks):
    """Query heads resident and pages a step, other than `chunk_blocks`
    picks: the same sums in another order."""
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((2, 20, C_PAGE, 2, D)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, 8, C_CHUNK, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(20)[:C_PAGES].astype(np.int32))
    mask = sa.attention_mask((96 + jnp.arange(C_CHUNK))[None],
                             jnp.asarray([128]), C_PAGES * C_PAGE)[0]
    assert pa.chunk_blocks(4, C_CHUNK, C_PAGES, C_PAGE, 2, D, "float32",
                           "float32") == (4, 8)
    want = pa.paged_chunk_attention(q, pool[0], pool[1], table, mask)
    got = pa.paged_chunk_attention(q, pool[0], pool[1], table, mask,
                                   blocks=blocks)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-6


_CHUNK = dict(pool_dtype="bfloat16", q_heads=32, kv_heads=4, head_dim=128,
              page_size=128, chunk=512, n_pages=224)


@pytest.mark.parametrize("kw,route,why", [
    (dict(backend="cpu"), "window", "backend 'cpu' is not tpu"),
    (dict(backend="tpu"), "pallas",
     "tpu backend, float pool, 8 query heads x 512 queries against 1024 "
     "keys a step"),
    (dict(backend="tpu", pool_dtype="int8"), "window",
     "pool dtype int8 is not a float"),
    (dict(backend="tpu", head_dim=64), "window",
     "head_dim 64 is not a multiple of 128"),
    (dict(backend="tpu", q_heads=30), "window",
     "30 query heads do not group over 4 KV heads"),
    (dict(backend="tpu", pool_dtype="float16"), "window",
     "4 KV heads of float16 a row"),
    (dict(backend="tpu", q_heads=30, kv_heads=3), "window",
     "3 KV heads of bfloat16 a row"),
    (dict(backend="tpu", page_size=8), "window",
     "page_size 8 is not a multiple of 16"),
    (dict(backend="tpu", chunk=72), "window",
     "chunk 72 is not a multiple of 32"),
    (dict(backend="tpu", page_size=16, n_pages=47), "window",
     "a table of 47 pages of 16 rows makes no key block"),
    # chip_smoke.py's model: 8 pages of 16 rows are one lane tile of keys
    (dict(backend="tpu", q_heads=8, kv_heads=2, page_size=16, chunk=256,
          n_pages=48), "pallas", "4 query heads x 256 queries against 128"),
    (dict(backend="tpu", pool_dtype="float32", q_heads=8, kv_heads=8,
          page_size=8, chunk=64, n_pages=32), "window",
     "a table of 32 pages of 8 rows makes no key block of up to 8 pages"),
    (dict(backend="tpu", pool_dtype="float32", q_heads=8, kv_heads=8,
          page_size=16, chunk=64, n_pages=32), "pallas",
     "1 query heads x 64 queries against 128"),
])
def test_chunk_attention_path_says_which_route_and_why(kw, route, why):
    got, reason = pa.paged_chunk_attention_path(**dict(_CHUNK, **kw))
    assert got == route and why in reason, (got, reason)


def test_cache_and_engine_route_a_chunk_by_what_they_hold(monkeypatch):
    """`PagedKVCache.chunk_attention_path` is `paged_chunk_attention_path`
    over the pool it built, for a TPU, a CPU and an int8 pool; the engine
    reports it beside `attn_route`, `None` where prompts go in whole."""
    mk = lambda **kw: PagedKVCache(["a"], n_heads=4, q_heads=32,
                                   head_dim=128, n_pages=4, page_size=128,
                                   n_slots=2, max_context=32768, **kw)
    bf16 = mk(dtype=jnp.bfloat16)
    assert bf16.chunk_attention_path(512, 224, backend="tpu") \
        == pa.paged_chunk_attention_path(**_CHUNK, backend="tpu")
    assert bf16.chunk_attention_path(512, backend="tpu")[0] == "pallas"
    assert bf16.chunk_attention_path(512, 224) \
        == ("window", "backend 'cpu' is not tpu")
    assert "int8 is not a float" in mk(int8=True).chunk_attention_path(
        512, 224, backend="tpu")[1]
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert bf16.chunk_attention_path(512, 224)[0] == "pallas"
    assert mk(int8=True).chunk_attention_path(512, 224)[0] == "window"

    lm = T.build("tiny", dropout=0.0, d_model=2 * D, n_heads=2, n_layers=1,
                 d_ff=64, max_len=256)
    lm.ensure_initialized()
    reg = ModelRegistry()
    reg.register("lm", lm)
    prompt = np.random.RandomState(0).randint(0, 256, (70,))

    def serve(**kw):
        eng = DecodeEngine(reg, "lm", slots=2, max_context=256,
                           max_new_tokens=4, **kw).warmup()
        try:
            return (eng.submit("lm", prompt[:kw["max_prompt"]]).result(300),
                    eng.stats(), eng.recorder, eng.chunk_attention_path())
        finally:
            eng.shutdown()

    _, st, rec, (route, why) = serve(max_prompt=16)
    assert st["chunk_attn_route"] is route is None and "no chunk" in why
    assert rec.gauge_value("decode/chunk_attn_route", -1.0) == -1.0
    out, st, rec, _ = serve(max_prompt=128, prefill_chunk=32, page_size=16)
    assert st["chunk_attn_route"] == "pallas" and st["prefill_chunks"] == 3
    assert rec.gauge_value("decode/chunk_attn_route", -1.0) == 1.0
    monkeypatch.setattr(pa, "_INTERPRET", False)
    ref, st, rec, (_, why) = serve(max_prompt=128, prefill_chunk=32,
                                   page_size=16)
    assert st["chunk_attn_route"] == "window" and "cpu" in why
    assert rec.gauge_value("decode/chunk_attn_route", -1.0) == 0.0
    assert np.array_equal(out, ref)          # the same greedy tokens
    monkeypatch.setattr(pa, "_INTERPRET", True)
    _, st, _, (_, why) = serve(max_prompt=128, prefill_chunk=32,
                               page_size=16, int8_kv=True)
    assert st["chunk_attn_route"] == "window" and "int8" in why


# -- the kernel under the chip's compiler, at the serve cell's widths -- #
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype,heads,kv_heads,page,cols,window", [
    ("bfloat16", 16, 16, 16, 32, 0), ("bfloat16", 8, 8, 16, 32, 0),
    ("float32", 8, 8, 16, 32, 0),
    # the mixed cell: a global layer's table, a window layer's ring
    ("bfloat16", 28, 4, 128, 128, 0), ("bfloat16", 28, 4, 128, 37, 4096),
    # chip_smoke.py's windowed stage (4 slots there)
    ("bfloat16", 14, 2, 128, 5, 256),
])
def test_kernel_compiles_for_v5e_without_a_pool_copy(one_chip, dtype, heads,
                                                     kv_heads, page, cols,
                                                     window):
    """32 slots x 512 tokens, 1,024 pages of 16 rows of ``heads`` x 128
    (the serve cell's pool, and chip_smoke.py's 8 heads); 32 slots of 28 /
    4 heads x 128 over pages of 128 rows, a table of 128 columns and a
    ring of 37 under a window of 4,096 (the mixed cell's two kinds of
    layer): Mosaic takes the kernel as written, and XLA hands it the pool
    without a relayout copy (no temporaries at all)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    assert paged_attention_path(dtype, kv_heads, 128, q_heads=heads,
                                page_size=page, backend="tpu")[0] == "pallas"
    pool = sds((32 * cols, page, kv_heads, 128), dtype)
    compiled = jax.jit(functools.partial(
        pa.paged_attention, window=window)).lower(
        sds((32, heads, 128), dtype), pool, pool,
        sds((32, cols), "int32"), sds((32,), "int32")).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("q_heads,kv_heads,page,chunk,n_pages,dtype", [
    (32, 4, 128, 512, 224, "bfloat16"),      # the long-document cell
    (8, 2, 16, 256, 48, "bfloat16"),         # chip_smoke.py's serve stage
    (8, 8, 16, 64, 32, "float32"),
])
def test_chunk_kernel_compiles_for_v5e_without_a_pool_copy(
        one_chip, q_heads, kv_heads, page, chunk, n_pages, dtype):
    """One Mosaic call, the blocks `chunk_blocks` chose fit the VMEM the
    call asks for, and XLA hands it both pools as they lie: no relayout
    copy, no temporary at all (a pool viewed as (pages, rows, heads x
    128) would be copied whole: its tiles hold the heads of one row)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    assert pa.paged_chunk_attention_path(
        dtype, q_heads, kv_heads, 128, page, chunk, n_pages,
        backend="tpu")[0] == "pallas"
    pool = sds((2 * n_pages, page, kv_heads, 128), dtype)
    compiled = jax.jit(pa.paged_chunk_attention).lower(
        sds((1, q_heads, chunk, 128), dtype), pool, pool,
        sds((n_pages,), "int32"), sds((chunk, n_pages * page), "int8")
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# The flash kernels under the chip's compiler.  Here and not in
# test_flash_pallas.py: one process holds the TPU's library, so every
# compile for a described chip lives in the one file that has the fixture.
@pytest.mark.parametrize("seq,dtype", [(2048, "bfloat16"),
                                       (8192, "bfloat16"),
                                       (15360, "bfloat16"),
                                       (7680, "float32")])
def test_flash_kernels_compile_for_v5e_at_the_blocks_chosen(one_chip, seq,
                                                            dtype):
    """``flash_blocks`` reckons VMEM from shapes; Mosaic has the last
    word.  The LM cells' sequence, a middle one, and the longest the
    kernels take in bf16 and in f32: forward, dk/dv and dq all compile."""
    from bigdl_tpu.ops import flash_attention_mod as fa
    x = jax.ShapeDtypeStruct((1, 2, seq, 128), jnp.dtype(dtype),
                             sharding=one_chip)
    bq, bk = fa.flash_blocks(seq, seq, 128, dtype)
    cfg = fa._Config(True, 128 ** -0.5, bq, bk, True)
    lse = jax.ShapeDtypeStruct((1, 2, seq), jnp.float32, sharding=one_chip)
    fwd = jax.jit(lambda q, k, v: fa._fwd_pallas(q, k, v, cfg)
                  ).lower(x, x, x).compile()
    bwd = jax.jit(lambda q, k, v, o, l, do: fa._bwd_pallas(
        q, k, v, o, l, do, cfg)).lower(x, x, x, x, lse, x).compile()
    assert fwd.as_text().count("tpu_custom_call") == 1
    assert bwd.as_text().count("tpu_custom_call") == 2
