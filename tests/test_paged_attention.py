"""The paged decode-attention kernel against the gathered-window route.

The kernel (``ops/paged_attention.py``) runs here in Pallas interpret
mode through its ``_INTERPRET`` hook; ``PagedKVCache.gather_window`` +
``attend_window`` is the reference.  Pinned:

  * kernel == reference over pool dtype, page size and ragged lengths
    (a dead slot, 1 key, a page less one, a page, a page and one, the
    whole context)
  * non-finite rows past a slot's length (the tail of its last page, a
    page it holds but has not reached, every free page) never leak
  * ``paged_attention_path`` picks the route from backend, pool dtype
    and row geometry, and says why
  * one ``DecodeEngine`` stream through the kernel emits the greedy
    tokens of the gather route, eviction and readmission included
  * the kernel compiles under Mosaic for a described v5e at the serve
    cell's widths (no chip needed)
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

H, D, CTX = 8, 128, 64


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(pa, "_INTERPRET", True)


def _lengths(page):
    # slot 0 is dead (no page, length 0); the rest are live
    return [0, 0, 1, page - 1, page, page + 1, CTX - 1]


def _case(dtype, page, poison=False, pages_per_block=2):
    """(kernel output, reference, live mask) for one pool: slot ``s``
    holds ``_lengths(page)[s]`` tokens and attends one more."""
    lens = _lengths(page)
    n_slots = len(lens)
    kv = PagedKVCache(["a"], n_heads=H, head_dim=D,
                      n_pages=n_slots * CTX // page + 2, page_size=page,
                      n_slots=n_slots, max_context=CTX, dtype=dtype)
    rng = np.random.default_rng(page)
    shape = (kv.n_pages, page, H, D)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    for s, n in enumerate(lens):
        if s:
            # slot 3 also holds a page it has not reached yet
            assert kv.alloc_for(s, n + 1 + (page if s == 3 else 0))
    if poison:
        for arr in (k, v):
            arr[kv._free] = np.nan                       # freed pages
            for s, n in enumerate(lens):
                for j, p in enumerate(kv.tables[s]):
                    if p >= 0:                           # rows past n
                        arr[p, max(n + 1 - j * page, 0):] = np.nan
    tables = jnp.asarray(kv.tables)
    lengths = jnp.asarray(np.asarray(lens, np.int32))
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    q = jnp.asarray(rng.standard_normal((n_slots, H, 1, D)), dtype)
    k_win, v_win = kv.gather_window({"k": k, "v": v}, tables)
    ref = pa.attend_window(q, k_win, v_win, lengths)[:, :, 0]
    out = pa.paged_attention(q[:, :, 0], k, v, tables, lengths,
                             pages_per_block=pages_per_block)
    return (np.asarray(out, np.float32), np.asarray(ref, np.float32))


_cached_case = functools.lru_cache(maxsize=None)(_case)


@pytest.mark.parametrize("slot", range(7))
@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 1.6e-2)])
def test_kernel_matches_gathered_window(interpret, dtype, tol, page, slot):
    out, ref = _cached_case(dtype, page)
    assert np.isfinite(out).all()
    if slot == 0:
        assert not out[0].any()          # a dead slot reads zeros
    else:
        assert np.abs(out[slot] - ref[slot]).max() <= tol, \
            f"length {_lengths(page)[slot]}"


@pytest.mark.parametrize("pages_per_block", [1, 3, 8])
def test_kernel_block_size_does_not_change_the_result(interpret,
                                                      pages_per_block):
    out, ref = _case("float32", 8, pages_per_block=pages_per_block)
    assert np.abs(out[1:] - ref[1:]).max() <= 2e-6


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 1.6e-2)])
def test_non_finite_rows_past_the_length_do_not_leak(interpret, dtype,
                                                     tol):
    """NaN in the masked tail of a slot's last page, in a page the slot
    holds but has not reached, and in every free page: 0 * NaN would
    be NaN, so both routes scrub the masked V rows."""
    out, ref = _case(dtype, 8, poison=True)
    clean, _ = _cached_case(dtype, 8)
    assert np.isfinite(ref).all() and np.isfinite(out).all()
    assert np.abs(out[1:] - ref[1:]).max() <= tol
    assert np.array_equal(out, clean)    # the poison changed nothing


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
])
def test_paged_attention_path_says_which_route_and_why(kw, route, why):
    got, reason = paged_attention_path(**kw)
    assert got == route and why in reason, (got, reason)


def test_cache_routes_by_what_it_holds(interpret):
    """``PagedKVCache.attention_path`` is ``paged_attention_path`` over
    the pool it built: the hook stands in for the TPU backend, an int8
    pool and the tiny preset's head_dim 64 stay on the window."""
    mk = lambda **kw: PagedKVCache(["a"], n_pages=4, page_size=8,
                                   n_slots=2, max_context=16, **kw)
    assert mk(n_heads=H, head_dim=D).attention_path()[0] == "pallas"
    assert mk(n_heads=H, head_dim=D, int8=True).attention_path()[0] \
        == "gather"
    assert mk(n_heads=2, head_dim=64).attention_path()[0] == "gather"
    assert mk(n_heads=H, head_dim=D).attention_path(backend="tpu") \
        == paged_attention_path("float32", H, D, backend="tpu")


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


@pytest.mark.parametrize("dtype,heads", [("bfloat16", 16), ("bfloat16", 8),
                                         ("float32", 8)])
def test_kernel_compiles_for_v5e_without_a_pool_copy(one_chip, dtype,
                                                     heads):
    """32 slots x 512 tokens, 1,024 pages of 16 rows of ``heads`` x 128
    (the serve cell's pool, and chip_smoke.py's 8 heads): Mosaic takes
    the kernel as written, and XLA hands it the pool without a relayout
    copy (no temporaries at all)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    pool = sds((1024, 16, heads, 128), dtype)
    compiled = jax.jit(pa.paged_attention).lower(
        sds((32, heads, 128), dtype), pool, pool,
        sds((32, 32), "int32"), sds((32,), "int32")).compile()
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
