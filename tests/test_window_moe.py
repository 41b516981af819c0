"""Layers of two kinds in one model and one cache manager: sliding-window
attention with rope beside global attention without, 7 query heads a KV
head, routed ReGLU experts whose router reads the block's input, against
the plain reference `benchmarks/reference/window_moe_ref.py` on seeded
weights at a small size: hidden 32, 7 query / 1 KV head x 8, eight layers
in the pattern [global, window, window, window] x 2, window 8, pages of 4,
chunks of 8 (a ring of 8/4 + 8/4 + 1 = 5 pages), 8 experts top-2,
vocabulary 128.  Sequences of 72 tokens: the ring wraps three times.
Logits are compared, never tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import window_moe_ref as ref
from benchmarks.runners import window_moe_program as prog
from bigdl_tpu.nn import moe
from bigdl_tpu.nn.module import Ctx
from bigdl_tpu.ops.sparse_attention import ring_positions
from bigdl_tpu.serving import DecodeEngine, ModelRegistry
from bigdl_tpu.serving.kvcache import PagedKVCache, PagePoolError

TOY = dict(vocab_size=128, hidden_size=32, num_attention_heads=7,
           num_key_value_heads=1, head_dim=8, num_hidden_layers=8,
           moe_num_primary_experts=8, moe_num_active_primary_experts=2,
           moe_ffn_hidden_size=16, moe_primary_router_apply_softmax=True,
           norm_topk_prob=True, tie_word_embeddings=False, rms_norm_eps=1e-6,
           rope_theta=1.5e6, rope_scaling=None, max_position_embeddings=128,
           sliding_window_size=8, sliding_window_layout=[0, 1, 1, 1] * 2,
           rope_layout=[0, 1, 1, 1] * 2,
           # at hidden 32 a matrix of N(0, 0.15) carries a unit vector to
           # about unit size, as N(0, 0.02) does at the published 2,560
           initializer_range=0.15, init_qk_gain=1.5, init_embed_gain=6.0,
           init_router_gain=2.5,
           activation_dtype="float32", param_dtype="float32")
KEY = jax.random.PRNGKey(5)
SEQ = np.random.default_rng(0).integers(0, 128, 72).astype(np.int32)
N_PROMPT = 45            # five chunks of 8 and one of 5
PAGE, CHUNK, RING = 4, 8, 5
# float32 against float32 at "highest": rounding in another order only
F32_TOL = 2e-5
# what a wrong layer moves the logits (of standard deviation 0.84) by at
# the least (readings below)
CONTROL_MOVES = 0.5


def build(cfg=TOY):
    model = prog.build_model(cfg)
    return model, prog.program_tree(cfg, KEY, model)


def reference_logits(cfg=TOY, variant=ref.SOUND, seq=SEQ):
    w = ref.make_weights(cfg, KEY)
    return np.asarray(ref.logits(w, jnp.asarray(seq), cfg, variant))


def cache_for(model, n_pages=None, page_size=PAGE, chunk=CHUNK,
              max_context=96, n_slots=2, dtype=jnp.float32):
    cfg = model.cfg
    return PagedKVCache(
        [b.attn.name for b in model.blocks], n_heads=cfg.n_kv_heads,
        q_heads=cfg.n_heads, head_dim=cfg.head_dim, n_pages=n_pages,
        page_size=page_size, n_slots=n_slots, max_context=max_context,
        dtype=dtype, windows=list(cfg.windows), ring_slack=chunk)


def through_cache(model, params, kv, slot, pool=None, seq=SEQ,
                  n_prompt=N_PROMPT, chunk=CHUNK):
    """Chunked prefill of seq[:n_prompt] then one-token decode of the rest
    through `kv`'s pages of `slot`, allocating as the engine does (the
    prompt at once, then a row a step): the logits after every position
    from the prompt's last on, and after each chunk's last token."""
    pool = kv.init_pool() if pool is None else pool
    assert kv.alloc_for(slot, n_prompt)
    table = kv.pack([jnp.asarray(k.tables[slot]) for k in kv.kinds])
    out = {}
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = seq[start:start + n]

        def kv_io(name, q, k, v):
            tab = kv.table_of(table, name)
            pool[name] = kv.write_chunk(
                pool[name], kv.chunk_pages(tab, jnp.int32(start), chunk,
                                           name), k, v)
            return kv.attend_chunk(pool[name], tab, jnp.int32(start), q,
                                   layer=name)
        out[start + n - 1] = model.prefill_chunk(
            params, jnp.asarray(toks), jnp.int32(start), jnp.int32(n), kv_io)
    lengths = np.zeros(kv.n_slots, np.int32)
    lengths[slot] = n_prompt
    for t in range(n_prompt, len(seq)):
        assert kv.alloc_for(slot, t + 1)
        tokens = np.zeros(kv.n_slots, np.int32)
        tokens[slot] = seq[t]
        tabs = []
        for k in kv.kinds:
            tabs.append(np.full_like(k.tables, -1))     # the others dead
            tabs[-1][slot] = k.tables[slot]
        tabs = kv.pack([jnp.asarray(t) for t in tabs])
        lens = jnp.asarray(lengths)

        def kv_io(name, q, k, v):
            tab = kv.table_of(tabs, name)
            pool[name] = kv.write_token(pool[name], tab, lens, k, v,
                                        layer=name)
            return kv.attend(pool[name], tab, lens, q, layer=name)
        out[t] = model.decode_tokens(params, jnp.asarray(tokens), lens,
                                     kv_io)[slot]
        lengths[slot] += 1
    return {t: np.asarray(v, np.float32) for t, v in out.items()}


def worst(got, want):
    return max(float(np.abs(v - want[t]).max()) for t, v in got.items())


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        model, params = build()
        yield model, params, reference_logits()


# --------------------------------------------------------------------- #
# the three routes against the reference
# --------------------------------------------------------------------- #
def test_the_model_is_what_the_configuration_says(f32):
    model, params, _ = f32
    cfg = model.cfg
    assert [b.attn.window for b in model.blocks] == [0, 8, 8, 8] * 2
    assert [b.attn.rope for b in model.blocks] == [False, True, True,
                                                   True] * 2
    assert all(b.router_pre_attention and b.mlp.activation == "relu"
               for b in model.blocks)
    assert cfg.n_heads // cfg.n_kv_heads == 7
    assert sorted(params[model.blocks[0].attn.name]) == ["wk", "wo", "wq",
                                                        "wv"]


def test_full_forward_is_the_reference(f32):
    model, params, want = f32
    got = model.apply(params, jnp.asarray(SEQ)[None], Ctx(training=False))[0]
    assert float(np.abs(np.asarray(got) - want).max()) < F32_TOL


def test_static_cache_prefill_then_decode_is_the_reference(f32):
    model, params, want = f32
    cache = model.init_cache(1, cache_len=len(SEQ))
    lg, cache = model.apply_with_cache(
        params, jnp.asarray(SEQ[:N_PROMPT])[None], cache, 0)
    assert float(np.abs(np.asarray(lg[0]) - want[:N_PROMPT]).max()) < F32_TOL
    for t in range(N_PROMPT, len(SEQ)):
        lg, cache = model.apply_with_cache(
            params, jnp.asarray(SEQ[t:t + 1])[None], cache, t)
        assert float(np.abs(np.asarray(lg[0, 0]) - want[t]).max()) < F32_TOL


def test_chunked_prefill_then_decode_through_a_ring_is_the_reference(f32):
    model, params, want = f32
    kv = cache_for(model)
    glob, ring = kv.kinds
    assert (glob.name, glob.width, len(glob.layers)) == ("global", 24, 2)
    assert (ring.name, ring.width, ring.window, len(ring.layers)) \
        == ("window", RING, 8, 6)
    assert kv.attention_path()[0] == "gather"
    assert kv.alloc_for(0, 3)                # the slot's pages start at 1
    got = through_cache(model, params, kv, 1)
    assert sorted(got) == [7, 15, 23, 31, 39] + list(range(44, 72))
    assert worst(got, want) < F32_TOL
    # 72 rows are 18 pages through a ring of 5: it wrapped three times,
    # every page past the fifth recycled in place
    assert ring.covered[1] == 18 and len(ring.owned[1]) == RING
    assert len(glob.owned[1]) == 18
    kv.check_invariants()


@pytest.mark.parametrize("control", ["no_window", "rope_on_global",
                                     "router_reads_u", "silu"])
def test_a_wrong_layer_moves_the_logits(f32, control):
    """The four controls of the model itself (the cell's other two are a
    precision and the cache's stale rows), each far outside the tolerance
    the three routes are held to.  Readings: 3.04, 2.31, 2.16, 1.03; the
    stale rows below 1.44."""
    _, _, want = f32
    with jax.default_matmul_precision("highest"):
        bad = reference_logits(variant=ref.controls(PAGE, RING)[control])
    assert float(np.abs(bad - want).max()) > CONTROL_MOVES > 100 * F32_TOL


def test_stale_rows_attended_move_the_logits(f32):
    """The cache's own control: where a query also sees what a recycled
    page still holds past its own row, the logits move, and only from the
    position on at which the ring first wraps."""
    _, _, want = f32
    with jax.default_matmul_precision("highest"):
        bad = reference_logits(variant=ref.controls(PAGE, RING)["stale_rows"])
    first = RING * PAGE                     # the first recycled row
    assert float(np.abs(bad[:first] - want[:first]).max()) < F32_TOL
    assert float(np.abs(bad[first:] - want[first:]).max()) > CONTROL_MOVES


def test_gates_over_the_chosen_logits_are_the_renormalised_softmax():
    """`RoutedExperts.route` takes the top-k of the softmax over all the
    experts and divides by their sum; the model's gates are the softmax
    over the k largest logits: the same numbers."""
    layer = moe.RoutedExperts(32, 16, 64, 6, name="moe", activation="relu")
    p = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 32)) * 3.0
    with jax.default_matmul_precision("highest"):
        idx, gate = layer.route(p, x)
        z = x @ p["moe"]["router"]
    top, want_idx = jax.lax.top_k(z, 6)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert float(np.abs(np.asarray(gate)
                        - np.asarray(jax.nn.softmax(top, -1))).max()) < 1e-6
    with pytest.raises(ValueError, match="activation"):
        moe.RoutedExperts(32, 16, 8, 2, activation="gelu")


def test_the_router_reads_the_blocks_input_and_the_gate_is_relu():
    """One block by hand: the router's logits come from norm1(x), the
    experts' input from norm2(x + attention), and the gate's half of an
    expert goes through ReLU."""
    with jax.default_matmul_precision("highest"):
        model, params = build(dict(TOY, num_hidden_layers=1,
                                   sliding_window_layout=[0],
                                   rope_layout=[0]))
        blk = model.blocks[0]
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 32))
        ctx = Ctx(training=False)
        got = np.asarray(blk.apply(params, x, ctx))[0]
        n1 = blk.norm1.apply(params, x, ctx)
        h = x + blk.attn.apply(params, n1, ctx)
        u = np.asarray(blk.norm2.apply(params, h, ctx))[0]
        idx, gate = blk.mlp.route(params, n1[0])
        w = {k: np.asarray(v, np.float64)
             for k, v in params[blk.mlp.name].items()}
    want = np.asarray(h)[0].astype(np.float64)
    for t in range(6):
        for e, c in zip(np.asarray(idx)[t], np.asarray(gate)[t]):
            want[t] += c * ((np.maximum(u[t] @ w["w1"][e], 0.0)
                             * (u[t] @ w["w3"][e])) @ w["w2"][e])
    assert np.abs(got - want).max() < F32_TOL


# --------------------------------------------------------------------- #
# the chunk kernel with the window in its mask
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", 0.05)])
def test_chunk_kernel_with_the_window_in_its_mask_is_the_window_route(
        monkeypatch, dtype, tol):
    """`through_cache` with every chunk through the Pallas chunk kernel
    (interpreted) against the same through the gathered window: heads of
    128, pages of 16, chunks of 32, window 64, so a ring of 4 + 2 + 1 = 7
    pages filled up to the kernel's 8; 7 query heads a KV head; a prompt
    of 200 (the ring wraps), the last chunk short."""
    from bigdl_tpu.ops import paged_attention_mod as pa
    wide = dict(TOY, head_dim=128, num_hidden_layers=4,
                sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
                sliding_window_size=64, max_position_embeddings=256,
                activation_dtype=dtype, param_dtype=dtype)
    seq = np.random.default_rng(1).integers(0, 128, 208).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        model, params = build(wide)
        kw = dict(page_size=16, chunk=32, max_context=256,
                  dtype=jnp.dtype(dtype))
        monkeypatch.setattr(pa, "_INTERPRET", True)
        kv = cache_for(model, **kw)
        ring = kv.kinds[1]
        assert ring.width == 7 and kv.chunk_table_width(ring) == 8
        names = [kv.kinds[0].layers[0], ring.layers[0]]
        assert [kv.chunk_attention_path(32, layer=n)[0]
                for n in names] == ["pallas", "pallas"]
        kernel = through_cache(model, params, kv, 0, seq=seq, n_prompt=200,
                               chunk=32)
        monkeypatch.setattr(pa, "_INTERPRET", False)
        kv = cache_for(model, **kw)
        assert kv.chunk_attention_path(32, layer=names[1])[0] == "window"
        window = through_cache(model, params, kv, 0, seq=seq, n_prompt=200,
                               chunk=32)
        assert sorted(kernel) == [31, 63, 95, 127, 159, 191] \
            + list(range(199, 208))
        assert worst(kernel, window) < tol
        if dtype == "float32":
            want = reference_logits(wide, seq=np.concatenate(
                [seq, np.zeros(48, np.int32)]))
            assert worst(kernel, want) < F32_TOL


# --------------------------------------------------------------------- #
# the cache manager
# --------------------------------------------------------------------- #
def test_ring_positions_follow_the_top_page():
    # a ring of 3 pages of 2 rows whose newest row lies in page 4: column
    # 1 holds page 4, column 0 page 3, column 2 page 2
    pos = np.asarray(ring_positions(jnp.asarray([4, 1, 0]), 3, 2))
    assert pos[0].tolist() == [6, 7, 8, 9, 4, 5]
    # before the ring is whole the columns not yet held read negative
    assert pos[1].tolist() == [0, 1, 2, 3, -2, -1]
    assert pos[2].tolist() == [0, 1, -4, -3, -2, -1]


def test_allocator_invariants_per_kind_through_churn():
    model, _ = build()
    kv = cache_for(model, n_pages={"global": 30, "window": 12}, n_slots=3)
    glob, ring = kv.kinds
    rec = kv.recorder
    assert kv.init_pool()[glob.layers[0]]["k"].shape == (30, 4, 1, 8)
    assert kv.init_pool()[ring.layers[0]]["k"].shape == (12, 4, 1, 8)
    assert kv.alloc_for(0, 45)               # 12 pages: 12 global, 5 ring
    assert (len(glob.owned[0]), len(ring.owned[0])) == (12, RING)
    assert ring.covered[0] == 12
    assert kv.alloc_for(1, 9)                # 3 and 3
    assert kv.can_fit(16) and not kv.can_fit(20)   # the ring's 4 are left
    assert not kv.alloc_for(2, 70)           # 18 global > 15 free: nothing
    assert (len(glob.owned[2]), len(ring.owned[2])) == (0, 0)
    kv.check_invariants()
    assert kv.alloc_for(2, 16)               # 4 and 4: the ring pool is full
    assert not kv.alloc_for(1, 13)           # slot 1's ring cannot grow
    assert len(glob.owned[1]) == 3           # ... so nothing grew
    assert kv.alloc_for(0, 72)               # a whole ring grows no more
    assert (len(glob.owned[0]), len(ring.owned[0])) == (18, RING)
    kv.check_invariants()
    assert kv.free_slot(2) == 8 and kv.free_slot(0, evict=True) == 23
    assert kv.alloc_for(1, 40)
    kv.check_invariants()
    assert kv.free_slot(1) == 15 and kv.pages_in_use() == 0
    assert kv.fits_pool(96) and kv.fill() == 0.0
    with pytest.raises(PagePoolError):
        ring.owned[1] = [0]                  # the ledger corrupted
        kv.check_invariants()
    with pytest.raises(ValueError, match="kind"):
        cache_for(model, n_pages=40)
    with pytest.raises(ValueError, match="float pool"):
        PagedKVCache(["a", "b"], n_heads=1, head_dim=8, int8=True,
                     windows=[0, 8])
    del rec


def test_recycling_is_counted_and_spanned():
    from bigdl_tpu.observability import Recorder
    model, _ = build()
    rec = Recorder(annotate=False)
    cfg = model.cfg
    kv = PagedKVCache([b.attn.name for b in model.blocks], n_heads=1,
                      q_heads=7, head_dim=8, page_size=PAGE, n_slots=2,
                      max_context=96, windows=list(cfg.windows),
                      ring_slack=CHUNK, recorder=rec)
    assert kv.alloc_for(0, 20)               # the ring whole, none recycled
    assert rec.counter_value("kv/pages_recycled") == 0
    assert kv.alloc_for(0, 21) and kv.alloc_for(0, 24)   # page 6, once
    assert rec.counter_value("kv/pages_recycled") == 1
    assert kv.alloc_for(1, 45)               # 12 pages at once: 7 recycled
    assert rec.counter_value("kv/pages_recycled") == 8
    assert rec._span_counts["kv.recycle"] == 2
    assert rec.gauge_value("kv/pages_in_use_window") == 10
    assert rec.gauge_value("kv/pages_in_use_global") == 6 + 12
    assert rec.gauge_value("kv/pages_in_use") == 28


def test_evict_and_replay_through_nan_poisoned_recycled_pages(f32):
    """A slot is evicted mid-reply and served again (re-prefill, then its
    tokens replayed) in pages that other slots have dirtied: every page
    on the free lists, of both kinds, is filled with NaN first.  The
    logits are those of an untouched cache: no stale and no poisoned row
    is attended (masked K, zeroed V), in the ring as in the table."""
    model, params, want = f32
    kv = cache_for(model, n_pages={"global": 40, "window": 11})
    pool = kv.init_pool()
    assert kv.alloc_for(0, 30)
    first = through_cache(model, params, kv, 1, pool, seq=SEQ[:60])
    assert worst(first, want) < F32_TOL
    kv.free_slot(1, evict=True)
    kv.free_slot(0)
    for k in kv.kinds:                       # poison what is free
        for name in k.layers:
            for leaf in ("k", "v"):
                arr = np.asarray(pool[name][leaf]).copy()
                arr[k.free] = np.nan
                pool[name][leaf] = jnp.asarray(arr)
    assert kv.alloc_for(0, 10)               # other pages than before
    again = through_cache(model, params, kv, 1, pool)
    assert all(np.isfinite(v).all() for v in again.values())
    assert worst(again, want) < F32_TOL
    assert worst({t: again[t] for t in first}, first) < 1e-6
    kv.check_invariants()


# --------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------- #
def engine_for(model, **kw):
    reg = ModelRegistry()
    reg.register("lm", model)
    kw = dict(dict(slots=3, page_size=PAGE, max_context=96, max_prompt=64,
                   prefill_chunk=CHUNK, max_new_tokens=12), **kw)
    return DecodeEngine(reg, "lm", **kw).warmup()


def served_gaps(cfg, outs):
    """Every served token's gap under the reference's best, teacher-forced
    on the sequence as served."""
    w = ref.make_weights(cfg, KEY)
    gaps = []
    for out, n_prompt in outs:
        pad = np.zeros(-len(out) % 8, np.int32)
        lg = np.asarray(ref.logits(
            w, jnp.asarray(np.concatenate([out, pad])), cfg))
        rows = lg[n_prompt - 1:len(out) - 1]
        gaps.append(rows.max(-1) - rows[np.arange(len(rows)), out[n_prompt:]])
    return np.concatenate(gaps)


def test_engine_serves_short_and_long_prompts_mixed(f32):
    """ModelRegistry -> DecodeEngine.warmup() -> stream(): prompts under
    the chunk and past the window in one queue, every one in chunks
    between decode steps; one decode program, one chunk program, nothing
    compiled after the warm-up; every served token is the reference's
    first choice."""
    model, params, _ = f32
    with jax.default_matmul_precision("highest"):
        model.set_params(params, {})
        eng = engine_for(model)
        assert eng.chunked and list(eng.ladder) == []
        rng = np.random.default_rng(3)
        lens = (3, 60, 11, 45, 7, 30)
        prompts = [rng.integers(0, 128, n).astype(np.int32) for n in lens]
        streams = [eng.stream("lm", p, max_new_tokens=12) for p in prompts]
        outs = [np.asarray(s.result(300)) for s in streams]
        st, rec = eng.stats(), eng.recorder
        eng.kv.check_invariants()
        eng.shutdown()
        gaps = served_gaps(TOY, list(zip(outs, lens)))
    assert st["recompiles"] == 0 and st["warmup_compiles"] == 2
    assert st["attn_route"] == "gather" and st["chunk_attn_route"] == "window"
    assert st["kv_kinds"] == {
        "global": {"layers": 2, "window": 0, "pages_per_slot": 24,
                   "n_pages": 72},
        "window": {"layers": 6, "window": 8, "pages_per_slot": RING,
                   "n_pages": 15}}
    assert st["prefills"] == 6
    assert st["prefill_chunks"] == sum(-(-n // CHUNK) for n in lens)
    assert st["evictions"] == 0 and eng.kv.pages_in_use() == 0
    assert gaps.max() < F32_TOL, gaps
    # what the layers counted: rows by kind, pairs, recycled pages
    live = rec.counter_value("attn/rows_live")
    assert rec.counter_value("attn/rows_attended_global") == live * 2 / 8
    assert rec.counter_value("attn/rows_attended_window") < live * 6 / 8
    assert rec.counter_value("moe/pairs") == st["tokens"] * 2 * 8
    assert rec.counter_value("kv/pages_recycled") > 0
    assert rec.span_value("kv.recycle") > 0


def test_engine_evicts_and_replays_with_a_ring(f32):
    """Pools too small for three long replies at once: slots are evicted,
    re-prefilled and replayed, rings and all, and every served token is
    still the reference's first choice."""
    model, params, _ = f32
    with jax.default_matmul_precision("highest"):
        model.set_params(params, {})
        eng = engine_for(model, pool_pages={"global": 26, "window": 13},
                         max_new_tokens=30)
        rng = np.random.default_rng(4)
        lens = (20, 33, 26)
        prompts = [rng.integers(0, 128, n).astype(np.int32) for n in lens]
        streams = [eng.stream("lm", p, max_new_tokens=30) for p in prompts]
        outs = [np.asarray(s.result(600)) for s in streams]
        st = eng.stats()
        eng.kv.check_invariants()
        eng.shutdown()
        gaps = served_gaps(TOY, list(zip(outs, lens)))
    assert st["evictions"] > 0 and st["readmissions"] > 0
    assert st["recompiles"] == 0
    assert gaps.max() < F32_TOL, gaps


def test_engine_stream_through_the_decode_kernel_is_the_gather_routes(
        monkeypatch):
    """A model of two kinds of layer at heads of 128 (pages of 16, window
    64, a ring of 4 + 2 + 1 pages, 7 query heads a KV head), prompts
    under a chunk and past the ring in one queue, pools small enough to
    evict: every decode step through the Pallas decode kernel
    (interpreted; `_window_attend` picks it) streams the gather route's
    tokens, token for token, and the engine says which route it took."""
    from bigdl_tpu.ops import paged_attention_mod as pa
    wide = dict(TOY, head_dim=128, num_hidden_layers=4,
                sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
                sliding_window_size=64, max_position_embeddings=256)
    rng = np.random.default_rng(7)
    lens = (150, 9, 70, 200)
    prompts = [rng.integers(0, 128, n).astype(np.int32) for n in lens]

    def serve():
        eng = engine_for(model, slots=3, page_size=16, max_context=256,
                         max_prompt=224, prefill_chunk=32, max_new_tokens=24,
                         pool_pages={"global": 22, "window": 14})
        try:
            streams = [eng.stream("lm", p, max_new_tokens=24)
                       for p in prompts]
            outs = [np.asarray(s.result(600)) for s in streams]
            eng.kv.check_invariants()
            return outs, eng.stats(), eng.recorder
        finally:
            eng.shutdown()

    with jax.default_matmul_precision("highest"):
        model, params = build(wide)
        model.set_params(params, {})
        want, st, rec = serve()
        assert st["attn_route"] == "gather"
        assert rec.gauge_value("decode/attn_route", -1.0) == 0.0
        monkeypatch.setattr(pa, "_INTERPRET", True)
        pa._window_attend.clear_cache()   # it reads the hook as it traces
        try:
            got, st, rec = serve()
        finally:
            pa._window_attend.clear_cache()
    assert st["attn_route"] == "pallas"
    assert rec.gauge_value("decode/attn_route", -1.0) == 1.0
    assert st["kv_kinds"]["window"]["pages_per_slot"] == 7
    assert rec.counter_value("kv/pages_recycled") > 0
    assert st["evictions"] > 0 and st["recompiles"] == 0
    for a, b, n in zip(want, got, lens):
        assert len(a) == n + 24 and np.array_equal(a, b)


# --------------------------------------------------------------------- #
# a model of one kind of layer is what it was
# --------------------------------------------------------------------- #
def test_a_config_of_one_kind_builds_what_it_built():
    """No window and rope everywhere, said or left out: the same
    parameter tree, the same forward and cached programs (as lowered
    text), one kind of table under the old names."""
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                n_layers=2, d_ff=16, moe_experts=4, moe_top_k=2,
                moe_capacity_factor=None, max_len=32)
    plain = TransformerLM(TransformerConfig(**base), name="lm")
    said = TransformerLM(TransformerConfig(
        **base, windows=[0, 0], rope_layers=[True, True],
        moe_activation="silu", moe_router_pre_attention=False), name="lm")
    shapes = lambda m: jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype),
        jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    assert shapes(plain) == shapes(said)
    assert sorted(shapes(plain)["lm.block0.moe"]) == ["router", "w1", "w2",
                                                     "w3"]
    params = plain.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 8), jnp.int32)

    def texts(m):
        fwd = jax.jit(lambda p, t: m.apply(p, t, Ctx(training=False)))
        cached = jax.jit(lambda p, t: m.apply_with_cache(
            p, t, m.init_cache(1, cache_len=16), 0)[0])
        return [f.lower(params, toks).as_text() for f in (fwd, cached)]
    assert texts(plain) == texts(said)
    kv = PagedKVCache(["a", "b"], n_heads=2, head_dim=8, n_pages=6,
                      page_size=4, n_slots=2, max_context=16)
    assert [k.name for k in kv.kinds] == ["global"] and not kv.windowed
    assert kv.tables.shape == (2, 4) and kv.n_pages == 6
    assert kv.pack([7]) == 7 and kv.table_of(7, "a") == 7
    with pytest.raises(ValueError, match="one size"):
        PagedKVCache(["a", "b"], n_heads=2, head_dim=8, page_size=4,
                     n_slots=2, max_context=16, windows=[4, 8])
