"""Grouped KV heads + the learned sparse-attention indexer + routed experts
(no drop), against the plain reference `benchmarks/reference/
sparse_moe_ref.py`, on seeded random weights at a small size: hidden 64,
4 query / 2 KV heads x 16, indexer 2 heads x 8 with top-k 8, 8 experts
top-2, 2 layers, vocabulary 128; contexts on both sides of top-k.  Logits
are compared, never tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sparse_moe_ref as ref
from benchmarks.runners import sparse_moe_program as prog
from bigdl_tpu.nn import moe
from bigdl_tpu.nn.module import Ctx
from bigdl_tpu.serving import DecodeEngine, ModelRegistry
from bigdl_tpu.serving.kvcache import PagedKVCache

TOY = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
           num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
           norm_topk_prob=True, tie_word_embeddings=False, rms_norm_eps=1e-6,
           rope_theta=1e7, max_position_embeddings=256,
           initializer_range=0.02, init_qk_norm_gain=1.4,
           sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                          indexer_num_kv_heads=1, topk=8),
           activation_dtype="float32", param_dtype="float32")
KEY = jax.random.PRNGKey(3)
SEQ = np.random.default_rng(0).integers(0, 128, 32).astype(np.int32)
N_PROMPT = 19            # five chunks of 4, the last of 3: past top-k 8
# float32 against float32 at "highest": rounding in another order only
F32_TOL = 1e-5
# bfloat16 keeps 8 bits: 4e-3 a rounding, through two layers of some
# twenty roundings each onto logits of size 0.6.  Readings over four
# sequences: 0.004, 0.004, 0.016, and 0.118 on one where a selection or
# an expert choice falls the other way at a near-tie (top-k is 8, so one
# swapped key is an eighth of a head's keys); SEQ is the first and reads
# 0.004.  A wrong layer reads 0.38-0.57 (the dense route) and 0.06-0.21
# (gates not renormalised; 0.148 on SEQ).
BF16_TOL = 0.03


def build(cfg=TOY, dtype="float32"):
    cfg = dict(cfg, activation_dtype=dtype, param_dtype=dtype)
    model = prog.build_model(cfg)
    return cfg, model, prog.program_tree(cfg, KEY, model)


def reference_logits(cfg, variant=ref.SOUND, seq=SEQ):
    w = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.make_weights(cfg, KEY, jnp.dtype(cfg["param_dtype"])))
    return np.asarray(ref.logits(w, jnp.asarray(seq), cfg, variant))


def cache_for(model, pages=16, page_size=4, dtype=jnp.float32,
              max_context=32):
    cfg = model.cfg
    return PagedKVCache(
        [b.attn.name for b in model.blocks], n_heads=cfg.n_kv_heads,
        q_heads=cfg.n_heads, head_dim=cfg.head_dim, n_pages=pages,
        page_size=page_size, n_slots=2, max_context=max_context, dtype=dtype,
        index_dim=cfg.index_dim if cfg.index_heads else 0,
        index_top_k=cfg.index_top_k if cfg.index_heads else 0)


def through_cache(model, params, kv, slot, chunk=4, SEQ=SEQ,
                  N_PROMPT=N_PROMPT):
    """Chunked prefill of SEQ[:N_PROMPT] then one-token decode of the rest
    through `kv`'s pages of `slot`: the logits after every position from
    the prompt's last on, and after each chunk's last token."""
    pool = kv.init_pool()
    table = jnp.asarray(kv.tables[slot])
    page = kv.page_size
    out = {}
    for start in range(0, N_PROMPT, chunk):
        n = min(chunk, N_PROMPT - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = SEQ[start:start + n]
        pages = table[start // page:(start + chunk) // page]

        def kv_io(name, q, k, v, index=None):
            qi, ki, w = index or (None, None, None)
            pool[name] = kv.write_chunk(pool[name], pages, k, v, ki)
            return kv.attend_chunk(pool[name], table, jnp.int32(start), q,
                                   index and (qi, w))
        out[start + n - 1] = model.prefill_chunk(
            params, jnp.asarray(toks), jnp.int32(start), jnp.int32(n), kv_io)
    lengths = np.zeros(2, np.int32)
    lengths[slot] = N_PROMPT
    tables = kv.tables.copy()
    tables[1 - slot] = -1                    # the other slot is dead
    for t in range(N_PROMPT, len(SEQ)):
        tokens = np.zeros(2, np.int32)
        tokens[slot] = SEQ[t]
        tabs, lens = jnp.asarray(tables), jnp.asarray(lengths)

        def kv_io(name, q, k, v, index=None):
            if index is None:
                pool[name] = kv.write_token(pool[name], tabs, lens, k, v)
                return kv.attend(pool[name], tabs, lens, q)
            qi, ki, w = index
            pool[name] = kv.write_token(pool[name], tabs, lens, k, v,
                                        ki[:, 0])
            return kv.attend(pool[name], tabs, lens, q, (qi[:, 0], w[:, 0]))
        out[t] = model.decode_tokens(params, jnp.asarray(tokens), lens,
                                     kv_io)[slot]
        lengths[slot] += 1
    return {t: np.asarray(v, np.float32) for t, v in out.items()}


def worst(got, want):
    return max(float(np.abs(v - want[t]).max()) for t, v in got.items())


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        cfg, model, params = build()
        yield cfg, model, params, reference_logits(cfg)


def test_full_forward_is_the_reference(f32):
    cfg, model, params, want = f32
    got = model.apply(params, jnp.asarray(SEQ)[None], Ctx(training=False))[0]
    assert float(np.abs(np.asarray(got) - want).max()) < F32_TOL
    # and the selection does something at this size: the dense route
    # reads another model
    dense = reference_logits(cfg, ref.Variant(select="dense"))
    assert float(np.abs(dense - want).max()) > 0.05


def test_static_cache_prefill_then_decode_is_the_reference(f32):
    cfg, model, params, want = f32
    cache = model.init_cache(1, cache_len=len(SEQ))
    assert cache[model.blocks[0].attn.name]["k"].shape == (1, 2, 32, 16)
    lg, cache = model.apply_with_cache(
        params, jnp.asarray(SEQ[:N_PROMPT])[None], cache, 0)
    assert float(np.abs(np.asarray(lg[0]) - want[:N_PROMPT]).max()) < F32_TOL
    for t in range(N_PROMPT, len(SEQ)):
        lg, cache = model.apply_with_cache(
            params, jnp.asarray(SEQ[t:t + 1])[None], cache, t)
        assert float(np.abs(np.asarray(lg[0, 0]) - want[t]).max()) < F32_TOL


def test_chunked_prefill_then_decode_is_the_reference(f32):
    cfg, model, params, want = f32
    kv = cache_for(model)
    assert kv.attention_path()[0] == "sparse"
    kv.alloc_for(0, len(SEQ))
    got = through_cache(model, params, kv, 0)
    assert sorted(got) == [3, 7, 11, 15] + list(range(18, 32))
    assert worst(got, want) < F32_TOL


def test_chunked_prefill_then_decode_in_bfloat16():
    cfg, model, params = build(dtype="bfloat16")
    want = reference_logits(cfg)           # float32, on the bf16 weights
    kv = cache_for(model, dtype=jnp.bfloat16)
    kv.alloc_for(1, len(SEQ))
    got = through_cache(model, params, kv, 1)
    assert worst(got, want) < BF16_TOL
    # a wrong layer is far outside it
    for variant in (ref.Variant(select="dense"), ref.Variant(renorm=False)):
        bad = reference_logits(cfg, variant)
        assert float(np.abs(bad[N_PROMPT:] - want[N_PROMPT:]).max()) \
            > 2 * BF16_TOL


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_chunked_prefill_through_the_chunk_kernel_is_the_reference(
        monkeypatch, dtype, tol):
    """`through_cache` once more with the chunk taking the Pallas kernel
    (interpreted): heads of 128, pages of 16, chunks of 32 over a prompt
    of 70 (the last chunk short, the table's last pages not held), then
    one-token decode; the selection reaches the kernel as its mask."""
    from bigdl_tpu.ops import paged_attention_mod as pa
    monkeypatch.setattr(pa, "_INTERPRET", True)
    wide = dict(TOY, head_dim=128)
    seq = np.random.default_rng(1).integers(0, 128, 96).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cfg, model, params = build(wide, dtype=dtype)
        want = reference_logits(cfg, seq=seq)
        kv = cache_for(model, pages=20, page_size=16, max_context=128,
                       dtype=jnp.dtype(dtype))
        assert kv.chunk_attention_path(32)[0] == "pallas"
        kv.alloc_for(1, 16)                  # the slot's pages start at 1
        kv.alloc_for(0, len(seq))
        got = through_cache(model, params, kv, 0, chunk=32, SEQ=seq,
                            N_PROMPT=70)
        assert sorted(got) == [31, 63] + list(range(69, 96))
        # bfloat16 over 96 tokens: a selection or an expert choice falls
        # the other way at some positions on EITHER route (0.2-0.3 there,
        # 0.004 elsewhere; the window route's table is the same to 0.004),
        # so against the reference it is the median position that is held
        gaps = [float(np.abs(v - want[t]).max()) for t, v in got.items()]
        assert (np.median(gaps) if dtype == "bfloat16" else max(gaps)) < tol
        monkeypatch.setattr(pa, "_INTERPRET", False)
        assert kv.chunk_attention_path(32)[0] == "window"
        window = through_cache(model, params, kv, 0, chunk=32, SEQ=seq,
                               N_PROMPT=70)
        assert worst(got, window) < tol


def test_selection_is_by_position_through_the_page_table(f32):
    """A slot whose pages lie scattered gives the logits of one whose
    pages are contiguous."""
    cfg, model, params, want = f32
    kv = cache_for(model)
    for slot, n in ((0, 4), (1, 8), (0, 12), (1, 32)):   # interleaved
        kv.alloc_for(slot, n)
    assert list(kv.tables[1]) == [1, 2, 5, 6, 7, 8, 9, 10]
    scattered = through_cache(model, params, kv, 1)
    kv2 = cache_for(model)
    kv2.alloc_for(0, 32)
    contiguous = through_cache(model, params, kv2, 0)
    assert worst(scattered, contiguous) < 1e-6
    assert worst(scattered, want) < F32_TOL


def test_below_top_k_the_sparse_route_is_the_dense_route():
    """With fewer live tokens than top-k every row is selected: the sparse
    route's logits are those of the same weights with no indexer."""
    big = dict(TOY, sa_config=dict(TOY["sa_config"], topk=64))
    with jax.default_matmul_precision("highest"):
        cfg, model, params = build(big)
        dense_model = prog.build_model(big)
        dense_model.cfg.index_heads = 0
        kv, kv_d = cache_for(model), cache_for(dense_model)
        assert (kv.attention_path()[0], kv_d.attention_path()[0]) \
            == ("sparse", "gather")
        kv.alloc_for(0, 32)
        kv_d.alloc_for(0, 32)
        got = through_cache(model, params, kv, 0)
        # the dense model reads the same tree: its block never asks for
        # the indexer's leaves
        same = {k.replace(model.name, dense_model.name): v
                for k, v in params.items()}
        want = through_cache(dense_model, same, kv_d, 0)
        assert worst(got, want) < F32_TOL
        assert worst(got, reference_logits(big, ref.Variant(
            select="dense"))) < F32_TOL


@pytest.mark.parametrize("interpret", [False, True])
def test_experts_are_a_per_token_loop_and_drop_nothing(monkeypatch,
                                                       interpret):
    """A router skewed so that one expert takes nearly every token: the
    layer is still, token by token, the gated sum over its chosen
    experts (no capacity, nothing dropped).  Once through
    `lax.ragged_dot`, once through the Pallas grouped matmul
    interpreted."""
    monkeypatch.setattr(moe, "_INTERPRET", interpret)
    d, f, e, k, n = 32, 128, 8, 2, 48
    layer = moe.RoutedExperts(d, f, e, k, name="moe")
    p = layer.init(jax.random.PRNGKey(0))
    p["moe"]["router"] = p["moe"]["router"].at[:, 3].add(2.0)   # the skew
    x = jax.random.normal(jax.random.PRNGKey(1), (2, n // 2, d)) + 0.5
    ctx = Ctx(training=False)
    ctx.token_mask = jnp.arange(n).reshape(2, -1) < n - 5   # 5 padded
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply(p, x, ctx)).reshape(n, d)
        idx, gate = layer.route(p, x.reshape(n, d))
    idx, gate, xs = np.asarray(idx), np.asarray(gate), np.asarray(
        x, np.float64).reshape(n, d)
    w1, w3, w2 = (np.asarray(p["moe"][m], np.float64)
                  for m in ("w1", "w3", "w2"))
    assert (idx == 3).any(1).mean() > 0.8            # most tokens take it
    assert np.allclose(gate.sum(1), 1.0, atol=1e-6)
    for t in range(n - 5):
        want = 0.0
        for j in range(k):
            a, b = xs[t] @ w1[idx[t, j]], xs[t] @ w3[idx[t, j]]
            want = want + gate[t, j] * ((a / (1 + np.exp(-a)) * b)
                                        @ w2[idx[t, j]])
        assert np.abs(got[t] - want).max() < 2e-5, t
    assert np.abs(got[n - 5:]).max() == 0.0          # padding: no pairs
    pairs = (n - 5) * k
    assert float(ctx.counters["moe/pairs"]) == pairs
    load = np.bincount(idx[:n - 5].reshape(-1), minlength=e)
    assert float(ctx.counters["moe/experts_touched"]) == (load > 0).sum()
    assert float(ctx.counters["moe/expert_load_max"]) == load.max() \
        > pairs // 3


def test_cache_invariants_with_index_rows():
    _, model, _ = build()
    kv = cache_for(model, pages=12)
    pool = kv.init_pool()
    name = kv.layer_names[0]
    assert pool[name]["ki"].shape == (12, 4, 8)
    assert pool[name]["k"].shape == (12, 4, 2, 16)
    assert kv.index_bytes() == 2 * 12 * 4 * 8 * 4
    assert kv.alloc_for(0, 9) and kv.alloc_for(1, 30)
    assert not kv.alloc_for(0, 24)                   # all or nothing
    kv.check_invariants()
    # a token's index key lands where its k and v rows do
    lens = jnp.asarray([8, 5], jnp.int32)
    tabs = jnp.asarray(kv.tables)
    new = kv.write_token(pool[name], tabs, lens,
                         jnp.ones((2, 2, 1, 16)), jnp.ones((2, 2, 1, 16)),
                         jnp.full((2, 8), 7.0))
    for slot, n in ((0, 8), (1, 5)):
        page, off = kv.tables[slot, n // 4], n % 4
        assert float(new["ki"][page, off, 0]) == 7.0
        assert float(new["k"][page, off, 0, 0]) == 1.0
    assert float(jnp.abs(new["ki"]).sum()) == 2 * 8 * 7.0
    kv.free_slot(1)
    kv.free_slot(0, evict=True)
    kv.check_invariants()
    assert kv.pages_in_use() == 0
    with pytest.raises(ValueError, match="index"):
        PagedKVCache(["a"], n_heads=2, head_dim=16, n_pages=4, int8=True,
                     index_dim=8, index_top_k=4)


def test_engine_serves_it_in_chunks_between_steps():
    """ModelRegistry -> DecodeEngine.warmup() -> stream(): a prompt past
    the chunk goes a chunk a tick through the one chunk program while the
    other slot keeps decoding; every served token is the reference's
    first choice on the sequence as served."""
    with jax.default_matmul_precision("highest"):
        cfg, model, params = build()
        model.set_params(params, {})
        reg = ModelRegistry()
        reg.register("lm", model)
        eng = DecodeEngine(reg, "lm", slots=2, page_size=4, max_context=64,
                           max_prompt=40, prefill_chunk=4,
                           max_new_tokens=8).warmup()
        assert eng.chunked and list(eng.ladder) == []
        first = eng.stream("lm", SEQ[:N_PROMPT], max_new_tokens=8)
        second = eng.stream("lm", SEQ[5:30], max_new_tokens=6)
        outs = [np.asarray(s.result(300)) for s in (first, second)]
        st = eng.stats()
        rec = eng.recorder
        eng.shutdown()
    assert st["attn_route"] == "sparse"
    assert rec.gauge_value("decode/attn_route") == 2.0
    assert rec.gauge_value("kv/index_bytes") == eng.kv.index_bytes() > 0
    assert st["prefills"] == 2 and st["prefill_chunks"] == 5 + 7
    assert st["recompiles"] == 0 and st["warmup_compiles"] == 2
    assert rec.counter_value("moe/pairs") == st["tokens"] * 2 * 2
    assert rec.counter_value("moe/prefill_pairs") == (19 + 25) * 2 * 2
    assert 0 < rec.counter_value("moe/experts_touched") \
        <= rec.counter_value("moe/pairs")
    assert rec.counter_value("sparse/rows_scored") \
        == rec.counter_value("sparse/rows_live")
    assert 0 < st["kv_rows_attended_share"] < 0.5    # 8 of 20 and more
    w = ref.make_weights(cfg, KEY)
    for out, n_prompt in zip(outs, (N_PROMPT, 25)):
        pad = np.zeros(-len(out) % 8, np.int32)
        lg = np.asarray(ref.logits(
            w, jnp.asarray(np.concatenate([out, pad])), cfg))
        rows = lg[n_prompt - 1:len(out) - 1]
        gap = rows.max(-1) - rows[np.arange(len(rows)), out[n_prompt:]]
        assert gap.max() < F32_TOL, gap
