"""Latent attention and a held share of routed experts in one model and one
cache manager, against the plain reference
`benchmarks/reference/mla_moe_ref.py` on seeded weights at a small size:
hidden 32, 4 heads x (8 nope + 4 rope), ranks 16 / 8, values 8 wide, one
dense layer (width 48) and two expert layers of 16 experts in 4 groups
(top 4 of the best 2 groups, a correction bias, a shared expert; this
share holds experts 4-7), pages of 4, chunks of 8, vocabulary 128.
Logits are compared, never tokens."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mla_moe_ref as ref
from benchmarks.runners import mla_moe_program as prog
from bigdl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                          rope_frequencies, yarn_mscale)
from bigdl_tpu.nn.module import Ctx
from bigdl_tpu.nn.moe import RoutedExperts
from bigdl_tpu.ops import paged_attention_mod as pa
from bigdl_tpu.serving import DecodeEngine, ModelRegistry
from bigdl_tpu.serving.kvcache import PagedKVCache

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
TOY = dict(vocab_size=128, hidden_size=32, num_attention_heads=4,
           q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
           moe_intermediate_size=16, n_shared_experts=1,
           num_experts_per_tok=4, n_group=4, topk_group=2,
           norm_topk_prob=True, routed_scaling_factor=2.5,
           scoring_func="sigmoid", topk_method="noaux_tc", moe_layer_freq=1,
           num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=4,
           held_experts=[4, 4], published={"n_routed_experts": 16},
           rope_theta=10000, rope_scaling=YARN, rms_norm_eps=1e-6,
           hidden_act="silu", attention_bias=False,
           tie_word_embeddings=False, max_position_embeddings=128,
           # at hidden 32 a matrix of N(0, 0.15) carries a unit vector to
           # about unit size, as N(0, 0.02) does at the published 7,168
           initializer_range=0.15, init_q_gain=3.0, init_embed_gain=6.0,
           init_router_gain=3.0, init_router_bias_std=0.1,
           activation_dtype="float32", param_dtype="float32")
KEY = jax.random.PRNGKey(11)
SEQ = np.random.default_rng(0).integers(0, 128, 40).astype(np.int32)
N_PROMPT = 21            # two chunks of 8 and one of 5
PAGE, CHUNK = 4, 8
F32_TOL = 2e-5           # float32 at "highest": rounding in another order
CONTROL_MOVES = 0.05     # what a wrong layer moves the logits by, at least
MODEL_CONTROLS = [n for n in ref.controls() if n != "fp8"]


def build(cfg=TOY):
    model = prog.build_model(cfg)
    return model, prog.program_tree(cfg, KEY, model)


def reference_logits(cfg=TOY, variant=ref.SOUND, seq=SEQ, held=None):
    w = ref.make_weights(cfg, KEY, held=held)
    return np.asarray(ref.logits(w, jnp.asarray(seq), cfg, variant, held))


def cache_for(model, n_slots=2, max_context=64, n_pages=None):
    return PagedKVCache([b.attn.name for b in model.blocks],
                        **model.kv_geometry(), n_pages=n_pages,
                        page_size=PAGE, n_slots=n_slots,
                        max_context=max_context, dtype=jnp.float32)


def through_cache(model, params, kv, slot, seq=SEQ, n_prompt=N_PROMPT):
    """Chunked prefill of seq[:n_prompt] then one-token decode of the rest
    through `kv`'s pages of `slot`: the logits after every position from
    the prompt's last on, and after each chunk's last token."""
    pool = kv.init_pool()
    assert kv.alloc_for(slot, n_prompt)
    table = jnp.asarray(kv.tables[slot])
    out = {}
    for start in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - start)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :n] = seq[start:start + n]

        def kv_io(name, q, rows, up):
            pool[name] = kv.write_chunk(
                pool[name], kv.chunk_pages(table, jnp.int32(start), CHUNK),
                rows)
            return kv.attend_chunk(pool[name], table, jnp.int32(start), q,
                                   up=up)
        out[start + n - 1] = model.prefill_chunk(
            params, jnp.asarray(toks), jnp.int32(start), jnp.int32(n), kv_io)
    lengths = np.zeros(kv.n_slots, np.int32)
    lengths[slot] = n_prompt
    for t in range(n_prompt, len(seq)):
        assert kv.alloc_for(slot, t + 1)
        tokens = np.zeros(kv.n_slots, np.int32)
        tokens[slot] = seq[t]
        tabs = np.full_like(kv.tables, -1)              # the others dead
        tabs[slot] = kv.tables[slot]
        tabs, lens = jnp.asarray(tabs), jnp.asarray(lengths)

        def kv_io(name, q, rows):
            o = kv.attend(pool[name], tabs, lens, q, rows=rows)
            pool[name] = kv.write_token(pool[name], tabs, lens, rows)
            return o
        out[t] = model.decode_tokens(params, jnp.asarray(tokens), lens,
                                     kv_io)[slot]
        lengths[slot] += 1
    return {t: np.asarray(v, np.float32) for t, v in out.items()}, pool


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        model, params = build()
        yield model, params, reference_logits()


# --------------------------------------------------------------------- #
# the three cached routes and the full forward against the reference
# --------------------------------------------------------------------- #
def test_full_forward_equals_the_reference(f32):
    model, params, want = f32
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(SEQ)[None],
                          Ctx(state={}, training=False, rng_key=None))[0]
    assert float(np.abs(np.asarray(got) - want).max()) < F32_TOL
    assert want.std() > 0.3          # logits that say something


def test_static_cache_prefill_and_decode_equal_the_reference(f32):
    model, params, want = f32
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(1, cache_len=48)
        assert {k: v.shape for k, v in
                cache[model.blocks[0].attn.name].items()} \
            == {"latent": (1, 48, 12)}
        got, cache = model.apply_with_cache(
            params, jnp.asarray(SEQ)[None, :N_PROMPT], cache, 0)
        rows = [np.asarray(got[0])]
        for t in range(N_PROMPT, len(SEQ)):
            got, cache = model.apply_with_cache(
                params, jnp.asarray(SEQ)[None, t:t + 1], cache, t)
            rows.append(np.asarray(got[0]))
    assert float(np.abs(np.concatenate(rows) - want).max()) < F32_TOL


@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["chunk_up_projected", "chunk_absorbed"])
def test_chunked_prefill_through_pages_and_decode_equal_the_reference(
        f32, absorbed, monkeypatch):
    """Both formulas of the chunk's attention, and the absorbed decode:
    the cache never holds a per-head key, and every logit is the
    reference's."""
    model, params, want = f32
    monkeypatch.setattr(pa, "_LATENT_CHUNK_ABSORBED", absorbed)
    kv = cache_for(model)
    assert kv.chunk_attention_path(CHUNK)[0] == "latent"
    assert ("absorbed" if absorbed else "up-projected") \
        in kv.chunk_attention_path(CHUNK)[1]
    with jax.default_matmul_precision("highest"):
        got, _ = through_cache(model, params, kv, slot=1)
    assert sorted(got) == [7, 15] + list(range(20, len(SEQ)))
    assert max(float(np.abs(v - want[t]).max())
               for t, v in got.items()) < F32_TOL
    kv.check_invariants()


@pytest.fixture
def key_block():
    """Sets `_LATENT_KEY_BLOCK` (read as the walk traces, so the jitted
    walk's cache is cleared with it) for one test; put back after."""
    def set_to(rows):
        pa._LATENT_KEY_BLOCK = rows
        pa._latent_chunk_attend.clear_cache()
    saved = pa._LATENT_KEY_BLOCK
    yield set_to
    set_to(saved)


WALK_PAGES, WALK_KEYS = 13, 16    # a table of 52 rows, 4 pages a step


def walk_case(seed=5):
    """Seeded q, W_uk, W_uv, a pool of 20 pages of rank 8 + rope 4, and a
    table of 13 of its pages in a shuffled order."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(k[0], (4, CHUNK, 12))
    w_uk = jax.random.normal(k[1], (8, 4, 8)) * 0.5
    w_uv = jax.random.normal(k[2], (8, 4, 8)) * 0.5
    pool = np.array(jax.random.normal(k[3], (20, PAGE, 12)))
    table = np.array(jax.random.permutation(k[4], 20)[:WALK_PAGES],
                     np.int32)
    return q, w_uk, w_uv, pool, table


def full_table_attention(q, w_uk, w_uv, pool, table, start, sm_scale):
    """The plain float32 chunk attention: every row of the table gathered
    (a page of -1 reads zeros), each head's keys and values made from the
    rows, masked by position, softmax."""
    rows = np.where((table >= 0)[:, None, None],
                    pool[np.maximum(table, 0)], 0).reshape(-1, 12)
    c, k_r = rows[:, :8], rows[:, 8:]
    keys = jnp.concatenate([jnp.einsum("lr,rhn->hln", c, w_uk),
                            jnp.broadcast_to(k_r, (4,) + k_r.shape)], -1)
    sc = jnp.einsum("hcd,hld->hcl", q, keys) * sm_scale
    mask = np.arange(len(rows))[None, :] <= start + np.arange(CHUNK)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hcl,hlv->hcv", p, jnp.einsum("lr,rhv->hlv", c, w_uv))


@pytest.mark.parametrize("tail", ["stale_rows", "no_pages"])
@pytest.mark.parametrize("start", [0, WALK_KEYS - PAGE, WALK_KEYS, 24,
                                   WALK_PAGES * PAGE - CHUNK],
                         ids=["first", "a_page_under_a_step", "one_step",
                              "mid_table", "last_chunk"])
@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["up_projected", "absorbed"])
def test_the_chunk_walk_equals_the_full_table(key_block, absorbed, start,
                                              tail):
    """The walk over the keys up to the chunk's end (4 pages a step, the
    last step past the table's 13 pages read as zeros) is the whole
    table's attention; rows past the chunk's end hold NaN and 1e30
    (`stale_rows`) or lie in no page (`no_pages`), and change nothing."""
    key_block(WALK_KEYS)
    q, w_uk, w_uv, pool, table = walk_case()
    with jax.default_matmul_precision("highest"):
        want = full_table_attention(q, w_uk, w_uv, pool, table, start, 0.3)
        end = start + CHUNK
        if tail == "no_pages":
            table[-(-end // PAGE):] = -1
        else:
            rows = pool[table].reshape(-1, 12)
            rows[end::2], rows[end + 1::2] = np.nan, 1e30
            pool[table] = rows.reshape(-1, PAGE, 12)
        got = pa._latent_chunk_attend(
            q, w_uk, w_uv, jnp.asarray(pool), jnp.asarray(table),
            jnp.int32(start), rank=8, sm_scale=0.3, absorbed=absorbed)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < F32_TOL


def test_the_walk_is_one_program_for_every_offset(key_block):
    """The steps are a traced count: three offsets, one trace."""
    key_block(WALK_KEYS)
    q, w_uk, w_uv, pool, table = walk_case()
    assert pa.latent_chunk_walk(0, CHUNK, PAGE, WALK_PAGES) == (1, 16)
    assert pa.latent_chunk_walk(44, CHUNK, PAGE, WALK_PAGES) == (4, 16)
    # a step is at most the table
    assert pa.latent_chunk_walk(0, CHUNK, PAGE, 2) == (1, 8)
    for start in (0, 20, 44):
        pa._latent_chunk_attend(q, w_uk, w_uv, jnp.asarray(pool),
                                jnp.asarray(table), jnp.int32(start), rank=8,
                                sm_scale=0.3, absorbed=False)
    assert pa._latent_chunk_attend._cache_size() == 1


def test_the_engine_counts_the_rows_each_chunk_walks(f32, key_block):
    """`mla/chunk_rows_walked` is, a chunk, the layers x its steps x the
    keys a step: 3 x cdiv(start + 8, 12) x 12 over a table of 8 pages of
    4 rows; the chunks at offsets 0 to 24 compile nothing new."""
    key_block(12)
    model, params, _ = f32
    with jax.default_matmul_precision("highest"):
        model.set_params(params, {})
        reg = ModelRegistry()
        reg.register("lm", model)
        eng = DecodeEngine(reg, "lm", slots=2, page_size=PAGE,
                           max_context=48, max_prompt=32,
                           prefill_chunk=CHUNK, max_new_tokens=2)
        eng.warmup()
        rec = eng.recorder
        for n in (5, 21, 32):
            before = rec.counter_value("mla/chunk_rows_walked")
            list(eng.stream("lm", SEQ[:n]).tokens())
            want = sum(3 * -(-(start + CHUNK) // 12) * 12
                       for start in range(0, n, CHUNK))
            assert rec.counter_value("mla/chunk_rows_walked") - before \
                == want, n
        st = eng.stats()
        eng.shutdown()
    assert st["chunk_rows_walked"] == rec.counter_value(
        "mla/chunk_rows_walked") == 3 * 12 * (1 + (1 + 2 + 2)
                                              + (1 + 2 + 2 + 3))
    assert st["recompiles"] == 0


def test_absorbed_attention_equals_the_up_projected(f32):
    """`absorb` + `_latent_attend` + `up_values` is `attend_rows`."""
    model, params, _ = f32
    attn = model.blocks[1].attn
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    q_n = jax.random.normal(k[0], (2, 4, 1, 8))
    q_r = jax.random.normal(k[1], (2, 4, 1, 4))
    pool = jax.random.normal(k[2], (6, PAGE, 12))
    tables = jnp.asarray([[4, 0, 5], [2, 3, -1]], jnp.int32)
    lengths = jnp.asarray([9, 5], jnp.int32)
    own = jax.random.normal(jax.random.PRNGKey(8), (2, 12))
    with jax.default_matmul_precision("highest"):
        o = pa._latent_attend(attn.absorb(params, q_n, q_r)[:, :, 0], own,
                              pool, tables, lengths, rank=8,
                              sm_scale=attn.sm_scale)
        got = attn.up_values(params, o[:, :, None])
        # what the pool holds once the tokens' own rows are written
        rows = jnp.take(pool, jnp.maximum(tables, 0), axis=0).reshape(
            2, -1, 12).at[jnp.arange(2), lengths].set(own)
        mask = jnp.arange(12)[None, None, :] <= lengths[:, None, None]
        want = attn.attend_rows(params, q_n, q_r, rows, mask)
    assert float(jnp.abs(got - want).max()) < F32_TOL


# --------------------------------------------------------------------- #
# the held share
# --------------------------------------------------------------------- #
def moe_layer(held):
    return RoutedExperts(32, 16, 16, 4, name="moe", scoring="sigmoid",
                         n_groups=4, top_groups=2, routed_scale=2.5,
                         router_bias=True, held=held, shared_d_ff=16)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares plus the shared expert once are
    the uncut reference's layer, in the program and in the reference."""
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32))
    with jax.default_matmul_precision("highest"):
        uncut = ref.make_layer(TOY, KEY, 1, held=(0, 16))
        want = ref.experts(u[0], uncut, TOY, ref.SOUND, (0, 16)) \
            + ref._swiglu(u[0], uncut["shared_w1"], uncut["shared_w3"],
                          uncut["shared_w2"], ref.SOUND)
        shared = ref._swiglu(u[0], uncut["shared_w1"], uncut["shared_w3"],
                             uncut["shared_w2"], ref.SOUND)
        total, by_ref, pairs = 0.0, 0.0, 0.0
        for first in (0, 4, 8, 12):
            lw = ref.make_layer(TOY, KEY, 1, held=(first, 4))
            ctx = Ctx(state={}, training=False, rng_key=None)
            layer = moe_layer((first, 4))
            y = layer.apply({"moe": {k: lw[k] for k in prog.MOE}}, u, ctx)[0]
            total = total + (y - shared)
            by_ref = by_ref + ref.experts(u[0], lw, TOY, ref.SOUND,
                                          (first, 4))
            pairs += float(ctx.counters["moe/pairs"])
            assert float(ctx.counters["moe/pairs_routed"]) == 24 * 4
    assert float(jnp.abs(total + shared - want).max()) < F32_TOL
    assert float(jnp.abs(by_ref + shared - want).max()) < F32_TOL
    assert pairs == 24 * 4           # every routed pair is some share's


def test_route_is_the_references(f32):
    """Expert ids and gates: sigmoid scores, the bias in the selection
    only, the group limit, the gates renormalised and scaled."""
    u = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    lw = ref.make_layer(TOY, KEY, 2)
    with jax.default_matmul_precision("highest"):
        idx, gate = moe_layer((4, 4)).route(
            {"moe": {k: lw[k] for k in prog.MOE}}, u)
        want_idx, want_gate = ref.route(u, lw, TOY)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert float(jnp.abs(gate - want_gate).max()) < 1e-6
    assert np.allclose(np.asarray(gate.sum(-1)), 2.5, atol=1e-5)
    # top 4 of the best 2 groups: a token's experts lie in two groups
    assert max(len(set(row // 4)) for row in np.asarray(idx)) <= 2


@pytest.mark.parametrize("control", MODEL_CONTROLS)
def test_each_control_moves_the_logits(f32, control):
    _, _, want = f32
    with jax.default_matmul_precision("highest"):
        moved = reference_logits(variant=ref.controls()[control])
    assert float(np.abs(moved - want).max()) > CONTROL_MOVES, control


# --------------------------------------------------------------------- #
# YaRN
# --------------------------------------------------------------------- #
def test_yarn_frequencies_and_m_against_hand_values():
    """At the published sizes: rope 64, theta 10000, factor 40 over 4,096,
    beta 32 / 1: correction dims 10 and 23."""
    sc = dict(YARN, original_max_position_embeddings=4096)
    f = np.asarray(rope_frequencies(64, 10000.0, sc))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    assert np.allclose(f[:11], plain[:11], rtol=1e-6)       # fast: their own
    assert np.allclose(f[23:], plain[23:] / 40, rtol=1e-6)  # slow: / factor
    ramp = (16 - 10) / 13.0                                 # frequency 16
    assert math.isclose(f[16], plain[16] * (ramp / 40 + 1 - ramp),
                        rel_tol=1e-5)
    assert math.isclose(yarn_mscale(sc), 1.3688879, rel_tol=1e-6)
    cfg = dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=sc)
    assert math.isclose(ref.softmax_scale(cfg), 0.135234, rel_tol=1e-5)
    assert np.allclose(np.asarray(ref.yarn_inv_freq(cfg)), f, rtol=1e-6)
    assert np.allclose(np.asarray(rope_frequencies(64, 10000.0)), plain,
                       rtol=1e-6)
    assert yarn_mscale(None) == 1.0
    model = prog.build_model(dict(TOY, num_attention_heads=2,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                                  rope_scaling=sc))
    assert math.isclose(model.blocks[0].attn.sm_scale, 0.135234,
                        rel_tol=1e-5)


# --------------------------------------------------------------------- #
# the pool, the allocator, the engine
# --------------------------------------------------------------------- #
def test_a_latent_layers_pool_is_one_array_of_rows(f32):
    model, _, _ = f32
    kv = cache_for(model, n_slots=3, max_context=32)
    pool = kv.init_pool()
    assert sorted(pool) == sorted(b.attn.name for b in model.blocks)
    for layer in pool.values():
        assert {k: (v.shape, v.dtype) for k, v in layer.items()} \
            == {"latent": ((3 * 8, PAGE, 8 + 4), jnp.float32)}
    assert kv.latent_row_bytes() == 12 * 4
    assert kv.latent_bytes() == 3 * 24 * PAGE * 12 * 4
    assert kv.attention_path()[0] == "latent"
    # a slot's window of such a pool is its rows
    assert kv.gather_window(pool[kv.layer_names[0]],
                            jnp.asarray(kv.tables)).shape == (3, 32, 12)
    with pytest.raises(ValueError):
        PagedKVCache(["a"], n_heads=1, head_dim=12, latent_rank=8,
                     sm_scale=0.3, int8=True)


@pytest.fixture(scope="module")
def churned():
    """An engine with a pool too small for its slots, after a dozen
    requests of mixed lengths: (engine, the requests' tokens)."""
    with jax.default_matmul_precision("highest"):
        model, params = build()
        model.set_params(params, {})
        reg = ModelRegistry()
        reg.register("lm", model)
        eng = DecodeEngine(reg, "lm", slots=3, page_size=PAGE,
                           pool_pages=20, max_context=48, max_prompt=32,
                           prefill_chunk=CHUNK, max_new_tokens=8)
        eng.warmup()
        rng = np.random.default_rng(1)
        streams = [eng.stream("lm", rng.integers(0, 128, n).astype(np.int32),
                              max_new_tokens=8)
                   for n in (21, 5, 32, 9, 30, 17, 3, 26, 12, 31, 8, 24)]
        tokens = [list(s.tokens()) for s in streams]
        yield eng, tokens
        eng.shutdown()


def test_allocator_invariants_through_churn(churned):
    eng, tokens = churned
    assert all(len(t) == 8 for t in tokens)
    eng.kv.check_invariants()
    assert eng.kv.pages_in_use() == 0
    st = eng.stats()
    assert st["recompiles"] == 0 and st["warmup_compiles"] == 2
    assert st["evictions"] > 0       # 20 pages under three slots of 12


def test_the_engine_names_its_routes_and_counts_its_rows(churned):
    eng, _ = churned
    st, rec = eng.stats(), eng.recorder
    assert st["attn_route"] == st["chunk_attn_route"] == "latent"
    assert st["kv_kinds"] == {"global": {
        "layers": 3, "window": 0, "pages_per_slot": 12, "n_pages": 20,
        "content": "latent"}}
    assert rec.gauge_value("decode/attn_route") == 3.0
    assert rec.gauge_value("decode/chunk_attn_route") == 2.0
    assert rec.gauge_value("kv/latent_row_bytes") == 12 * 4
    assert rec.gauge_value("kv/latent_bytes") == 3 * 20 * PAGE * 12 * 4
    for name in ("mla/rows_live", "mla/chunk_rows_visible",
                 "mla/chunk_rows_live", "moe/pairs_routed", "moe/pairs",
                 "moe/prefill_pairs_routed", "moe/prefill_pairs"):
        assert rec.counter_value(name) > 0, name
    # valid tokens x 4, two expert layers; this share's are some of them
    assert rec.counter_value("moe/pairs_routed") \
        == 2 * 4 * rec.counter_value("decode/tokens")
    assert rec.counter_value("moe/pairs") \
        < rec.counter_value("moe/pairs_routed")
    assert rec.counter_value("mla/chunk_rows_visible") \
        <= CHUNK * rec.counter_value("mla/chunk_rows_live")


def test_engine_tokens_are_the_static_caches(f32):
    """The served tokens of one request are `generate`'s (greedy)."""
    model, params, _ = f32
    with jax.default_matmul_precision("highest"):
        model.set_params(params, {})
        want = np.asarray(model.generate(params, SEQ[None, :N_PROMPT], 6))
        reg = ModelRegistry()
        reg.register("lm", model)
        eng = DecodeEngine(reg, "lm", slots=2, page_size=PAGE,
                           max_context=48, max_prompt=32,
                           prefill_chunk=CHUNK, max_new_tokens=6)
        got = list(eng.stream("lm", SEQ[:N_PROMPT]).tokens())
        eng.shutdown()
    assert got == want[0, N_PROMPT:].tolist()


# --------------------------------------------------------------------- #
# what was there builds what it built
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", ["dense", "switch", "routed"])
def test_a_config_without_the_new_fields_builds_the_old_tree(kind):
    extra = {"dense": {}, "switch": dict(moe_experts=4, moe_top_k=2),
             "routed": dict(moe_experts=4, moe_top_k=2,
                            moe_capacity_factor=None)}[kind]
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=48,
        max_len=32, **extra), name="lm")
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert shapes["lm.block1.attn"] == {
        "wq": (32, 32), "wk": (32, 32), "wv": (32, 32), "wo": (32, 32)}
    mlp = {"dense": ("lm.block1.mlp", {
               "w1": (32, 48), "w3": (32, 48), "w2": (48, 32)}),
           "switch": ("lm.block1.moe", {
               "router": (32, 4), "w1": (4, 32, 48), "w3": (4, 32, 48),
               "w2": (4, 48, 32)})}
    name, want = mlp.get(kind, mlp["switch"])
    assert shapes[name] == want
    assert model.kv_geometry() == dict(n_heads=2, q_heads=2, head_dim=16,
                                       index_dim=0, index_top_k=0)
    assert model.blocks[0].attn.rope_freqs is None


def test_routed_experts_default_route_is_softmax_top_k():
    layer = RoutedExperts(32, 16, 8, 2, name="moe")
    params = layer.init(jax.random.PRNGKey(0))
    assert sorted(params["moe"]) == ["router", "w1", "w2", "w3"]
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 32))
    with jax.default_matmul_precision("highest"):
        idx, gate = layer.route(params, x)
        probs = jax.nn.softmax(x @ params["moe"]["router"], -1)
    top, want = jax.lax.top_k(probs, 2)
    assert np.array_equal(np.asarray(idx), np.asarray(want))
    assert float(jnp.abs(gate - top / top.sum(-1, keepdims=True)).max()) \
        < 1e-6
    ctx = Ctx(state={}, training=False, rng_key=None)
    layer.apply(params, x[None], ctx)
    assert sorted(ctx.counters) == ["moe/expert_load_max",
                                    "moe/experts_touched", "moe/pairs"]
    with pytest.raises(ValueError):
        RoutedExperts(32, 16, 8, 2, held=(6, 4))
    with pytest.raises(ValueError):
        RoutedExperts(32, 16, 8, 4, n_groups=4, top_groups=1)
