"""Plain float32 reference of a decoder of latent attention and routed
experts, one chip's share of it (dots.vlm1.inst's language model, whose
block is DeepSeek-V3's: 128 heads over ONE cached row of 512 + 64 a
token; after `first_k_dense_replace` dense layers, 256 sigmoid-scored
experts in 8 groups, top 8 of the best 4 groups, a shared expert beside
them; this chip holds `held_experts` = (first, count) of the 256).

Straight `jax.numpy` in float32 under matmul precision "highest": no
kernels, no cache, no batching, nothing absorbed; imports nothing of the
program.  Layer `l` on one sequence, x (T, hidden), `t` a query, `s <= t`
a key, `i` a head:

    h   = RMSNorm(x; norm1)
    c_q = RMSNorm(h W_qa; q_norm)                           (q_lora_rank)
    [q_n,i ; q_r,i] = c_q W_qb                              (nope + rope a head)
    [c ; k_r] = h W_kva;  c <- RMSNorm(c; kv_norm)          (kv_lora_rank + rope)
    k_r <- rope(k_r)  (one a token, every head's);  q_r,i <- rope(q_r,i)
    [k_n,i ; v_i] = c W_kvb                                 (nope + v a head)
    score_i(t,s) = (q_n,i(t) . k_n,i(s) + q_r,i(t) . k_r(s)) (nope + rope)^-0.5 m^2
    a_i = softmax_s(score_i) v_i;   x' = x + concat_i(a_i) W_o
    u   = RMSNorm(x'; norm2)
    dense layer (l < first_k_dense_replace): out = x' + W2(silu(W1 u) * (W3 u))
    expert layer:
      s  = sigmoid(u W_r)  (all the router's experts, float32);  s' = s + b
      a group's score is the sum of its two largest s'; the topk_group
      best groups stay; the K largest s' among them are chosen
      gate_e = routed_scaling_factor s_e / sum_chosen(s)    (from s, not s')
      y   = sum_{e chosen AND held} gate_e E_e(u) + E_shared(u)
      out = x' + y,   each E a SwiGLU W2(silu(W1 u) * (W3 u))

then the final RMSNorm and the untied head.  Rope is YaRN's: per frequency
`inv_freq_j = (1 - g_j) / (factor theta_j) + g_j / theta_j`, `theta_j =
rope_theta^(2j / rope)`, `g` one minus the linear ramp between the
correction dims of beta_fast and beta_slow rotations over the original
length; `m = 0.1 mscale_all_dim ln(factor) + 1`; cos and sin unscaled
(mscale = mscale_all_dim).  No token is dropped.  What the absent experts
would add is left out: the sum over every share's routed part, plus the
shared expert once, is the uncut layer (`held` = (0, all)).

Departures from the published model, each also under `assumed` in the
configuration file: no vision tower and no multi-token-prediction module;
rotary pairs are interleaved (2i, 2i + 1), the program's convention;
weights are seeded N(0, initializer_range) with `init_q_gain` on W_qb,
`init_embed_gain` on the embedding's rows, `init_router_gain` on the
router, and a correction bias N(0, init_router_bias_std).

Queries are walked in blocks with the mask written out; the experts are a
loop over the HELD experts in which every token carries its own gate for
the expert at hand (zero where it did not choose it).

`Variant` is the hook of the controls, each one way of computing the
model wrongly that the comparison has to catch: `quant` (a function
applied to both operands of every matmul: `fp8`, `bf16`), `stored` (a
function applied to every activation where the program stores one),
`m2` False (the softmax scale without m^2), `yarn` False (plain rope),
`scoring` "softmax", `bias_in_gates` True (the gates from s', not s),
`group_limit` False, `shared` False (the shared expert left out),
`latent_norm` False (c cached without its RMSNorm).  `choice_gaps` judges
the served tokens, and each variant's own first choices, by the sound
reference's logits, as `window_moe_ref.choice_gaps` does.
"""
import collections
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 128

Variant = collections.namedtuple(
    "Variant", "quant stored m2 yarn scoring bias_in_gates group_limit "
    "shared latent_norm",
    defaults=(None, None, True, True, "sigmoid", False, True, True, True))
SOUND = Variant()


def dims(cfg):
    first, count = cfg["held_experts"]
    assert count == cfg["n_routed_experts"], "n_routed_experts: those held"
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                q_rank=cfg["q_lora_rank"], rank=cfg["kv_lora_rank"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"], dense_f=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"],
                shared_f=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"],
                e=cfg["published"]["n_routed_experts"], first=first,
                held=count, k=cfg["num_experts_per_tok"],
                groups=cfg["n_group"], top_groups=cfg["topk_group"],
                v=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
                n_dense=cfg["first_k_dense_replace"])


# --------------------------------------------------------------------- #
# weights, a layer at a time
# --------------------------------------------------------------------- #
def _mat(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _experts(key, first, count, shape, std, dtype):
    """`count` experts' matrices from expert `first` on, each from its own
    key: an expert's weights do not depend on which share holds it."""
    return jax.vmap(lambda e: _mat(jax.random.fold_in(key, e), shape, std,
                                   dtype))(first + jnp.arange(count))


def make_layer(cfg, key, i, dtype=jnp.float32, dense=False, held=None):
    """Layer i's weights, laid out as the program stores them (the held
    experts stacked on a leading axis; W_qb and W_kvb by head, a head's
    nope part first).  `held` = (first, count), the configuration's own
    share by default."""
    m = dims(cfg)
    std = cfg.get("initializer_range", 0.02)
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    d, h = m["d"], m["h"]
    out = {
        "wq_a": _mat(ks[0], (d, m["q_rank"]), std, dtype),
        "q_norm": jnp.ones((m["q_rank"],), dtype),
        "wq_b": _mat(ks[1], (m["q_rank"], h * (m["nope"] + m["rope"])),
                     std * cfg.get("init_q_gain", 1.0), dtype),
        "wkv_a": _mat(ks[2], (d, m["rank"] + m["rope"]), std, dtype),
        "kv_norm": jnp.ones((m["rank"],), dtype),
        "wkv_b": _mat(ks[3], (m["rank"], h * (m["nope"] + m["v_dim"])), std,
                      dtype),
        "wo": _mat(ks[4], (h * m["v_dim"], d), std, dtype),
        "norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype)}
    if dense:
        f = m["dense_f"]
        out.update(w1=_mat(ks[5], (d, f), std, dtype),
                   w3=_mat(ks[6], (d, f), std, dtype),
                   w2=_mat(ks[7], (f, d), std, dtype))
        return out
    first, count = held or (m["first"], m["held"])
    f, fs = m["f"], m["shared_f"]
    out.update(
        router=_mat(ks[8], (d, m["e"]),
                    std * cfg.get("init_router_gain", 1.0), dtype),
        router_bias=_mat(ks[9], (m["e"],),
                         cfg.get("init_router_bias_std", 0.0), jnp.float32),
        w1=_experts(ks[10], first, count, (d, f), std, dtype),
        w3=_experts(ks[11], first, count, (d, f), std, dtype),
        w2=_experts(ks[12], first, count, (f, d), std, dtype),
        shared_w1=_mat(ks[13], (d, fs), std, dtype),
        shared_w3=_mat(ks[14], (d, fs), std, dtype),
        shared_w2=_mat(ks[15], (fs, d), std, dtype))
    return out


def make_embed(cfg, key, dtype=jnp.float32):
    m = dims(cfg)
    return _mat(jax.random.fold_in(key, 10_001), (m["v"], m["d"]),
                cfg.get("initializer_range", 0.02)
                * cfg.get("init_embed_gain", 1.0), dtype)


def make_head(cfg, key, dtype=jnp.float32):
    m = dims(cfg)
    return {"head": _mat(jax.random.fold_in(key, 10_002), (m["d"], m["v"]),
                         cfg.get("initializer_range", 0.02), dtype),
            "final_norm": jnp.ones((m["d"],), dtype)}


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def make_weights(cfg, key, dtype=jnp.float32, held=None):
    """Every weight at once: small sizes only (6.19 B parameters in
    float32 are 24.7 GB at the configuration's own)."""
    return dict(make_head(cfg, key, dtype),
                embed=make_embed(cfg, key, dtype),
                layers=[make_layer(cfg, key, i, dtype, is_dense(cfg, i), held)
                        for i in range(cfg["num_hidden_layers"])])


# --------------------------------------------------------------------- #
# the layer
# --------------------------------------------------------------------- #
def fp8(x):
    """Round through float8_e4m3fn with one scale a tensor: the precision
    below bfloat16."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(x):
    """Round to bfloat16's values, the configuration's own precision, by
    `lax.reduce_precision`, which a compiler may not remove (a convert
    down and up again is one that XLA is allowed to drop: PERF.md section
    6, PR 32)."""
    return lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                mantissa_bits=7)


def controls():
    """The eight ways of computing the model wrongly that the cell's
    comparison has to catch."""
    return {"fp8": Variant(quant=fp8),
            "scale_without_m2": Variant(m2=False),
            "plain_rope": Variant(yarn=False),
            "softmax_scores": Variant(scoring="softmax"),
            "bias_in_gates": Variant(bias_in_gates=True),
            "no_group_limit": Variant(group_limit=False),
            "no_shared_expert": Variant(shared=False),
            "no_latent_norm": Variant(latent_norm=False)}


# the reference in the configuration's own precision: matmul operands in
# `param_dtype` and every activation stored in `activation_dtype`, both
# bfloat16
OWN_PRECISION = Variant(quant=bf16, stored=bf16)


def _ein(spec, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _stored(x, variant):
    return x if variant.stored is None else variant.stored(x)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def yarn_inv_freq(cfg, yarn=True):
    """The rope's inverse frequencies (rope / 2,): YaRN's, or the plain
    `rope_theta^(-2j / rope)` ladder."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    sc = cfg.get("rope_scaling")
    if not (yarn and sc):
        return plain
    orig = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    g = 1.0 - ramp
    return (1.0 - g) * plain / sc["factor"] + g * plain


def yarn_m(cfg):
    sc = cfg.get("rope_scaling")
    if not sc or sc["factor"] <= 1:
        return 1.0
    assert sc["mscale"] == sc["mscale_all_dim"], "cos and sin unscaled"
    return 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0


def softmax_scale(cfg, m2=True):
    m = dims(cfg)
    return (m["nope"] + m["rope"]) ** -0.5 * (yarn_m(cfg) ** 2 if m2 else 1.0)


def rope(x, inv_freq):
    """x (T, heads, D), interleaved pairs, positions 0..T-1."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(h, lw, cfg, variant):
    """h (T, D), the block's normed input -> concat_i(a_i) (T, H * v)."""
    m, quant, eps = dims(cfg), variant.quant, cfg["rms_norm_eps"]
    t = h.shape[0]
    nh, nope, rp, rank, vd = m["h"], m["nope"], m["rope"], m["rank"], \
        m["v_dim"]
    st = lambda a: _stored(a, variant)
    inv_freq = yarn_inv_freq(cfg, variant.yarn)
    c_q = st(rmsnorm(st(_ein("td,dr->tr", h, lw["wq_a"], quant)),
                     lw["q_norm"], eps))
    q = st(_ein("tr,re->te", c_q, lw["wq_b"], quant)).reshape(
        t, nh, nope + rp)
    q_n, q_r = q[..., :nope], st(rope(q[..., nope:], inv_freq))
    kv = st(_ein("td,dr->tr", h, lw["wkv_a"], quant))
    c = kv[:, :rank]
    if variant.latent_norm:
        c = st(rmsnorm(c, lw["kv_norm"], eps))
    k_r = st(rope(kv[:, None, rank:], inv_freq))[:, 0]
    kvb = st(_ein("tr,re->te", c, lw["wkv_b"], quant)).reshape(
        t, nh, nope + vd)
    k_n, v = kvb[..., :nope], kvb[..., nope:]
    scale = softmax_scale(cfg, variant.m2)
    k_pos = jnp.arange(t)

    def block(args):
        qn_b, qr_b, pos = args                      # a block of queries
        seen = k_pos[None, :] <= pos[:, None]
        sc = (_ein("qhn,shn->hqs", qn_b, k_n, quant)
              + _ein("qhr,sr->hqs", qr_b, k_r, quant)) * scale
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        return st(_ein("hqs,shv->qhv", pr, v, quant)).reshape(-1, nh * vd)

    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    n = t // blk
    out = lax.map(block, (q_n.reshape(n, blk, nh, nope),
                          q_r.reshape(n, blk, nh, rp),
                          k_pos.reshape(n, blk)))
    return out.reshape(t, nh * vd)


def route(u, lw, cfg, variant=SOUND):
    """u (T, D) -> (expert ids (T, K) among all the router's experts,
    gates (T, K))."""
    m = dims(cfg)
    assert cfg["norm_topk_prob"] and cfg["scoring_func"] == "sigmoid" \
        and cfg["topk_method"] == "noaux_tc"
    z = _ein("td,de->te", u, lw["router"], None)
    s = jax.nn.sigmoid(z) if variant.scoring == "sigmoid" \
        else jax.nn.softmax(z, -1)
    choice = s + lw["router_bias"].astype(jnp.float32)
    weigh = choice if variant.bias_in_gates else s
    if variant.group_limit:
        t, g = choice.shape[0], m["groups"]
        by_group = choice.reshape(t, g, -1)
        group_score = lax.top_k(by_group, 2)[0].sum(-1)
        best = lax.top_k(group_score, m["top_groups"])[1]
        kept = (best[:, :, None] == jnp.arange(g)[None, None, :]).any(1)
        choice = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(t, -1)
    _, idx = lax.top_k(choice, m["k"])
    picked = jnp.take_along_axis(weigh, idx, -1)
    return idx, cfg["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)


def _swiglu(u, w1, w3, w2, variant):
    quant = variant.quant
    st = lambda a: _stored(a, variant)
    hid = st(jax.nn.silu(st(_ein("td,df->tf", u, w1, quant)))
             * st(_ein("td,df->tf", u, w3, quant)))
    return st(_ein("tf,fd->td", hid, w2, quant))


def experts(u, lw, cfg, variant, held=None):
    """The routed part of this share: sum over the experts that a token
    chose AND `held` = (first, count) holds, `lw`'s stacks being those
    experts' in order."""
    m = dims(cfg)
    first, count = held or (m["first"], m["held"])
    idx, gate = route(u, lw, cfg, variant)

    def one(j, acc):
        c = jnp.where(idx == first + j, gate, 0.0).sum(-1)       # (T,)
        return acc + c[:, None] * _swiglu(u, lw["w1"][j], lw["w3"][j],
                                          lw["w2"][j], variant)

    return lax.fori_loop(0, count, one, jnp.zeros_like(u))


def layer(x, lw, cfg, variant=SOUND, dense=False, held=None):
    """One layer on one sequence: x (T, D) float32."""
    eps = cfg["rms_norm_eps"]
    st = lambda a: _stored(a, variant)
    h = st(rmsnorm(x, lw["norm1"], eps))
    a = attention(h, lw, cfg, variant)
    x = st(x + st(_ein("te,ed->td", a, lw["wo"], variant.quant)))
    u = st(rmsnorm(x, lw["norm2"], eps))
    if dense:
        return st(x + _swiglu(u, lw["w1"], lw["w3"], lw["w2"], variant))
    y = experts(u, lw, cfg, variant, held)
    if variant.shared:
        y = y + _swiglu(u, lw["shared_w1"], lw["shared_w3"],
                        lw["shared_w2"], variant)
    return st(x + st(y))


def logits(weights, tokens, cfg, variant=SOUND, held=None):
    """Full forward of one sequence from a whole weight tree (small
    sizes): (T,) int -> (T, V) float32."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, lw in enumerate(weights["layers"]):
        x = layer(x, lw, cfg, variant, is_dense(cfg, i), held)
    x = _stored(rmsnorm(x, weights["final_norm"], cfg["rms_norm_eps"]),
                variant)
    return _stored(_ein("td,dv->tv", x, weights["head"], variant.quant),
                   variant)


# --------------------------------------------------------------------- #
# the check, walking the layers
# --------------------------------------------------------------------- #
def _freeze(cfg):
    """The configuration as a hashable static argument."""
    return json.dumps(cfg, sort_keys=True)


_thaw = json.loads


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed_rows(key, seq, cfg_items, dtype):
    cfg = _thaw(cfg_items)
    return jnp.take(make_embed(cfg, key, dtype), seq,
                    axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(0,))
def _layer_from_key(x, key, i, cfg_items, dtype, variant, dense):
    """Layer i's weights made in the configuration's dtype, upcast, and
    applied: only one layer's float32 weights are ever live (3.8 GB at
    the published widths).  `dense` is static, `i` is not: five layers of
    two kinds compile twice."""
    cfg = _thaw(cfg_items)
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make_layer(cfg, key, i, dtype, dense))
    return layer(x, lw, cfg, variant, dense)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head_rows(x_rows, key, cfg_items, dtype, variant):
    cfg = _thaw(cfg_items)
    w = make_head(cfg, key, dtype)
    x = _stored(rmsnorm(x_rows, w["final_norm"], cfg["rms_norm_eps"]),
                variant)
    return _stored(_ein("td,dv->tv", x, w["head"], variant.quant), variant)


def served_logits(cfg, key, seq, lo, hi, variant=SOUND):
    """Reference logits (hi - lo, V) at positions lo..hi-1 of the padded
    sequence `seq` (T,), from the seed's weights, a layer at a time."""
    items, dtype = _freeze(cfg), jnp.dtype(cfg["param_dtype"])
    x = _embed_rows(key, seq, items, dtype)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_from_key(x, key, jnp.int32(i), items, dtype, variant,
                            is_dense(cfg, i))
    return _head_rows(x[lo:hi], key, items, dtype, variant)


def choice_gaps(cfg, key, sequence, n_prompt, variants, pad_to=512,
                noise=(), noise_rows=0):
    """One served sequence (prompt, then the served tokens) against the
    sound reference: at each served position, how far below the
    reference's best logit lies the logit of the token that was served
    (under "served"), and of the token that the reference puts first when
    it is computed as each of `variants` {name: Variant} says, teacher-
    forced on the same sequence (a control need not decode).  A variant
    named in `noise` is read at the last `noise_rows` positions of the
    sequence (never fewer than the served ones; the prompt's end where the
    reply is shorter).  The sequence is padded to a multiple of `pad_to`
    (causal: padding changes nothing before it).  -> {name: gaps
    [n_served, or the rows of a `noise` variant]}."""
    n = len(sequence)
    padded = -(-n // pad_to) * pad_to
    seq = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(sequence, jnp.int32))
    lo = max(min(n_prompt - 1, n - 1 - noise_rows), 0) if noise \
        else n_prompt - 1
    rows = served_logits(cfg, key, seq, lo, n - 1)
    best = rows.max(-1)
    served = slice(n_prompt - 1 - lo, None)

    def below(tokens):
        return best - jnp.take_along_axis(rows, tokens[:, None], -1)[:, 0]
    # row i predicts token i + 1
    out = {"served": below(seq[lo + 1:n])[served]}
    for name, variant in variants.items():
        gaps = below(jnp.argmax(served_logits(
            cfg, key, seq, lo, n - 1, variant), -1))
        out[name] = gaps if name in noise else gaps[served]
    return jax.device_get(out)
