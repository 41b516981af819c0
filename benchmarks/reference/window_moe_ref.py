"""Plain float32 reference of a decoder whose layers are of two kinds,
sliding-window attention with rope and global attention without, each
followed by routed ReGLU experts whose router reads the block's INPUT
(SmallThinker-21BA3B-Instruct: 28 query / 4 KV heads x 128, window 4,096,
64 experts x 768, top-6, gates a softmax over the chosen logits; untied
head).

Straight `jax.numpy` in float32 under matmul precision "highest": no
kernels, no cache, no batching; imports nothing of the program.  Layer
`l` on one sequence, x (T, hidden), W the window:

    h   = RMSNorm(x; norm1)
    q   = h Wq -> (T, H, Dh)   k = h Wk -> (T, Hkv, Dh)   v = h Wv
    q,k = rope(t) of both            iff rope_layout[l] == 1
    a[t,hd] = sum_s softmax_s(q[t,hd] . k[s,hd // G] / sqrt(Dh)) v[s,hd // G]
              over s <= t, and s > t - W iff sliding_window_layout[l] == 1
    x'  = x + concat_hd(a) Wo
    u   = RMSNorm(x'; norm2)
    z   = h Wr  (E logits, float32: from h, NOT from u)
    E_t = the K largest z[t];  c[t,e] = softmax over z[t, E_t]
    out = x' + sum_{e in E_t} c[t,e] W2_e(relu(W1_e u) * (W3_e u))

then the final RMSNorm and the untied head.  No token is dropped.

Departures from the published description, each also under `assumed` in
the configuration file: no bias and no q/k norm (the config has no key
for either); the "primary + secondary experts / sparse ReGLU" of the
model card is an inference-time sparsity inside an expert that the config
gives no size for, and is left out: an expert is dense; rotary pairs are
interleaved (2i, 2i + 1), the program's convention; weights are seeded
N(0, initializer_range) with `init_qk_gain` on Wq and Wk (scores of
standard deviation about 2: attention peaked, so that a mask computed
wrongly shows), `init_embed_gain` on the embedding's rows (each position
led by its own token) and `init_router_gain` on the router (a token's
sixth gate small).

Queries are walked in blocks with the mask written out; the experts are a
loop over the experts in which every token carries its own gate for that
expert (zero where it did not choose it): the sum over a token's chosen
experts, at 64 / 6 of the work and none of the sorting.

`Variant` is the hook of the controls, each one way of computing the
model wrongly that the comparison has to catch: `quant` (a function
applied to both operands of every matmul: `fp8`, `bf16`), `stored` (a
function applied to every activation where the program stores one: the
configuration's own precision rounds both), `window` False
(the window dropped: window layers attend every earlier key),
`rope_global` True (rope applied on the global layers too),
`router_input` "mlp" (the router reads u), `act` "silu", and `stale` =
(page_size, ring_pages): a recycled page's stale rows attended, that is,
beside its window a query at t sees the rows of the page `ring_pages`
pages behind its own that lie past its own row (what a ring of
`ring_pages` pages still holds there when the mask goes by column).
`choice_gaps` judges the served tokens, and each variant's own first
choices, by the sound reference's logits; the reference in the
configuration's own precision (`OWN_PRECISION`, the yardstick of the
served tokens' gaps) is read at `noise_rows` positions of the sequence,
not at the served ones alone.
"""
import collections
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 128

Variant = collections.namedtuple(
    "Variant", "quant window rope_global router_input act stale stored",
    defaults=(None, True, False, "block", "relu", None, None))
SOUND = Variant()


def dims(cfg):
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                e=cfg["moe_num_primary_experts"],
                k=cfg["moe_num_active_primary_experts"],
                f=cfg["moe_ffn_hidden_size"], v=cfg["vocab_size"],
                w=cfg["sliding_window_size"],
                n_layers=cfg["num_hidden_layers"])


# --------------------------------------------------------------------- #
# weights, a layer at a time
# --------------------------------------------------------------------- #
def _mat(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_layer(cfg, key, i, dtype=jnp.float32):
    """Layer i's weights, laid out as the program stores them (experts
    stacked on a leading axis)."""
    m = dims(cfg)
    std = cfg.get("initializer_range", 0.02)
    ks = jax.random.split(jax.random.fold_in(key, i), 8)
    d, dh, e, f = m["d"], m["dh"], m["e"], m["f"]
    qk = std * cfg.get("init_qk_gain", 1.0)
    return {
        "wq": _mat(ks[0], (d, m["h"] * dh), qk, dtype),
        "wk": _mat(ks[1], (d, m["hkv"] * dh), qk, dtype),
        "wv": _mat(ks[2], (d, m["hkv"] * dh), std, dtype),
        "wo": _mat(ks[3], (m["h"] * dh, d), std, dtype),
        "router": _mat(ks[4], (d, e), std * cfg.get("init_router_gain", 1.0),
                       dtype),
        "w1": _mat(ks[5], (e, d, f), std, dtype),
        "w3": _mat(ks[6], (e, d, f), std, dtype),
        "w2": _mat(ks[7], (e, f, d), std, dtype),
        "norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype)}


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


def make_weights(cfg, key, dtype=jnp.float32):
    """Every weight at once: small sizes only (3.97 B parameters in
    float32 are 15.9 GB at the configuration's own)."""
    return dict(make_head(cfg, key, dtype),
                embed=make_embed(cfg, key, dtype),
                layers=[make_layer(cfg, key, i, dtype)
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
    `lax.reduce_precision`, which a compiler may not remove.  A convert
    down and up again is one that XLA is allowed to drop
    (`xla_allow_excess_precision`) and on the TPU partly does: a reference
    rounded so keeps float32 in places and reads about half the noise
    (PERF.md section 6, PR 32).  On the CPU the two give the same values,
    bit for bit."""
    return lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                mantissa_bits=7)


def controls(page_size, ring_pages):
    """The six ways of computing the model wrongly that the cell's
    comparison has to catch; the last needs the served engine's page size
    and ring width."""
    return {"fp8": Variant(quant=fp8),
            "no_window": Variant(window=False),
            "rope_on_global": Variant(rope_global=True),
            "router_reads_u": Variant(router_input="mlp"),
            "silu": Variant(act="silu"),
            "stale_rows": Variant(stale=(int(page_size), int(ring_pages)))}


# the reference in the configuration's own precision: matmul operands in
# `param_dtype` and every activation stored in `activation_dtype`, both
# bfloat16 (the stream after each residual add, the norms' outputs, q, k
# and v before and after rope, an expert's hidden row, the logits)
OWN_PRECISION = Variant(quant=bf16, stored=bf16)


def _ein(spec, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _stored(x, variant):
    """An activation as the variant stores it (`stored`: a rounding, or
    None for float32)."""
    return x if variant.stored is None else variant.stored(x)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """x (T, heads, D), interleaved pairs, positions 0..T-1."""
    t, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def layer_kind(cfg, i):
    """(windowed, roped) of layer i, from the configuration's layouts."""
    return (bool(cfg["sliding_window_layout"][i]),
            bool(cfg["rope_layout"][i]))


def attention(h, lw, cfg, variant, windowed, roped):
    """h (T, D), the block's normed input -> concat_hd(a) (T, H * Dh)."""
    m, quant = dims(cfg), variant.quant
    t = h.shape[0]
    nh, hkv, dh, w = m["h"], m["hkv"], m["dh"], m["w"]
    st = lambda a: _stored(a, variant)
    q = st(_ein("td,de->te", h, lw["wq"], quant)).reshape(t, nh, dh)
    k = st(_ein("td,de->te", h, lw["wk"], quant)).reshape(t, hkv, dh)
    v = st(_ein("td,de->te", h, lw["wv"], quant)).reshape(t, hkv, dh)
    if roped or variant.rope_global:
        q, k = st(rope(q, cfg["rope_theta"])), st(rope(k, cfg["rope_theta"]))
    k_pos = jnp.arange(t)

    def block(args):
        qb, pos = args                              # a block of queries
        s, p = k_pos[None, :], pos[:, None]
        seen = s <= p
        if windowed and variant.window:
            seen = seen & (s > p - w)
            if variant.stale is not None:
                page, ring = variant.stale
                seen = seen | ((s // page == p // page - ring)
                               & (s % page > p % page))
        qg = qb.reshape(-1, hkv, nh // hkv, dh)
        sc = _ein("qkgd,skd->kgqs", qg, k, quant) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return st(_ein("kgqs,skd->qkgd", pr, v, quant)).reshape(-1, nh * dh)

    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    n = t // blk
    out = lax.map(block, (q.reshape(n, blk, nh, dh), k_pos.reshape(n, blk)))
    return out.reshape(t, nh * dh)


def route(r, lw, cfg):
    """r (T, D), what the router reads -> (expert ids (T, K), gates (T, K)):
    the K largest logits, and the softmax over those K."""
    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    z = _ein("td,de->te", r, lw["router"], None)
    top, idx = lax.top_k(z, dims(cfg)["k"])
    return idx, jax.nn.softmax(top, -1)


def experts(u, r, lw, cfg, variant):
    """sum_e c[t,e] W2_e(act(W1_e u) * W3_e u) over each token's chosen
    experts, routed on r: a loop over the experts, every token with its
    own gate for the expert at hand (zero: not chosen)."""
    quant = variant.quant
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[variant.act]
    idx, gate = route(r, lw, cfg)

    st = lambda a: _stored(a, variant)

    def one(e, acc):
        c = jnp.where(idx == e, gate, 0.0).sum(-1)            # (T,)
        hid = st(act(st(_ein("td,df->tf", u, lw["w1"][e], quant)))
                 * st(_ein("td,df->tf", u, lw["w3"][e], quant)))
        y = st(_ein("tf,fd->td", hid, lw["w2"][e], quant))
        return acc + c[:, None] * y

    return st(lax.fori_loop(0, dims(cfg)["e"], one, jnp.zeros_like(u)))


def layer(x, lw, cfg, variant=SOUND, windowed=False, roped=True):
    """One layer on one sequence: x (T, D) float32."""
    eps = cfg["rms_norm_eps"]
    st = lambda a: _stored(a, variant)
    h = st(rmsnorm(x, lw["norm1"], eps))
    a = attention(h, lw, cfg, variant, windowed, roped)
    x = st(x + st(_ein("te,ed->td", a, lw["wo"], variant.quant)))
    u = st(rmsnorm(x, lw["norm2"], eps))
    r = h if variant.router_input == "block" else u
    return st(x + experts(u, r, lw, cfg, variant))


def logits(weights, tokens, cfg, variant=SOUND):
    """Full forward of one sequence from a whole weight tree (small
    sizes): (T,) int -> (T, V) float32."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, lw in enumerate(weights["layers"]):
        x = layer(x, lw, cfg, variant, *layer_kind(cfg, i))
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
def _layer_from_key(x, key, i, cfg_items, dtype, variant, kind):
    """Layer i's weights made in the configuration's dtype, upcast, and
    applied: only one layer's float32 weights are ever live (1.6 GB at
    the published widths).  `kind` = (windowed, roped) is static, `i` is
    not: eight layers of two kinds compile twice."""
    cfg = _thaw(cfg_items)
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make_layer(cfg, key, i, dtype))
    return layer(x, lw, cfg, variant, *kind)


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
                            layer_kind(cfg, i))
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
    reply is shorter): the reference's own rounding noise, which the
    served tokens' gaps are set against, changes one first choice in
    sixteen, and over a reply of 145 tokens that is a mean of nine gaps.
    The sequence is padded to a multiple of `pad_to` (causal: padding
    changes nothing before it).  -> {name: gaps [n_served, or the rows of
    a `noise` variant]}."""
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
