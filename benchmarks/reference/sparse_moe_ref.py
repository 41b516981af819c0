"""Plain float32 reference of a decoder whose every layer is grouped-query
attention under a learned top-k sparse-attention indexer, then routed
experts (Keye-VL-2.0-30B-A3B's language model: 32 query / 4 KV heads x
128, indexer 16 heads x 64 against one index key a token, top-k 2048;
128 experts x 768, top-8, gates renormalised, no shared expert; untied
head).

Straight `jax.numpy` in float32 under matmul precision "highest": no
kernels, no cache, no batching; imports nothing of the program.  One
layer on one sequence, h (T, hidden):

    x   = RMSNorm(h; norm1)
    q   = x Wq -> (T, H, Dh)   k = x Wk -> (T, Hkv, Dh)   v = x Wv
    q,k = RMSNorm over Dh of each head (q_norm, k_norm), then rope(t)
    qI  = x Wqi -> (T, Hi, Di)   kI = LayerNorm_Di(x Wki) -> (T, Di)
    w   = x Wwi -> (T, Hi);  rope on qI, kI
    I[t,s] = sum_j w[t,j] Hi^-1/2 ReLU(qI[t,j] . kI[s]) Di^-1/2,  s <= t
    S_t = the min(t + 1, topk) positions s <= t with the largest I[t,s]
    a[t,hd] = sum_{s in S_t} softmax_{S_t}(q[t,hd] . k[s,hd // G] / sqrt(Dh)) v[s,hd // G]
    h'  = h + concat_hd(a) Wo
    y   = RMSNorm(h'; norm2);  p = softmax_E(y Wr);  E_t = top-K of p[t]
    c[t,e] = p[t,e] / sum_{e' in E_t} p[t,e']
    h'' = h' + sum_{e in E_t} c[t,e] W2_e(silu(W1_e y) * W3_e y)

Departures from the published description, each also under `assumed` in
the configuration file:
  * per-head q/k RMSNorm: the family's convention; `config` has no key;
  * the indexer's three projections read the block's normed input x
    (DeepSeek-V3.2's reads a q latent, which a GQA model has not);
    LayerNorm (eps 1e-6, scale and bias) on kI and rope on qI and kI as
    in V3.2's published inference code; the two scale factors in I;
  * `q_chunk_size` / `kv_chunk_size` are tile sizes and change no value;
  * `mrope_section` with text-only traffic gives all three position
    streams the same t: plain rope; rotary pairs are interleaved
    (2i, 2i + 1), the program's convention;
  * weights: seeded N(0, initializer_range) matrices; norm scales 1
    except the q/k-norm gains (`init_qk_norm_gain`), which make the
    attention softmax peaked: under flat attention a missing or wrong
    selection would hide inside the tolerance of the comparison.  The
    embedding's rows are scaled by `init_embed_gain` (each position led
    by its own token: at 1, six seeded layers collapse every position
    onto one common vector) and the router's by `init_router_gain` (a
    token's eighth gate small, as trained routers have it).

Queries are walked in blocks so a 32k-token sequence fits; experts are
evaluated over (token, expert) pairs sorted by expert, a slab of rows at
a time (the same mathematics as a per-token loop over its chosen
experts; a dense pass of every token through all experts would be
sixteen times the work).

`Variant` is the hook of the controls: `quant` (a function applied to
both operands of every matmul: `fp8`, `bf16`), `select` ("topk": the
model; "dense": the selection left out; "random": top-k of random
scores) and `renorm` (False: gates not renormalised).  `choice_gaps`
judges the served tokens, and each variant's own first choices, by the
sound reference's logits.
"""
import collections
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 128
SLAB = 256

Variant = collections.namedtuple("Variant", "quant select renorm",
                                 defaults=(None, "topk", True))
SOUND = Variant()


def dims(cfg):
    sa = cfg["sa_config"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
                topk=sa["topk"], e=cfg["num_experts"],
                k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
                v=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"])


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
    ks = jax.random.split(jax.random.fold_in(key, i), 11)
    d, dh, e, f = m["d"], m["dh"], m["e"], m["f"]
    gain = jnp.full((dh,), cfg.get("init_qk_norm_gain", 1.0), dtype)
    return {
        "wq": _mat(ks[0], (d, m["h"] * dh), std, dtype),
        "wk": _mat(ks[1], (d, m["hkv"] * dh), std, dtype),
        "wv": _mat(ks[2], (d, m["hkv"] * dh), std, dtype),
        "wo": _mat(ks[3], (m["h"] * dh, d), std, dtype),
        "q_norm": gain, "k_norm": gain,
        "wqi": _mat(ks[4], (d, m["hi"] * m["di"]), std, dtype),
        "wki": _mat(ks[5], (d, m["di"]), std, dtype),
        "wwi": _mat(ks[6], (d, m["hi"]), std, dtype),
        "ki_norm": jnp.ones((m["di"],), dtype),
        "ki_bias": jnp.zeros((m["di"],), dtype),
        "router": _mat(ks[7], (d, e), std * cfg.get("init_router_gain", 1.0),
                       dtype),
        "w1": _mat(ks[8], (e, d, f), std, dtype),
        "w3": _mat(ks[9], (e, d, f), std, dtype),
        "w2": _mat(ks[10], (e, f, d), std, dtype),
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
    """Every weight at once: small sizes only (4.37 B parameters in
    float32 are 17.5 GB at the configuration's own)."""
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
    """Round through bfloat16: the configuration's own precision."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# the reference in the configuration's own precision, and the four ways
# of computing it wrongly that the comparison has to catch
OWN_PRECISION = Variant(quant=bf16)
CONTROLS = {"fp8": Variant(quant=fp8), "dense": Variant(select="dense"),
            "random": Variant(select="random"),
            "no_renorm": Variant(renorm=False)}


def _ein(spec, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def layernorm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True)
                                + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def rope(x, theta):
    """x (T, heads, D), interleaved pairs, positions 0..T-1."""
    t, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(x, lw, cfg, variant, layer_index=0):
    """x (T, D), the block's normed input -> concat_hd(a) (T, H * Dh)."""
    m, quant = dims(cfg), variant.quant
    t = x.shape[0]
    h, hkv, dh, hi, di = m["h"], m["hkv"], m["dh"], m["hi"], m["di"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _ein("td,de->te", x, lw["wq"], quant).reshape(t, h, dh)
    k = _ein("td,de->te", x, lw["wk"], quant).reshape(t, hkv, dh)
    v = _ein("td,de->te", x, lw["wv"], quant).reshape(t, hkv, dh)
    q = rope(rmsnorm(q, lw["q_norm"], eps), theta)
    k = rope(rmsnorm(k, lw["k_norm"], eps), theta)
    qi = rope(_ein("td,de->te", x, lw["wqi"], quant).reshape(t, hi, di),
              theta)
    ki = rope(layernorm(_ein("td,de->te", x, lw["wki"], quant),
                        lw["ki_norm"], lw["ki_bias"])[:, None], theta)[:, 0]
    w = _ein("td,de->te", x, lw["wwi"], quant)
    topk = min(m["topk"], t)
    k_pos = jnp.arange(t)
    noise = jax.random.fold_in(jax.random.PRNGKey(7), layer_index)

    def block(args):
        qb, qib, wb, pos = args                     # a block of queries
        visible = k_pos[None, :] <= pos[:, None]
        if variant.select == "dense":
            chosen = visible
        else:
            if variant.select == "random":
                score = jax.random.uniform(
                    jax.random.fold_in(noise, pos[0]), (qb.shape[0], t))
            else:
                s = jax.nn.relu(_ein("qjd,sd->qjs", qib, ki, quant)) \
                    * di ** -0.5
                score = jnp.einsum("qjs,qj->qs", s, wb * hi ** -0.5,
                                   precision=HIGHEST)
            score = jnp.where(visible, score, -jnp.inf)
            # lax.top_k is exact and takes equal scores from the lowest
            # position up; with fewer than topk visible it returns masked
            # positions too, which `visible` takes out again
            _, best = lax.top_k(score, topk)
            chosen = jnp.zeros_like(visible).at[
                jnp.arange(qb.shape[0])[:, None], best].set(True) & visible
        qg = qb.reshape(-1, hkv, h // hkv, dh)
        sc = _ein("qkgd,skd->kgqs", qg, k, quant) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None, None], sc, -jnp.inf), -1)
        return _ein("kgqs,skd->qkgd", p, v, quant).reshape(-1, h * dh)

    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    n = t // blk
    out = lax.map(block, (q.reshape(n, blk, h, dh),
                          qi.reshape(n, blk, hi, di), w.reshape(n, blk, hi),
                          k_pos.reshape(n, blk)))
    return out.reshape(t, h * dh)


def route(y, lw, cfg, variant):
    """y (T, D) -> (expert ids (T, K), gates (T, K)) in float32."""
    p = jax.nn.softmax(_ein("td,de->te", y, lw["router"], None), -1)
    gate, idx = lax.top_k(p, dims(cfg)["k"])
    if variant.renorm:
        gate = gate / gate.sum(-1, keepdims=True)
    return idx, gate


def experts(y, lw, cfg, variant):
    """sum_e c[t,e] W2_e(silu(W1_e y) * W3_e y) over each token's chosen
    experts: pairs sorted by expert, a slab of an expert's rows at a
    time."""
    m, quant = dims(cfg), variant.quant
    t, d = y.shape
    idx, gate = route(y, lw, cfg, variant)
    pair_e = idx.reshape(-1)
    n_pairs = pair_e.shape[0]
    order = jnp.argsort(pair_e, stable=True)
    xs = jnp.take(y, order // m["k"], axis=0)
    load = jnp.bincount(pair_e, length=m["e"])
    first = jnp.cumsum(load) - load
    slab = min(SLAB, n_pairs)

    def one_expert(e, out):
        w1, w3, w2 = lw["w1"][e], lw["w3"][e], lw["w2"][e]

        def one_slab(j, out):
            lo = first[e] + j * slab
            at = jnp.minimum(lo, n_pairs - slab)       # stay inside
            x = lax.dynamic_slice_in_dim(xs, at, slab)
            y_ = _ein("rf,fd->rd", jax.nn.silu(_ein("rd,df->rf", x, w1, quant))
                      * _ein("rd,df->rf", x, w3, quant), w2, quant)
            rows = at + jnp.arange(slab)
            mine = (rows >= lo) & (rows < first[e] + load[e])
            old = lax.dynamic_slice_in_dim(out, at, slab)
            return lax.dynamic_update_slice_in_dim(
                out, jnp.where(mine[:, None], y_, old), at, 0)

        return lax.fori_loop(0, -(-load[e] // slab), one_slab, out)

    ys = lax.fori_loop(0, m["e"], one_expert,
                       jnp.zeros((n_pairs, d), jnp.float32))
    ys = ys * gate.reshape(-1)[order][:, None]
    return jnp.zeros((t, d), jnp.float32).at[order // m["k"]].add(ys)


def layer(h, lw, cfg, variant=SOUND, layer_index=0):
    """One layer on one sequence: h (T, D) float32."""
    eps = cfg["rms_norm_eps"]
    a = attention(rmsnorm(h, lw["norm1"], eps), lw, cfg, variant,
                  layer_index)
    h = h + _ein("te,ed->td", a, lw["wo"], variant.quant)
    return h + experts(rmsnorm(h, lw["norm2"], eps), lw, cfg, variant)


def logits(weights, tokens, cfg, variant=SOUND):
    """Full forward of one sequence from a whole weight tree (small
    sizes): (T,) int -> (T, V) float32."""
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, lw in enumerate(weights["layers"]):
        h = layer(h, lw, cfg, variant, i)
    h = rmsnorm(h, weights["final_norm"], cfg["rms_norm_eps"])
    return _ein("td,dv->tv", h, weights["head"], variant.quant)


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


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0,))
def _layer_from_key(h, key, i, cfg_items, dtype, variant):
    """Layer i's weights made in the configuration's dtype, upcast, and
    applied: only one layer's float32 weights are ever live."""
    cfg = _thaw(cfg_items)
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make_layer(cfg, key, i, dtype))
    return layer(h, lw, cfg, variant, i)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head_rows(h_rows, key, cfg_items, dtype, variant):
    cfg = _thaw(cfg_items)
    w = make_head(cfg, key, dtype)
    h = rmsnorm(h_rows, w["final_norm"], cfg["rms_norm_eps"])
    return _ein("td,dv->tv", h, w["head"], variant.quant)


def served_logits(cfg, key, seq, lo, hi, variant=SOUND):
    """Reference logits (hi - lo, V) at positions lo..hi-1 of the padded
    sequence `seq` (T,), from the seed's weights, a layer at a time."""
    items, dtype = _freeze(cfg), jnp.dtype(cfg["param_dtype"])
    h = _embed_rows(key, seq, items, dtype)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer_from_key(h, key, jnp.int32(i), items, dtype, variant)
    return _head_rows(h[lo:hi], key, items, dtype, variant)


def choice_gaps(cfg, key, sequence, n_prompt, variants, pad_to=512):
    """One served sequence (prompt, then the served tokens) against the
    sound reference: at each served position, how far below the
    reference's best logit lies the logit of the token that was served
    (under "served"), and of the token that the reference puts first when
    it is computed as each of `variants` {name: Variant} says, teacher-
    forced on the same sequence (a control need not decode).  The
    sequence is padded to a multiple of `pad_to` (causal: padding changes
    nothing before it).  -> {name: gaps [n_served]}."""
    n = len(sequence)
    padded = -(-n // pad_to) * pad_to
    seq = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(sequence, jnp.int32))
    rows = served_logits(cfg, key, seq, n_prompt - 1, n - 1)
    best = rows.max(-1)

    def below(tokens):
        return best - jnp.take_along_axis(rows, tokens[:, None], -1)[:, 0]
    out = {"served": below(seq[n_prompt:n])}  # row i predicts token i + 1
    for name, variant in variants.items():
        out[name] = below(jnp.argmax(served_logits(
            cfg, key, seq, n_prompt - 1, n - 1, variant), -1))
    return jax.device_get(out)
