"""Plain float32 reference of the decoder-only LM (OLMo-style block).

Straight `jax.numpy`: no kernels, no cache, no batching tricks; imports
nothing of the program.  Weights come from `make_weights` (the benchmark
makes them from the seed and hands the same values to the program through
an adapter), the block is pre-norm RMSNorm -> rotary causal attention ->
residual -> RMSNorm -> SwiGLU -> residual, the head is the tied embedding.

Departures from allenai/OLMo-1B, all listed under `assumed` in the
configuration files: a parametric RMSNorm where OLMo has a non-parametric
LayerNorm (the program's block hard-codes it), and rotary pairs taken as
interleaved (2i, 2i+1) columns where the HF code pairs (i, i + d/2): with
seeded random weights the two differ by a fixed permutation of the columns
of wq and wk.

`quant` is the hook of the control: a function applied to both operands of
every matmul (None: float32 at "highest"), whose optional `cotangent`
attribute is applied to the matmul's result and acts on the backward.
`fp8` rounds operands through float8_e4m3fn and cotangents through
float8_e5m2, one scale per tensor: the precision below bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def make_weights(cfg, key, dtype=jnp.float32):
    """Every weight of the model from one key, in one traceable call.
    Matrices N(0, 0.02), norm scales 1 (`assumed` in the config files)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, n_layers = cfg["vocab_size"], cfg["num_hidden_layers"]
    std = cfg.get("initializer_range", 0.02)

    def mat(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    keys = jax.random.split(key, n_layers + 1)
    layers = []
    for i in range(n_layers):
        ks = jax.random.split(keys[i], 7)
        layers.append({
            "wq": mat(ks[0], (d, d)), "wk": mat(ks[1], (d, d)),
            "wv": mat(ks[2], (d, d)), "wo": mat(ks[3], (d, d)),
            "w1": mat(ks[4], (d, f)), "w3": mat(ks[5], (d, f)),
            "w2": mat(ks[6], (f, d)),
            "norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype)})
    return {"embed": mat(keys[-1], (v, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dtype)}


def fp8(x):
    """Fake-quantise to float8_e4m3fn with a per-tensor scale.  The
    gradient passes straight through: an unscaled cotangent cast to fp8
    underflows to zero, which no fp8 training path would do."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


def _fp8_cotangent_bwd(_, g):
    g = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / 57344.0
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale,)


_fp8_cotangent.defvjp(lambda y: (y, None), _fp8_cotangent_bwd)
# what an fp8 training path does to the backward: the cotangent that
# enters a matmul's or convolution's transpose is rounded through
# float8_e5m2 with a per-tensor scale (the operands through e4m3, above)
fp8.cotangent = _fp8_cotangent


def bf16(x):
    """Round through bfloat16 (the control of a float32 configuration)."""
    x = x.astype(jnp.float32)
    return x + lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    out = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                     precision=HIGHEST)
    return getattr(quant, "cotangent", lambda y: y)(out)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """x (S, H, Dh), interleaved pairs, positions 0..S-1."""
    s, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def block(h, lw, cfg, quant):
    """One layer on one sequence: h (S, D) float32."""
    s, d = h.shape
    n_heads = cfg["num_attention_heads"]
    dh = d // n_heads
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = rmsnorm(h, lw["norm1"], eps)
    q = rope(_mm(x, lw["wq"], quant).reshape(s, n_heads, dh), theta)
    k = rope(_mm(x, lw["wk"], quant).reshape(s, n_heads, dh), theta)
    v = _mm(x, lw["wv"], quant).reshape(s, n_heads, dh)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))   # (H, S, Dh)
    scores = _mm(qh, jnp.swapaxes(kh, 1, 2), quant) * dh ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    o = jnp.swapaxes(_mm(probs, vh, quant), 0, 1).reshape(s, d)
    h = h + _mm(o, lw["wo"], quant)
    x = rmsnorm(h, lw["norm2"], eps)
    gate = jax.nn.silu(_mm(x, lw["w1"], quant)) * _mm(x, lw["w3"], quant)
    return h + _mm(gate, lw["w2"], quant)


def hidden(weights, tokens, cfg, quant=None, remat=False):
    """tokens (S,) -> final-normed hidden (S, D)."""
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    blk = functools.partial(block, cfg=cfg, quant=quant)
    if remat:
        blk = jax.checkpoint(blk)
    for lw in weights["layers"]:
        h = blk(h, lw)
    return rmsnorm(h, weights["final_norm"], cfg["rms_norm_eps"])


def logits(weights, tokens, cfg, quant=None):
    """Full forward of one sequence: (S,) int -> (S, V) float32."""
    return _mm(hidden(weights, tokens, cfg, quant), weights["embed"].T, quant)


def sequence_nll(weights, tokens, targets, cfg, quant=None, remat=True):
    """Sum of token NLLs of one sequence."""
    lg = _mm(hidden(weights, tokens, cfg, quant, remat),
             weights["embed"].T, quant)
    lse = jax.scipy.special.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


def loss_and_grads(weights, tokens, targets, cfg, quant=None):
    """Mean token NLL over a batch (B, S) and its gradients, one row at a
    time: a scan over rows whose body is rematerialised, so only one
    row's activations are ever live beside the weights and one gradient
    accumulator."""
    n_tok = tokens.shape[0] * tokens.shape[1]
    row = jax.checkpoint(
        lambda w, t, y: sequence_nll(w, t, y, cfg, quant, remat=True))

    def total(w):
        def body(acc, ty):
            return acc + row(w, *ty), None
        acc, _ = lax.scan(body, jnp.float32(0), (tokens, targets))
        return acc / n_tok

    return jax.value_and_grad(total)(weights)


def adamw_step(weights, grads, m, v, t, opt):
    """One AdamW step as the traffic file states it (bias-corrected,
    decay decoupled and applied to the old weights); t counts from 1."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    tm = jax.tree_util.tree_map
    m = tm(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = tm(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = tm(lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
             - lr * wd * p, weights, m, v)
    return new, m, v


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def train_reference(cfg, key, batches, opt, quant=None):
    """Follow the first len(batches) steps from the seed's weights.
    -> dict(losses [n], grad_norms {leaf: first gradient's norm},
    dparam_norms {leaf: norm of the parameters' change after all steps}).
    Each batch is (tokens, targets) int32 (B, S)."""
    make = lambda k: make_weights(cfg, k, jnp.float32)
    w = jax.jit(make)(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, tok, tgt, t):
        loss, g = loss_and_grads(w, tok, tgt, cfg, quant)
        gn = leaf_norms(g)
        w, m, v = adamw_step(w, g, m, v, t, opt)
        return w, m, v, loss, gn

    losses, grad_norms = [], None
    for i, (tok, tgt) in enumerate(batches):
        w, m, v, loss, gn = step(w, m, v, jnp.asarray(tok), jnp.asarray(tgt),
                                 jnp.float32(i + 1))
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(gn)
    del m, v
    # the seed's weights are made again inside the subtraction: a kept
    # copy would be a fifth model-sized buffer on the chip
    dnorm = jax.jit(lambda a, k: leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x - y, a, make(k))))(w, key)
    return {"losses": losses, "grad_norms": flat(grad_norms),
            "dparam_norms": flat(jax.device_get(dnorm))}


def _named(tree):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path), leaf


def flat(tree):
    """{'embed': .., 'layers.0.wq': ..} from the plain weight tree."""
    return {name: float(leaf) for name, leaf in _named(tree)}


def leaf_shapes(tree):
    """`flat`'s names, each with its leaf's shape."""
    return {name: tuple(leaf.shape) for name, leaf in _named(tree)}


def token_gaps(weights, sequence, n_prompt, cfg, quant=None, pad_to=128):
    """For each served token of `sequence` (prompt then served tokens), how
    far its float32 reference logit lies below the reference's best at
    that position.  With `quant`, the token judged at each position is
    the one the quantised forward puts first (the control need not
    decode).  The sequence is padded to a multiple of `pad_to` (causal:
    padding changes nothing before it) so few lengths compile.
    -> gaps [n_served]."""
    n = len(sequence)
    padded = -(-n // pad_to) * pad_to
    seq = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(sequence, jnp.int32))
    rows = _logits_jit(weights, seq, _freeze(cfg), None)[n_prompt - 1:n - 1]
    if quant is None:
        judged = seq[n_prompt:n]             # row i predicts token i + 1
    else:
        judged = jnp.argmax(_logits_jit(weights, seq, _freeze(cfg), quant)
                            [n_prompt - 1:n - 1], -1)
    got = jnp.take_along_axis(rows, judged[:, None], -1)[:, 0]
    return jax.device_get(jnp.max(rows, -1) - got)


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits_jit(weights, seq, cfg_items, quant):
    return logits(weights, seq, dict(cfg_items), quant)
