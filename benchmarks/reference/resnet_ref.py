"""Plain float32 reference of ResNet (bottleneck, shortcut type B) training.

Straight `jax.numpy` / `lax.conv_general_dilated` at "highest" precision;
imports nothing of the program.  He et al. 2015 Table 1 with the stride on
the 3x3 conv of a bottleneck (ResNet.scala does the same), BatchNorm on
batch statistics (biased variance, eps from the configuration), 3x3/2 max
pool with padding 1, 7x7 average pool, linear classifier, log-softmax and
the mean negative log-likelihood of 1-based labels.  NHWC activations,
OIHW kernels.

`quant` is the control's hook: applied to both operands of every
convolution and of the classifier (see lm_ref.fp8).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.lm_ref import flat, leaf_norms   # tree helpers

HIGHEST = lax.Precision.HIGHEST


def block_plan(cfg):
    """[(c_in, width, stride, has_projection)] in forward order."""
    plan, c_in = [], cfg["base_width"]
    exp = cfg["bottleneck_expansion"]
    for si, count in enumerate(cfg["stages"]):
        n = cfg["base_width"] * 2 ** si
        for bi in range(count):
            stride = 2 if (bi == 0 and si > 0) else 1
            plan.append((c_in, n, stride, c_in != n * exp))
            c_in = n * exp
    return plan


def make_weights(cfg, key):
    """Every parameter from one key, float32: conv N(0, sqrt(2 / fan_in)),
    fc N(0, 0.01), BN scale 1 and shift 0, fc bias 0."""
    counter = [0]

    def conv(c_out, c_in, k):
        counter[0] += 1
        std = (2.0 / (c_in * k * k)) ** 0.5
        return jax.random.normal(jax.random.fold_in(key, counter[0]),
                                 (c_out, c_in, k, k), jnp.float32) * std

    def bn(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "shift": jnp.zeros((c,), jnp.float32)}

    exp, base = cfg["bottleneck_expansion"], cfg["base_width"]
    w = {"conv1": conv(base, 3, 7), "bn1": bn(base), "blocks": []}
    for c_in, n, _, proj in block_plan(cfg):
        b = {"c1": conv(n, c_in, 1), "b1": bn(n),
             "c2": conv(n, n, 3), "b2": bn(n),
             "c3": conv(n * exp, n, 1), "b3": bn(n * exp)}
        if proj:
            b["sc"], b["sb"] = conv(n * exp, c_in, 1), bn(n * exp)
        w["blocks"].append(b)
    c_last = base * 2 ** (len(cfg["stages"]) - 1) * exp
    counter[0] += 1
    w["fc"] = {"weight": jax.random.normal(
        jax.random.fold_in(key, counter[0]), (cfg["class_num"], c_last),
        jnp.float32) * 0.01,
        "bias": jnp.zeros((cfg["class_num"],), jnp.float32)}
    return w


def make_pool(cfg, key, n_batches, batch, dtype=jnp.bfloat16):
    """The traffic's images and labels, made on the device from the seed:
    x (n, B, H, W, 3) uniform [0, 1) in `dtype`, y (n, B) 1-based float
    labels.  Every row differs, and batch i depends on (key, i) alone, so
    the reference makes only the batches it follows."""
    s = cfg["image_size"]

    def one(k):
        kx, ky = jax.random.split(k)
        x = jax.random.uniform(kx, (batch, s, s, 3), jnp.float32)
        y = jax.random.randint(ky, (batch,), 1, cfg["class_num"] + 1)
        return x.astype(dtype), y.astype(jnp.float32)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_batches))
    return jax.vmap(one)(keys)


def conv(x, w, stride, pad, quant):
    if quant is not None:
        x, w = quant(x), quant(w)
    out = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=HIGHEST)
    return getattr(quant, "cotangent", lambda y: y)(out)


def batchnorm(x, p, eps):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["shift"]


def bottleneck(x, b, stride, eps, quant):
    h = jax.nn.relu(batchnorm(conv(x, b["c1"], 1, 0, quant), b["b1"], eps))
    h = jax.nn.relu(batchnorm(conv(h, b["c2"], stride, 1, quant), b["b2"],
                              eps))
    h = batchnorm(conv(h, b["c3"], 1, 0, quant), b["b3"], eps)
    if "sc" in b:
        x = batchnorm(conv(x, b["sc"], stride, 0, quant), b["sb"], eps)
    return jax.nn.relu(h + x)


def log_probs(w, x, cfg, quant=None, remat=False):
    eps = cfg["bn_eps"]
    h = conv(x.astype(jnp.float32), w["conv1"], 2, 3, quant)
    h = jax.nn.relu(batchnorm(h, w["bn1"], eps))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for b, (_, _, stride, _) in zip(w["blocks"], block_plan(cfg)):
        f = functools.partial(bottleneck, stride=stride, eps=eps, quant=quant)
        h = (jax.checkpoint(f) if remat else f)(h, b)
    h = jnp.mean(h, (1, 2))                      # 7x7 average pool
    a, wt = (quant(h), quant(w["fc"]["weight"])) if quant else \
        (h, w["fc"]["weight"])
    logits = getattr(quant, "cotangent", lambda y: y)(
        jnp.matmul(a, wt.T, precision=HIGHEST)) + w["fc"]["bias"]
    return jax.nn.log_softmax(logits, -1)


def loss(w, x, y, cfg, quant=None, remat=True):
    lp = log_probs(w, x, cfg, quant, remat)
    idx = (y.astype(jnp.int32) - 1)[:, None]
    return -jnp.mean(jnp.take_along_axis(lp, idx, -1))


def train_reference(cfg, key, batches, opt, quant=None):
    """Follow the first len(batches) SGD steps from the seed's weights.
    `opt`: learning_rate, momentum, dampening (velocity = momentum * v +
    (1 - dampening) * g, the framework's Torch-style rule).  -> dict as
    lm_ref.train_reference."""
    lr, mom, damp = opt["learning_rate"], opt["momentum"], opt["dampening"]
    w0 = jax.jit(lambda k: make_weights(cfg, k))(key)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(w, vel, x, y):
        l, g = jax.value_and_grad(loss)(w, x, y, cfg, quant)
        vel = jax.tree_util.tree_map(
            lambda v, g_: mom * v + (1.0 - damp) * g_, vel, g)
        w = jax.tree_util.tree_map(lambda p, v: p - lr * v, w, vel)
        return w, vel, l, leaf_norms(g)

    w = jax.tree_util.tree_map(jnp.copy, w0)
    vel = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses, grad_norms = [], None
    for i, (x, y) in enumerate(batches):
        w, vel, l, gn = step(w, vel, x, y)
        losses.append(float(l))
        if i == 0:
            grad_norms = jax.device_get(gn)
    dn = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        lambda p, q: p - q, a, b)))(w, w0)
    return {"losses": losses, "grad_norms": flat(grad_norms),
            "dparam_norms": flat(jax.device_get(dn))}
