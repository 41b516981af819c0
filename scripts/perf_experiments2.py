"""Round-3 follow-up perf experiments (run on the real TPU).

perf_experiments.py established (v5e, ResNet-50 NHWC bf16, batch 256):
  threaded full step   98.98 ms  2586 img/s   (the honest protocol)
  fwd only             27.35 ms  (the number r2 mislabeled "full step")
  bare-conv fwd floor  ~19.2 ms  (51.6% MFU on the distinct conv shapes)

This suite hunts the remaining 3x between the threaded step and 3x the
conv floor:

  E  batch sweep of the threaded full step: 256 / 512 / 1024
  F  BN ablation: full step with BatchNorm replaced by bias-add
     (isolates the BN fwd+bwd + fp32-stat cost)
  G  complete fwd+bwd (ALL grads consumed — no DCE) vs update-included
     threaded step (isolates the optimizer-update cost)
  H  conv floor at batch 512 (does the MXU floor improve with batch?)
"""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
from jax import lax                                        # noqa: E402

from bigdl_tpu import nn                                   # noqa: E402
from bigdl_tpu.models import resnet                        # noqa: E402
from bigdl_tpu.optim import SGD                            # noqa: E402
from bigdl_tpu.optim.optimizer import make_train_step      # noqa: E402
from bigdl_tpu.nn.module import Ctx                        # noqa: E402
from bigdl_tpu.observability.profile import specs          # noqa: E402

# MFU denominator from the one peak table; no TPU or an unknown device
# kind is an error here, never a default
PEAK_FLOPS = specs.require_chip()[1].peak_flops


def lat():
    ones = jnp.ones(4)
    ls = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jnp.sum(ones))
        ls.append(time.perf_counter() - t0)
    return float(np.median(ls))


def _mix(x, c):
    return x + (c * 1e-30).astype(x.dtype)


def timeit_carry(fn, carry, args, k=10, trials=3):
    @jax.jit
    def many(carry, *a):
        def body(c, i):
            return fn(c, i, *a)
        return lax.scan(body, carry, jnp.arange(k))

    carry, losses = many(carry, *args)
    float(jnp.sum(losses))
    l = lat()
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        carry, losses = many(carry, *args)
        float(jnp.sum(losses))
        ts.append((time.perf_counter() - t0 - l) / k)
    return float(np.median(ts))


def timeit_inv(fn, args, k=10, trials=3):
    @jax.jit
    def many(*a):
        def body(c, i):
            return fn(c, *a), jnp.float32(0)
        carry, _ = lax.scan(body, jnp.float32(0), jnp.arange(k))
        return carry

    float(many(*args))
    l = lat()
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(many(*args))
        ts.append((time.perf_counter() - t0 - l) / k)
    return float(np.median(ts))


def setup(batch=256, fmt="NHWC", bn=True):
    if bn:
        model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                             format=fmt)
    else:
        orig = resnet._Builder.bn
        resnet._Builder.bn = lambda self, n: nn.Identity()
        try:
            model = resnet.build(class_num=1000, depth=50,
                                 dataset="imagenet", format=fmt)
        finally:
            resnet._Builder.bn = orig
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init_params(0)
    opt_state = method.init_state(params)
    rng = np.random.RandomState(0)
    shape = (batch, 3, 224, 224) if fmt == "NCHW" else (batch, 224, 224, 3)
    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 1001, batch).astype(np.float32))
    return model, criterion, method, params, state, opt_state, x, y


def _threaded(model, criterion, method, params, state, opt_state, x, y,
              k=10):
    step = make_train_step(model, criterion, method, mixed_precision=True)
    key = jax.random.PRNGKey(0)

    def thr(carry, i, xx, yy):
        p, o, s = carry
        p, o, s, loss = step(p, o, s, xx, yy, key)
        return (p, o, s), loss

    return timeit_carry(thr, (params, opt_state, state), (x, y), k=k)


def exp_E():
    for batch in (256, 512, 1024):
        try:
            args = setup(batch)
            t = _threaded(*args, k=8)
            print(f"E threaded b{batch:<5d}: {t*1e3:7.2f} ms  "
                  f"{batch/t:8.0f} img/s  "
                  f"({batch*12.3e9/t/PEAK_FLOPS*100:4.1f}% MFU)", flush=True)
        except Exception as e:
            print(f"# E b{batch} FAILED: {type(e).__name__}: {e}",
                  flush=True)


def exp_F(batch=256):
    """BatchNorm cost: swap each BN for a per-channel scale+bias (CAdd-
    style affine with no statistics), same conv structure."""
    args = setup(batch, bn=False)
    t = _threaded(*args, k=10)
    print(f"F no-BN threaded: {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)


def exp_G(batch=256):
    """Complete fwd+bwd: consume EVERY gradient leaf (no DCE), no update."""
    model, criterion, method, params, state, opt_state, x, y = setup(batch)
    xb = x.astype(jnp.bfloat16)

    def fwdbwd_all(c, p, s, xx, yy):
        def loss_fn(pp):
            ctx = Ctx(state=s, training=True, rng_key=jax.random.PRNGKey(0))
            out = model.apply(pp, _mix(xx, c), ctx)
            return criterion.loss(out.astype(jnp.float32), yy)
        l, g = jax.value_and_grad(loss_fn)(p)
        tot = l
        for leaf in jax.tree_util.tree_leaves(g):
            tot = tot + jnp.sum(leaf.astype(jnp.float32)) * 1e-30
        return tot

    t = timeit_inv(fwdbwd_all, (params, state, xb, y))
    print(f"G fwd+bwd(all) : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)


R50_CONVS = [
    (64, 3, 7, 7, 2, 224, 1),
    (64, 64, 1, 1, 1, 56, 1), (64, 64, 3, 3, 1, 56, 3),
    (64, 256, 1, 1, 1, 56, 2), (256, 64, 1, 1, 1, 56, 3),
    (128, 256, 1, 1, 2, 56, 1), (512, 256, 1, 1, 2, 56, 1),
    (128, 128, 3, 3, 1, 28, 4), (512, 128, 1, 1, 1, 28, 4),
    (128, 512, 1, 1, 1, 28, 3),
    (256, 512, 1, 1, 2, 28, 1), (1024, 512, 1, 1, 2, 28, 1),
    (256, 256, 3, 3, 1, 14, 6), (1024, 256, 1, 1, 1, 14, 6),
    (256, 1024, 1, 1, 1, 14, 5),
    (512, 1024, 1, 1, 2, 14, 1), (2048, 1024, 1, 1, 2, 14, 1),
    (512, 512, 3, 3, 1, 7, 3), (2048, 512, 1, 1, 1, 7, 3),
    (512, 2048, 1, 1, 1, 7, 2),
]


def exp_H(batch=512):
    rng = np.random.RandomState(0)
    xs = []
    for (co, ci, kh, kw, s, hw, mult) in R50_CONVS:
        pad = (kh // 2, kh // 2)
        x = jnp.asarray(rng.rand(batch, hw, hw, ci), jnp.bfloat16)
        w = jnp.asarray(rng.rand(kh, kw, ci, co), jnp.bfloat16)
        xs.append((x, w, s, pad, mult))

    def run(c, *arrs):
        tot = jnp.float32(0)
        it = iter(arrs)
        for (x, w, s, pad, mult) in xs:
            xx = _mix(next(it), c)
            yv = lax.conv_general_dilated(
                xx, next(it), (s, s), [pad, pad],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            tot = tot + jnp.sum(yv.astype(jnp.float32)) * mult
        return tot

    flat = []
    for (x, w, s, pad, m) in xs:
        flat += [x, w]
    t = timeit_inv(run, tuple(flat), k=4)
    uflops = sum(2.0 * batch * (hw // s) ** 2 * co * ci * kh * kw
                 for (co, ci, kh, kw, s, hw, m) in R50_CONVS)
    print(f"H conv floor b{batch}: {t*1e3:7.2f} ms 1x-each "
          f"-> {uflops/t/PEAK_FLOPS*100:5.1f}% MFU", flush=True)


if __name__ == "__main__":
    which = sys.argv[1:] or ["G", "E", "H", "F"]
    t0 = time.time()
    for w in which:
        try:
            {"E": exp_E, "F": exp_F, "G": exp_G, "H": exp_H}[w]()
        except Exception as e:
            print(f"# [{w}] FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"# [{w}] done at +{time.time()-t0:.0f}s", flush=True)
