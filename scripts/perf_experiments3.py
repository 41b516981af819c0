"""Round-3 perf experiments, part 3: localize the slow backward convs.

Established so far (v5e, ResNet-50 NHWC bf16 b256):
  fwd 27.35 ms   fwd+bwd(all grads) 98.5 ms   update ~free
  bare-conv fwd floor ~19.2 ms (51.6% MFU)
Backward costs 71 ms for 2x the fwd FLOPs -> some backward conv forms
run far below the fwd floor.  Experiments:

  I  per-shape fwd / d_input / d_weight times for every distinct
     resnet50 conv shape (multiplicity-weighted totals at the end)
  J  stem alternatives: plain 7x7/2 C3 conv vs space-to-depth
     (2x2 -> 112x112x12, 4x4 kernel from zero-padded 8x8) — fwd+bwd
  F2 no-BN full step (fresh process; OOM killed it last time)
  H2 conv floor at b512 (fresh process)
"""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
from jax import lax                                        # noqa: E402


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


def exp_I(batch=256):
    rng = np.random.RandomState(0)
    tot_f = tot_dx = tot_dw = 0.0
    print("  shape                       fwd      d_in     d_w   "
          " (ms, x mult)", flush=True)
    for (co, ci, kh, kw, s, hw, mult) in R50_CONVS:
        pad = [(kh // 2, kh // 2)] * 2
        x = jnp.asarray(rng.rand(batch, hw, hw, ci), jnp.bfloat16)
        w = jnp.asarray(rng.rand(kh, kw, ci, co), jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")

        def fwd(c, x, w):
            y = lax.conv_general_dilated(_mix(x, c), w, (s, s), pad,
                                         dimension_numbers=dn)
            return jnp.sum(y.astype(jnp.float32))

        def d_in(c, x, w):
            g = jax.grad(
                lambda xx: jnp.sum(
                    lax.conv_general_dilated(xx, w, (s, s), pad,
                                             dimension_numbers=dn)
                    .astype(jnp.float32)))(_mix(x, c))
            return jnp.sum(g.astype(jnp.float32))

        def d_w(c, x, w):
            g = jax.grad(
                lambda ww: jnp.sum(
                    lax.conv_general_dilated(_mix(x, c), ww, (s, s), pad,
                                             dimension_numbers=dn)
                    .astype(jnp.float32)))(w)
            return jnp.sum(g.astype(jnp.float32))

        k = 6
        tf = timeit_inv(fwd, (x, w), k=k, trials=2)
        tdx = timeit_inv(d_in, (x, w), k=k, trials=2)
        tdw = timeit_inv(d_w, (x, w), k=k, trials=2)
        tot_f += tf * mult
        tot_dx += tdx * mult
        tot_dw += tdw * mult
        print(f"  {co:4d}x{ci:4d} {kh}x{kw}/{s} @{hw:3d} x{mult}: "
              f"{tf*mult*1e3:7.2f}  {tdx*mult*1e3:7.2f}  "
              f"{tdw*mult*1e3:7.2f}", flush=True)
    print(f"I totals: fwd {tot_f*1e3:6.1f} ms   d_in {tot_dx*1e3:6.1f} ms"
          f"   d_w {tot_dw*1e3:6.1f} ms   "
          f"sum {(tot_f+tot_dx+tot_dw)*1e3:6.1f} ms", flush=True)


def exp_J(batch=256):
    """Stem: plain 7x7/2 pad3 C3->64 vs space-to-depth equivalent."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 224, 224, 3), jnp.bfloat16)
    w = jnp.asarray(rng.rand(7, 7, 3, 64), jnp.bfloat16)
    dn = ("NHWC", "HWIO", "NHWC")

    def plain(c, x, w):
        def f(xx, ww):
            y = lax.conv_general_dilated(xx, ww, (2, 2),
                                         [(3, 3), (3, 3)],
                                         dimension_numbers=dn)
            return jnp.sum(y.astype(jnp.float32))
        l, (gx, gw) = jax.value_and_grad(f, argnums=(0, 1))(_mix(x, c), w)
        return l + jnp.sum(gx.astype(jnp.float32)) * 1e-30 \
            + jnp.sum(gw.astype(jnp.float32)) * 1e-30

    t = timeit_inv(plain, (x, w), k=10)
    print(f"J stem plain 7x7/2      : {t*1e3:7.2f} ms (fwd+bwd)",
          flush=True)

    def s2d(c, x, w):
        def f(xx, ww):
            # pad image by 3 left / 4 right (8x8 zero-padded kernel),
            # space-to-depth 2x2, then 4x4 stride-1 conv == 7x7/2 pad3
            wp = jnp.pad(ww, ((0, 1), (0, 1), (0, 0), (0, 0)))
            wp = wp.reshape(4, 2, 4, 2, 3, 64).transpose(0, 2, 1, 3, 4, 5) \
                   .reshape(4, 4, 12, 64)
            xp = jnp.pad(xx, ((0, 0), (3, 5), (3, 5), (0, 0)))
            B, H, W, C = xp.shape
            xs = xp.reshape(B, H // 2, 2, W // 2, 2, C) \
                   .transpose(0, 1, 3, 2, 4, 5).reshape(B, H // 2, W // 2,
                                                        4 * C)
            y = lax.conv_general_dilated(xs, wp, (1, 1), [(0, 0), (0, 0)],
                                         dimension_numbers=dn)
            return jnp.sum(y.astype(jnp.float32))
        l, (gx, gw) = jax.value_and_grad(f, argnums=(0, 1))(_mix(x, c), w)
        return l + jnp.sum(gx.astype(jnp.float32)) * 1e-30 \
            + jnp.sum(gw.astype(jnp.float32)) * 1e-30

    t2 = timeit_inv(s2d, (x, w), k=10)
    print(f"J stem space-to-depth   : {t2*1e3:7.2f} ms (fwd+bwd)",
          flush=True)
    # numerics: same result?
    y1 = lax.conv_general_dilated(x, w, (2, 2), [(3, 3), (3, 3)],
                                  dimension_numbers=dn)
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    wp = wp.reshape(4, 2, 4, 2, 3, 64).transpose(0, 2, 1, 3, 4, 5) \
           .reshape(4, 4, 12, 64)
    xp = jnp.pad(x, ((0, 0), (3, 5), (3, 5), (0, 0)))
    B, H, W, C = xp.shape
    xs = xp.reshape(B, H // 2, 2, W // 2, 2, C) \
           .transpose(0, 1, 3, 2, 4, 5).reshape(B, H // 2, W // 2, 4 * C)
    y2 = lax.conv_general_dilated(xs, wp, (1, 1), [(0, 0), (0, 0)],
                                  dimension_numbers=dn)
    y2 = y2[:, :y1.shape[1], :y1.shape[2], :]
    err = float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                - y2.astype(jnp.float32))))
    print(f"J s2d parity max|diff|  : {err}", flush=True)


def exp_F2(batch=256):
    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    orig = resnet._Builder.bn
    resnet._Builder.bn = lambda self, n: nn.Identity()
    try:
        model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                             format="NHWC")
    finally:
        resnet._Builder.bn = orig
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init_params(0)
    opt_state = method.init_state(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 1001, batch).astype(np.float32))
    step = make_train_step(model, criterion, method, mixed_precision=True)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def many(carry, x, y):
        def body(c, i):
            p, o, s = c
            p, o, s, loss = step(p, o, s, x, y, key)
            return (p, o, s), loss
        return lax.scan(body, carry, jnp.arange(10))

    carry, losses = many((params, opt_state, state), x, y)
    float(jnp.sum(losses))
    l = lat()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        carry, losses = many(carry, x, y)
        float(jnp.sum(losses))
        ts.append((time.perf_counter() - t0 - l) / 10)
    t = float(np.median(ts))
    print(f"F2 no-BN full step      : {t*1e3:7.2f} ms  {batch/t:8.0f} "
          "img/s", flush=True)


if __name__ == "__main__":
    which = sys.argv[1:] or ["I", "J", "F2"]
    t0 = time.time()
    for w in which:
        try:
            {"I": exp_I, "J": exp_J, "F2": exp_F2}[w]()
        except Exception as e:
            print(f"# [{w}] FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"# [{w}] done at +{time.time()-t0:.0f}s", flush=True)
