"""One-process ResNet-50 perf localization suite (round 3).

Every experiment runs in THIS process, sequentially (one process holds
the chip; run it through the chip tool).  Prints one flushed line per
measurement.

Experiments:
  A  timing-protocol comparison: scan-invariant params (tuning-style)
     vs threaded params (bench-style) vs threaded+donated
  B  parts, NHWC: fwd only / fwd+bwd / full step
  C  conv compute floor: the distinct resnet50 conv shapes as bare
     bf16 convs (what the MXU can do with zero overhead)
  D  kernel layout: OIHW vs HWIO dimension numbers
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


def timeit_carry(fn, carry, args, k=10, trials=3, donate=False):
    """fn(carry, i, *args) -> (carry, scalar); threads carry (bench-style)."""
    @(jax.jit if not donate else
      (lambda f: jax.jit(f, donate_argnums=(0,))))
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
    """fn(c, *args) -> scalar; params scan-invariant (tuning-style)."""
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


def setup(batch=256, fmt="NHWC"):
    model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                         format=fmt)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init_params(0)
    opt_state = method.init_state(params)
    rng = np.random.RandomState(0)
    shape = (batch, 3, 224, 224) if fmt == "NCHW" else (batch, 224, 224, 3)
    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 1001, batch).astype(np.float32))
    return model, criterion, method, params, state, opt_state, x, y


def exp_A(batch=256):
    model, criterion, method, params, state, opt_state, x, y = setup(batch)
    step = make_train_step(model, criterion, method, mixed_precision=True)
    key = jax.random.PRNGKey(0)

    def inv(c, p, o, s, xx, yy):
        p2, o2, s2, loss = step(p, o, s, _mix(xx, c), yy, key)
        return loss + jax.tree_util.tree_leaves(p2)[0].ravel()[0]

    t = timeit_inv(inv, (params, opt_state, state, x, y))
    print(f"A inv-params   : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)

    def thr(carry, i, xx, yy):
        p, o, s = carry
        p, o, s, loss = step(p, o, s, xx, yy, jax.random.fold_in(key, i))
        return (p, o, s), loss

    t = timeit_carry(thr, (params, opt_state, state), (x, y))
    print(f"A threaded     : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)

    def thr_fixed_key(carry, i, xx, yy):
        p, o, s = carry
        p, o, s, loss = step(p, o, s, xx, yy, key)
        return (p, o, s), loss

    t = timeit_carry(thr_fixed_key, (params, opt_state, state), (x, y))
    print(f"A thr fixed-key: {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)
    # donation invalidates the donated buffers — run LAST, on copies
    cp = jax.tree_util.tree_map(jnp.copy, (params, opt_state, state))
    t = timeit_carry(thr, cp, (x, y), donate=True)
    print(f"A thr+donate   : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)


def exp_B(batch=256):
    model, criterion, method, params, state, opt_state, x, y = setup(batch)
    xb = x.astype(jnp.bfloat16)

    def fwd(c, p, s, xx):
        ctx = Ctx(state=s, training=True, rng_key=jax.random.PRNGKey(0))
        out = model.apply(p, _mix(xx, c), ctx)
        return jnp.sum(out.astype(jnp.float32))

    t = timeit_inv(fwd, (params, state, xb))
    print(f"B fwd only     : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)

    def fwdbwd(c, p, s, xx, yy):
        def loss_fn(pp):
            ctx = Ctx(state=s, training=True, rng_key=jax.random.PRNGKey(0))
            out = model.apply(pp, _mix(xx, c), ctx)
            return nn.ClassNLLCriterion().loss(out.astype(jnp.float32), yy)
        l, g = jax.value_and_grad(loss_fn)(p)
        return l + jax.tree_util.tree_leaves(g)[0].ravel()[0]

    t = timeit_inv(fwdbwd, (params, state, xb, y))
    print(f"B fwd+bwd      : {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
          flush=True)


# (out_ch, in_ch, kh, kw, stride, spatial_in) for the distinct resnet50
# imagenet convs, with their multiplicities
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


def exp_C(batch=256):
    """Bare-conv compute floor: all distinct conv shapes, bf16, NHWC+HWIO,
    chained through independent inputs; total time ~= fwd conv floor."""
    rng = np.random.RandomState(0)
    xs, ws, flops = [], [], 0.0
    for (co, ci, kh, kw, s, hw, mult) in R50_CONVS:
        pad = (kh // 2, kh // 2)
        x = jnp.asarray(rng.rand(batch, hw, hw, ci), jnp.bfloat16)
        w = jnp.asarray(rng.rand(kh, kw, ci, co), jnp.bfloat16)
        xs.append((x, w, s, pad, mult))
        out_hw = hw // s
        flops += mult * 2.0 * batch * out_hw * out_hw * co * ci * kh * kw

    def run(c, *arrs):
        tot = jnp.float32(0)
        it = iter(arrs)
        for (x, w, s, pad, mult) in xs:
            xx = _mix(next(it), c)
            y = lax.conv_general_dilated(
                xx, next(it), (s, s), [pad, pad],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            tot = tot + jnp.sum(y.astype(jnp.float32)) * mult
        return tot

    flat = []
    for (x, w, s, pad, m) in xs:
        flat += [x, w]
    t = timeit_inv(run, tuple(flat), k=4)
    # weighted: each distinct conv ran once but counts mult times ->
    # scale measured time by weighted/unweighted flop ratio
    uflops = sum(2.0 * batch * (hw // s) ** 2 * co * ci * kh * kw
                 for (co, ci, kh, kw, s, hw, m) in R50_CONVS)
    eff = uflops / t / PEAK_FLOPS * 100
    print(f"C conv floor   : {t*1e3:7.2f} ms for 1x-each "
          f"({uflops/1e9:.0f} GFLOP) -> {eff:5.1f}% MFU; "
          f"full-net fwd conv time ~= {t*flops/uflops*1e3:6.2f} ms",
          flush=True)


def exp_D(batch=256):
    """OIHW vs HWIO kernel layout for a mid-size conv under scan."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 28, 28, 128), jnp.bfloat16)
    w_oihw = jnp.asarray(rng.rand(128, 128, 3, 3), jnp.bfloat16)
    w_hwio = jnp.transpose(w_oihw, (2, 3, 1, 0))

    def f_oihw(c, x, w):
        y = lax.conv_general_dilated(
            _mix(x, c), w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"))
        return jnp.sum(y.astype(jnp.float32))

    def f_hwio(c, x, w):
        y = lax.conv_general_dilated(
            _mix(x, c), w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(y.astype(jnp.float32))

    t1 = timeit_inv(f_oihw, (x, w_oihw), k=20)
    t2 = timeit_inv(f_hwio, (x, w_hwio), k=20)
    print(f"D OIHW {t1*1e3:6.2f} ms   HWIO {t2*1e3:6.2f} ms", flush=True)


if __name__ == "__main__":
    which = sys.argv[1:] or ["A", "B", "C", "D"]
    t0 = time.time()
    for w in which:
        try:
            {"A": exp_A, "B": exp_B, "C": exp_C, "D": exp_D}[w]()
        except Exception as e:   # one experiment must not sink the rest
            print(f"# [{w}] FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"# [{w}] done at +{time.time()-t0:.0f}s", flush=True)
