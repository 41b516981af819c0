"""ResNet-50 MFU localization + tuning matrix (needs the chip: run it
through the chip tool).

PROTOCOL WARNING (the r2 lesson): any timing whose scan body does not
consume EVERY output of the step lets XLA dead-code-eliminate the
unconsumed work — the original `matrix`/`parts` "full step" here only
read one updated-param leaf, which deleted most d_weight matmuls and
the whole optimizer update and inflated ResNet-50 b256 from the true
~2,600 img/s to a reported 9,260 (which is in fact the FORWARD-ONLY
rate). Full-step timings now thread (params, opt_state, state) through
the scan carry, matching bench.py. Localization phases (`parts`,
`stages`) still use invariant-params timing where DCE is the point
(e.g. fwd-only) — read them as lower bounds on cost, never as
throughput claims.

Three phases, each printing one line per measurement:

  parts    fwd-only vs fwd+bwd vs full train step  -> where the time goes
  stages   cumulative prefixes (stem, +layer1, ...) fwd+bwd
  matrix   batch x {layout, bn-fused} throughput grid

Usage:  python scripts/tpu_tuning.py [parts|stages|matrix|profile] ...
`profile` captures a jax.profiler trace of one train step into
/tmp/tpu_trace for TensorBoard's profile plugin.
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from bigdl_tpu import nn                                   # noqa: E402
from bigdl_tpu.models import resnet                        # noqa: E402
from bigdl_tpu.optim import SGD                            # noqa: E402
from bigdl_tpu.optim.optimizer import make_train_step      # noqa: E402


def lat():
    ones = jnp.ones(4)
    ls = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jnp.sum(ones))
        ls.append(time.perf_counter() - t0)
    return float(np.median(ls))


def _mix(x, c):
    """Make `x` depend on the loop carry without changing its value
    (c*1e-30 underflows at runtime but can't be folded at compile time),
    so XLA cannot hoist the body out of the timing scan."""
    return x + (c * 1e-30).astype(x.dtype)


def timeit(fn, args, k=10, trials=3):
    """fn(c, *args) -> scalar; times k dependency-chained evaluations.
    Implementations must _mix the carry `c` into their inputs.
    CAUTION: anything the scalar result doesn't depend on is DCE'd —
    use timeit_carry for full-train-step throughput claims."""
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


def timeit_carry(fn, carry, args, k=10, trials=3):
    """fn(carry, i, *args) -> (carry, scalar); threads full training
    state through the scan so no step output is dead (bench.py
    protocol — the only protocol valid for throughput claims)."""
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


def _setup(batch=256, fmt="NCHW", mixed=True):
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
    return model, criterion, method, params, state, opt_state, x, y, mixed


def parts(batch=256):
    (model, criterion, method, params, state, opt_state, x, y,
     mixed) = _setup(batch)
    from bigdl_tpu.nn.module import Ctx
    xb = x.astype(jnp.bfloat16)

    def fwd(c, p, s, xx):
        ctx = Ctx(state=s, training=True, rng_key=jax.random.PRNGKey(0))
        out = model.apply(p, _mix(xx, c), ctx)
        return jnp.sum(out.astype(jnp.float32))

    def fwdbwd(c, p, s, xx, yy):
        def loss_fn(pp):
            ctx = Ctx(state=s, training=True, rng_key=jax.random.PRNGKey(0))
            out = model.apply(pp, _mix(xx, c), ctx)
            return criterion.loss(out.astype(jnp.float32), yy)
        l, g = jax.value_and_grad(loss_fn)(p)
        return l + jax.tree_util.tree_leaves(g)[0].ravel()[0]

    step = make_train_step(model, criterion, method, mixed_precision=True)

    def full(carry, i, xx, yy):
        p, o, s = carry
        p, o, s, loss = step(p, o, s, xx, yy, jax.random.PRNGKey(0))
        return (p, o, s), loss

    t_f = timeit(fwd, (params, state, xb), k=10)
    print(f"fwd only (bf16 in):    {t_f*1e3:7.2f} ms  "
          f"{batch/t_f:8.0f} img/s", flush=True)
    t_fb = timeit(fwdbwd, (params, state, xb, y), k=10)
    print(f"fwd+bwd (leaf-0 only): {t_fb*1e3:7.2f} ms  "
          f"{batch/t_fb:8.0f} img/s", flush=True)
    t_full = timeit_carry(full, (params, opt_state, state), (x, y), k=10)
    print(f"full train step:       {t_full*1e3:7.2f} ms  "
          f"{batch/t_full:8.0f} img/s", flush=True)


def stages(batch=256):
    """Cumulative prefixes of the ResNet trunk, fwd+bwd."""
    (model, criterion, method, params, state, opt_state, x, y,
     mixed) = _setup(batch)
    from bigdl_tpu.nn.module import Ctx
    xb = x.astype(jnp.bfloat16)
    kids = model.children()
    # prefix lengths: stem(4) then after each stage
    cuts = [4, 5, 6, 7, 8, len(kids)]
    names = ["stem", "+layer1", "+layer2", "+layer3", "+layer4", "full"]
    for cut, nm in zip(cuts, names):
        prefix = nn.Sequential(*kids[:cut])

        def fwdbwd(c, p, s, xx):
            def loss_fn(pp):
                ctx = Ctx(state=s, training=True,
                          rng_key=jax.random.PRNGKey(0))
                out = prefix.apply(pp, _mix(xx, c), ctx)
                return jnp.sum(out.astype(jnp.float32) ** 2)
            l, g = jax.value_and_grad(loss_fn)(p)
            return l + jax.tree_util.tree_leaves(g)[0].ravel()[0]

        t = timeit(fwdbwd, (params, state, xb), k=10)
        print(f"{nm:8s}: {t*1e3:7.2f} ms", flush=True)


def matrix():
    for fmt in ("NCHW", "NHWC"):
        for batch in (256, 512):
            (model, criterion, method, params, state, opt_state, x, y,
             mixed) = _setup(batch, fmt)
            step = make_train_step(model, criterion, method,
                                   mixed_precision=True)

            def full(carry, i, xx, yy):
                p, o, s = carry
                p, o, s, loss = step(p, o, s, xx, yy,
                                     jax.random.PRNGKey(0))
                return (p, o, s), loss

            t = timeit_carry(full, (params, opt_state, state), (x, y),
                             k=10)
            print(f"{fmt} b{batch}: {t*1e3:7.2f} ms  {batch/t:8.0f} img/s",
                  flush=True)


def profile(batch=256):
    (model, criterion, method, params, state, opt_state, x, y,
     mixed) = _setup(batch)
    step = jax.jit(make_train_step(model, criterion, method,
                                   mixed_precision=True))
    out = step(params, opt_state, state, x, y, jax.random.PRNGKey(0))
    float(out[3])
    with jax.profiler.trace("/tmp/tpu_trace"):
        out = step(params, opt_state, state, x, y, jax.random.PRNGKey(0))
        float(out[3])
    print("trace written to /tmp/tpu_trace", flush=True)


if __name__ == "__main__":
    cmd = sys.argv[1] if len(sys.argv) > 1 else "parts"
    {"parts": parts, "stages": stages, "matrix": matrix,
     "profile": profile}[cmd]()
