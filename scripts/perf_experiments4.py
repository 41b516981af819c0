"""Round-3 perf experiments, part 4: measure the conv rewrites.

Baseline (pre-rewrite): threaded full step NHWC b256 = 98.98 ms
(2,586 img/s).  Now in the tree: 1x1/stride-s convs compute as
slice+dense (always on), and resnet.build(stem='s2d') reparameterizes
the stem.  Experiments:

  K1 threaded full step, plain stem   (1x1 rewrite active)
  K2 threaded full step, s2d stem     (both rewrites)
  K3 K2 + plain-autodiff BN           (is the custom vjp helping?)
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
from bigdl_tpu.observability.profile import specs          # noqa: E402
from bigdl_tpu.utils.engine import enable_compile_cache    # noqa: E402

# MFU denominator from the one peak table; no TPU or an unknown device
# kind is an error here, never a default
PEAK_FLOPS = specs.require_chip()[1].peak_flops
enable_compile_cache()


def lat():
    ones = jnp.ones(4)
    ls = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jnp.sum(ones))
        ls.append(time.perf_counter() - t0)
    return float(np.median(ls))


def run_full(label, batch=256, stem="conv", k=10, x_bf16=False,
             remat=False):
    model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                         format="NHWC", stem=stem, remat=remat)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init_params(0)
    opt_state = method.init_state(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    if x_bf16:
        x = x.astype(jnp.bfloat16)
    y = jnp.asarray(rng.randint(1, 1001, batch).astype(np.float32))
    step = make_train_step(model, criterion, method, mixed_precision=True)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def many(carry, x, y):
        def body(c, i):
            p, o, s = c
            p, o, s, loss = step(p, o, s, x, y, key)
            return (p, o, s), loss
        return lax.scan(body, carry, jnp.arange(k))

    carry, losses = many((params, opt_state, state), x, y)
    float(jnp.sum(losses))
    l = lat()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        carry, losses = many(carry, x, y)
        float(jnp.sum(losses))
        ts.append((time.perf_counter() - t0 - l) / k)
    t = float(np.median(ts))
    print(f"{label}: {t*1e3:7.2f} ms  {batch/t:8.0f} img/s  "
          f"({batch*12.3e9/t/PEAK_FLOPS*100:4.1f}% MFU)", flush=True)
    return t


def exp_K1():
    run_full("K1 full step, conv stem ")


def exp_K9():
    """BN folding payoff at inference: bf16 fwd img/s, folded vs not
    (nn/fusion.py removes one HBM-bound elementwise pass per BN)."""
    from bigdl_tpu.nn.fusion import fold_batchnorm

    def infer(label, m):
        params, state = m._params, m._state
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.rand(256, 224, 224, 3), jnp.bfloat16)

        @jax.jit
        def fwd(p, s, xx):
            y, _ = m.run(p, xx, state=s, training=False)
            return y

        fwd(params, state, x).block_until_ready()
        l = lat()
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            fwd(params, state, x).block_until_ready()
            ts.append(time.perf_counter() - t0 - l)
        t = float(np.median(ts))
        print(f"{label}: {t*1e3:7.2f} ms  {256/t:8.0f} img/s", flush=True)

    model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                         format="NHWC")
    model.ensure_initialized()
    model.evaluate()
    infer("K9 bf16 infer, BN separate", model)
    infer("K9 bf16 infer, BN folded  ", fold_batchnorm(model))


def exp_K10():
    """Decode throughput, fp-bf16 vs weight-only int8 params: the
    weight-streaming HBM lever (docs/performance.md item 7)."""
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.quantized import (dequantize_weights,
                                     quantize_weights_only,
                                     quantized_bytes)

    model = T.build("small", dropout=0.0)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, 1000, (8, 64)), jnp.int32)
    new = 128

    def measure(label, p, transform=None):
        kw = dict(max_new_tokens=new, params_transform=transform)
        model.generate(p, prompt, **kw)  # compile
        l = lat()
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(model.generate(p, prompt, **kw))
            ts.append(time.perf_counter() - t0 - l)
        t = float(np.median(ts))
        tok = prompt.shape[0] * new
        print(f"{label}: {t*1e3:8.1f} ms  {tok/t:9.0f} tok/s decode",
              flush=True)

    measure("K10 decode bf16 weights  ", params)
    # weights STAY int8 in HBM; dequantize_weights traces inside the
    # compiled program (generate(params_transform=...))
    qp = quantize_weights_only(params)
    # the serving claim is "near-halved HBM weight bytes" — assert it,
    # don't narrate it (fp32 matrices -> int8+scale is ~4x on the
    # quantized leaves; embeddings/matrices dominate this model)
    b_fp, b_q = quantized_bytes(params), quantized_bytes(qp)
    print(f"K10 weight bytes: fp={b_fp/2**20:.1f} MiB "
          f"int8={b_q/2**20:.1f} MiB  ratio={b_fp/b_q:.2f}x", flush=True)
    assert b_q < 0.6 * b_fp, (b_fp, b_q)
    measure("K10 decode int8 weights  ", qp,
            transform=dequantize_weights)


def exp_K7():
    """remat cost at b256 (baseline for K8): blocks recompute in bwd."""
    run_full("K7 b256 remat           ", remat=True)


def exp_K8():
    """b512 via remat — the batch the non-remat step OOMs at
    (RESOURCE_EXHAUSTED, artifacts/perf_experiments2_20260731.txt).
    Larger batch amortizes BN reductions + weight traffic; if img/s
    beats K1's, flip the bench headline to remat+b512."""
    run_full("K8 b512 remat           ", batch=512, remat=True)


def exp_K2():
    run_full("K2 full step, s2d stem  ", stem="s2d")


def exp_K3():
    from bigdl_tpu.nn import normalization as nz
    orig = nz._bn_train

    def plain_bn(x, gamma, beta, channel_axis, eps):
        y, mean, var, _ = nz._bn_train_fwd_impl(x, gamma, beta,
                                                channel_axis, eps)
        return y, mean, var

    nz._bn_train = plain_bn
    try:
        run_full("K3 s2d + autodiff BN    ", stem="s2d")
    finally:
        nz._bn_train = orig


def exp_K11():
    """LSTM input-projection hoisting (nn/recurrent.py hoist_input):
    ONE (B*T, D) @ (D, 4H) MXU matmul outside the scan instead of T
    (B, D) ones inside it — bench_lstm's exact protocol.  If hoisted
    wins, flip bench_lstm to hoist_input=True."""

    def run(label, hoist):
        B, T_, D, H, V = 64, 128, 256, 512, 1000
        model = nn.Sequential(
            nn.Recurrent(nn.LSTM(D, H), hoist_input=hoist),
            nn.TimeDistributed(nn.Linear(H, V)))
        criterion = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        method = SGD(learning_rate=0.1, momentum=0.9)
        params, state = model.init_params(0)
        opt_state = method.init_state(params)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.rand(B, T_, D).astype(np.float32))
        y = jnp.asarray(rng.randint(1, V + 1, (B, T_)).astype(np.float32))
        step = make_train_step(model, criterion, method,
                               mixed_precision=True)
        key = jax.random.PRNGKey(0)
        k = 10

        @jax.jit
        def many(carry, x, y):
            def body(c, i):
                p, o, s = c
                p, o, s, loss = step(p, o, s, x, y, key)
                return (p, o, s), loss
            return lax.scan(body, carry, jnp.arange(k))

        carry, losses = many((params, opt_state, state), x, y)
        float(jnp.sum(losses))
        l = lat()
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            carry, losses = many(carry, x, y)
            float(jnp.sum(losses))
            ts.append((time.perf_counter() - t0 - l) / k)
        t = float(np.median(ts))
        print(f"{label}: {t*1e3:7.2f} ms  {B*T_/t:9.0f} tok/s", flush=True)

    run("K11 lstm per-step proj  ", False)
    run("K11 lstm hoisted proj   ", True)


def exp_K4():
    run_full("K4 s2d + bf16 input     ", stem="s2d", x_bf16=True)


def exp_K5():
    run_full("K5 conv stem, b128      ", batch=128, k=16)


def exp_K6():
    run_full("K6 s2d stem, b512       ", batch=512, stem="s2d", k=6)


if __name__ == "__main__":
    which = sys.argv[1:] or ["K1", "K2", "K3"]
    t0 = time.time()
    EXPS = {"K1": exp_K1, "K2": exp_K2, "K3": exp_K3, "K7": exp_K7,
            "K8": exp_K8, "K9": exp_K9, "K10": exp_K10,
            "K4": exp_K4, "K5": exp_K5, "K6": exp_K6, "K11": exp_K11}
    failed = []
    for w in which:
        try:
            EXPS[w]()
        except Exception as e:
            print(f"# [{w}] FAILED: {type(e).__name__}: {e}", flush=True)
            failed.append(w)
        print(f"# [{w}] done at +{time.time()-t0:.0f}s", flush=True)
    # non-zero exit on any failure: tpu_queue must NOT write a completion
    # sentinel for a run whose measurement never happened (a swallowed
    # wedge would otherwise mark the lever 'done' forever).  rc=4 is
    # bench.py's "config failed, run completed" convention — distinct
    # from rc=2 (backend unreachable), so tpu_queue keeps draining the
    # queue instead of treating the whole window as dead
    sys.exit(4 if failed else 0)
