"""Benchmarks over the BASELINE.json config set.

Mirrors the reference perf harnesses (models/utils/LocalOptimizerPerf.scala
and DistriOptimizerPerf.scala: synthetic-data sync-SGD step time) across
every BASELINE config:

  lenet        LeNet-5 MNIST train             img/s   (ref ~10k Xeon)
  vgg16        VGG-16 CIFAR-10 train           img/s   (ref ~180)
  lstm         LSTM seq model train            tok/s   (no published ref)
  inception    Inception-v1 via Caffe loader   img/s   (loader -> XLA path)
  int8         ResNet-50 int8 inference        img/s   (MXU int8 path)
  moe          Switch MoE LM train             tok/s   (routed experts)
  transformer  TransformerLM train w/ Pallas   tok/s   (flash attn on TPU)
  resnet50     ResNet-50 ImageNet train        img/s   (headline, ~57 ref)

Each config prints one JSON line {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "devices"}; configs run in the order above, so
the ResNet-50 headline prints last.  `python bench.py lenet vgg16` runs a
subset.

This measures on the chip or not at all: with no TPU it exits non-zero
before running anything, a config that raises makes the exit code
non-zero, and peaks come from observability/profile/specs.py — a device
that table does not know is an error, never a default.  Run it through
the chip tool (one process holds the chip).  Cells, regression bounds
and the timing protocol are ROADMAP S1's to define; until then this is
the harness as it stood, with nothing left that can hide the device.

Timing: K train steps run inside ONE jitted lax.scan (state threaded
through the loop so nothing hoists) and the wall time of that call is
divided by K.  A host transfer of the summed losses is the sync point.

The transformer config additionally ASSERTS the Pallas flash-attention
path is what a bench-width shape takes and that its on-device numerics
match attention_reference.
"""
import json
import os
import sys
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

TRIALS = 3

_DEVICE = {}     # platform / device_kind / devices, filled by main()


def _env_bool(name, default="0"):
    """Parse a 1/0 bench knob; a typo'd value must fail loudly — a chip
    run must never silently measure the wrong config."""
    raw = os.environ.get(name, default).lower()
    if raw in ("1", "true", "yes", "on"):
        return True
    if raw in ("0", "false", "no", "off", ""):
        return False
    raise ValueError(f"{name}={raw!r}: use 1/0")


def _time_scanned(step, carry, args, k):
    """Median per-step seconds of `k` steps fused into one device call."""
    @jax.jit
    def many(carry, *args):
        def body(c, i):
            c, loss = step(c, i, *args)
            return c, loss
        return lax.scan(body, carry, jnp.arange(k))

    carry, losses = many(carry, *args)   # compile + warm
    float(jnp.sum(losses))
    per = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        carry, losses = many(carry, *args)
        float(jnp.sum(losses))
        per.append((time.perf_counter() - t0) / k)
    return float(np.median(per))


def _train_throughput(model, batch_shape, class_num, batch, k,
                      mixed=True, criterion=None, label_shape=None):
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    criterion = criterion or nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init_params(0)
    opt_state = method.init_state(params)
    step = make_train_step(model, criterion, method, mixed_precision=mixed)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(*batch_shape).astype(np.float32))
    y = jnp.asarray(rng.randint(1, class_num + 1, label_shape or (batch,))
                    .astype(np.float32))
    key = jax.random.PRNGKey(0)

    def scan_step(carry, i, x, y):
        p, o, s = carry
        p, o, s, loss = step(p, o, s, x, y, jax.random.fold_in(key, i))
        return (p, o, s), loss

    sec = _time_scanned(scan_step, (params, opt_state, state), (x, y), k)
    return batch / sec


def _infer_throughput(model, params, state, x, batch, k=10):
    """Inference images/sec via the scanned-steps protocol (shared by the
    caffe-inception and int8 configs)."""
    def scan_step(carry, i, x):
        # input depends on the carry so XLA cannot hoist the forward out
        # of the scan (loop-invariant code motion would time 1 inference)
        xi = x + (carry * 0).astype(x.dtype)
        out, _ = model.run(params, xi, state=state, training=False)
        return jnp.sum(out.astype(jnp.float32)), jnp.float32(0)

    sec = _time_scanned(scan_step, jnp.float32(0), (x,), k)
    return batch / sec


def _emit(metric, value, unit, vs_baseline=None):
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      "vs_baseline": vs_baseline, **_DEVICE}), flush=True)


def _report(metric, value, unit, baseline):
    _emit(metric, round(value, 2), unit,
          round(value / baseline, 3) if baseline else None)


# --------------------------------------------------------------------- #
def bench_lenet():
    from bigdl_tpu.models import lenet
    model = lenet.build(class_num=10)
    batch = 2048
    ips = _train_throughput(model, (batch, 1, 28, 28), 10, batch, k=20)
    _report("lenet_mnist_train_images_per_sec", ips, "images/sec", 10000.0)


def bench_vgg16():
    from bigdl_tpu.models import vgg
    model = vgg.build(class_num=10, dataset="cifar10", format="NHWC")
    batch = 512
    ips = _train_throughput(model, (batch, 32, 32, 3), 10, batch, k=20)
    _report("vgg16_cifar10_train_images_per_sec", ips, "images/sec", 180.0)


def bench_lstm():
    """Seq2Seq-style LSTM LM step (≙ models/rnn on XLA): (B, T, D) through
    Recurrent(LSTM) + TimeDistributed classifier."""
    from bigdl_tpu import nn

    B, T, D, H, V = 64, 128, 256, 512, 1000
    # BENCH_LSTM_HOIST=1 hoists the input projection out of the scan
    # (one (B*T, D) MXU matmul); flip only after K11 proves it wins
    model = nn.Sequential(
        nn.Recurrent(nn.LSTM(D, H),
                     hoist_input=_env_bool("BENCH_LSTM_HOIST")),
        nn.TimeDistributed(nn.Linear(H, V)),
    )
    ips = _train_throughput(
        model, (B, T, D), V, B, k=10,
        criterion=nn.TimeDistributedCriterion(nn.CrossEntropyCriterion()),
        label_shape=(B, T))
    _report("lstm_seq_train_tokens_per_sec", ips * T, "tokens/sec", None)


def bench_inception():
    """Caffe-loader path: parse the BVLC GoogLeNet deploy prototxt into an
    nn.Graph and run inference (≙ example/loadmodel)."""
    import tempfile
    from bigdl_tpu.models.inception import googlenet_v1_deploy_prototxt
    from bigdl_tpu.utils.caffe import load_caffe

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "googlenet.prototxt")
        with open(p, "w") as f:
            f.write(googlenet_v1_deploy_prototxt(class_num=1000))
        model = load_caffe(p)

    batch = 256
    params, state = model.init_params(0)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, 224, 224), jnp.bfloat16)
    ips = _infer_throughput(model, params, state, x, batch)
    _report("inception_v1_caffe_infer_images_per_sec", ips,
            "images/sec", None)


def bench_transformer():
    """TransformerLM train step; asserts the Pallas flash-attention kernel
    is the path a bench-width shape takes and matches attention_reference
    on-device."""
    from bigdl_tpu.models.transformer import (TransformerLM,
                                              TransformerConfig)
    from bigdl_tpu.observability.profile import specs
    from bigdl_tpu.ops import flash_attention_mod as fa

    def _rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.abs(got - want).max()
                     / max(np.abs(want).max(), 1e-6))

    # --- Pallas path eligibility + numerics parity ------------------- #
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 8, 512, 128), jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 8, 512, 128), jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 8, 512, 128), jnp.bfloat16)
    path, why = fa.attention_path(q.shape, k.shape, q.dtype)
    assert path == "pallas", f"flash attention takes {path}: {why}"
    err = _rel_err(fa.flash_attention(q, k, v, causal=True),
                   fa.attention_reference(q, k, v, causal=True))
    assert err < 3e-2, f"pallas vs reference mismatch: {err}"
    _emit("flash_attention_pallas_parity", round(float(err), 6), "rel_err")

    # backward kernels: d(sum(attn))/d{q,k,v} Pallas vs reference
    def s_pallas(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def s_ref(q, k, v):
        return jnp.sum(fa.attention_reference(q, k, v, causal=True)
                       .astype(jnp.float32))

    gp = jax.grad(s_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(s_ref, argnums=(0, 1, 2))(q, k, v)
    gerr = max(_rel_err(a, b) for a, b in zip(gp, gr))
    assert gerr < 6e-2, f"pallas bwd vs reference mismatch: {gerr}"
    _emit("flash_attention_pallas_bwd_parity", round(gerr, 6), "rel_err")

    mcfg = TransformerConfig(vocab_size=32000, d_model=1024, n_heads=8,
                             n_layers=8, d_ff=4096, max_len=2048,
                             dropout=0.0, dtype="bfloat16")
    model = TransformerLM(mcfg)
    B, T = 8, 2048
    params = model.init(jax.random.PRNGKey(0))
    rng_np = np.random.RandomState(1)
    tokens = jnp.asarray(rng_np.randint(0, 32000, (B, T)), jnp.int32)

    # decode throughput through the kv cache (TransformerLM.generate)
    prompt = tokens[:, :128]
    n_new = 128
    np.asarray(model.generate(params, prompt, n_new))      # compile
    per = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        np.asarray(model.generate(params, prompt, n_new))
        per.append(time.perf_counter() - t0)
    dec_s = float(np.median(per))
    _emit("transformer_lm_decode_tokens_per_sec",
          round(B * n_new / dec_s, 2), "tokens/sec")

    tok_s, params = _lm_train_tok_per_sec(model, B, T, seed=1)
    # MFU: ~6 FLOPs per param per token (fwd+bwd) + attention term
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    attn_flops = 12 * mcfg.n_layers * mcfg.d_model * T  # per token
    flops_per_tok = 6 * n_params + attn_flops
    peak = specs.require_chip()[1].peak_flops   # unknown device: an error
    mfu = tok_s * flops_per_tok / peak * 100
    _emit("transformer_lm_train_tokens_per_sec", round(tok_s, 2),
          "tokens/sec", round(mfu, 2))


def _lm_train_tok_per_sec(model, B, T, k=5, seed=2):
    """Shared LM train-step timing: full state threaded through the scan
    (the only valid throughput protocol — scripts/README.md), side
    losses (MoE aux) included."""
    from bigdl_tpu.nn.module import Ctx
    from bigdl_tpu.optim import SGD

    V = model.cfg.vocab_size
    params = model.init(jax.random.PRNGKey(0))
    method = SGD(learning_rate=0.1)
    opt_state = method.init_state(params)
    rng_np = np.random.RandomState(seed)
    tokens = jnp.asarray(rng_np.randint(0, V, (B, T)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)
    key = jax.random.PRNGKey(1)

    def scan_step(carry, i, tokens, targets):
        p, o = carry

        def loss_fn(pp):
            ctx = Ctx(state={}, training=True,
                      rng_key=jax.random.fold_in(key, i))
            loss = model.loss(pp, tokens, targets, ctx=ctx)
            for sl in ctx.side_losses:      # e.g. Switch aux loss
                loss = loss + sl
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, o = method.update(grads, p, o)
        return (p, o), loss

    sec = _time_scanned(scan_step, (params, opt_state), (tokens, targets),
                        k)
    return B * T / sec, params


def bench_moe():
    """Switch-routed MoE TransformerLM train step on one chip (the
    expert-parallel 'ep' sharding is a mesh concern; single-chip this
    measures the fixed-capacity one-hot dispatch + batched expert
    einsum path, nn/moe.py)."""
    from bigdl_tpu.models.transformer import TransformerLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=32000, d_model=1024, n_heads=8,
                            n_layers=4, d_ff=4096, max_len=1024,
                            dropout=0.0, dtype="bfloat16",
                            moe_experts=8, moe_top_k=1)
    tok_s, _ = _lm_train_tok_per_sec(TransformerLM(cfg), B=8, T=1024)
    _report("moe_switch_lm_train_tokens_per_sec", tok_s, "tokens/sec",
            None)


def bench_int8():
    """Post-training int8 ResNet-50 inference (≙ the reference's
    quantized-model serving path, nn/quantized/): int8 weights +
    runtime-quantized activations through the MXU int8 conv path."""
    from bigdl_tpu.models import resnet
    from bigdl_tpu.quantized import quantize
    from bigdl_tpu.nn.fusion import fold_batchnorm

    model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                         format="NHWC")
    model.reset(0)
    batch = 256
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    # fold BN into conv weights (nn/fusion.py: exact at eval), then
    # calibrate static activation scales — together they remove both the
    # per-BN elementwise pass and the per-batch |x| reduction in front
    # of every int8 conv (quantized/__init__.py)
    qmodel = quantize(fold_batchnorm(model), calibration_data=[x[:32]])
    params = qmodel.ensure_initialized()
    state = qmodel._state or {}
    ips = _infer_throughput(qmodel, params, state, x, batch)
    _report("resnet50_int8_infer_images_per_sec", ips, "images/sec", None)


def bench_resnet50():
    # NHWC is the TPU-native layout (no transpose pairs around NCHW
    # batch-norms).  Honest full-step throughput is layout-insensitive
    # here (~2,600 img/s b256 — the step is backward/BN-bound, see
    # docs/performance.md); the earlier "2.7x NHWC" figure was a
    # forward-only measurement artifact.
    #
    # Env knobs so the scripts/README.md decision rules (flip s2d stem
    # if K2 wins, remat+b512 if K8 wins) are a one-line change for the
    # ladder run, not a code edit:
    #   BENCH_RESNET_STEM=s2d|conv  BENCH_RESNET_REMAT=1  BENCH_RESNET_BATCH=N
    from bigdl_tpu.models import resnet
    stem = os.environ.get("BENCH_RESNET_STEM", "conv")
    remat = _env_bool("BENCH_RESNET_REMAT")
    batch = int(os.environ.get("BENCH_RESNET_BATCH", "256"))
    model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                         format="NHWC", stem=stem, remat=remat)
    ips = _train_throughput(model, (batch, 224, 224, 3), 1000, batch, k=20)
    _report("resnet50_train_images_per_sec_per_chip", ips, "images/sec",
            57.0)


CONFIGS = {
    "lenet": bench_lenet,
    "vgg16": bench_vgg16,
    "lstm": bench_lstm,
    "inception": bench_inception,
    "int8": bench_int8,
    "moe": bench_moe,
    "transformer": bench_transformer,
    "resnet50": bench_resnet50,   # headline: runs and prints last
}


def main():
    from bigdl_tpu.observability.profile import specs
    from bigdl_tpu.utils.engine import enable_compile_cache

    names = sys.argv[1:] or list(CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        sys.exit(f"unknown bench config(s) {unknown}; "
                 f"choose from {list(CONFIGS)}")
    info, _ = specs.require_chip()      # no TPU, no known peaks: fail here
    _DEVICE.update(platform=info["platform"], device_kind=info["kind"],
                   devices=info["count"])
    enable_compile_cache()
    failed = []
    for name in names:
        try:
            CONFIGS[name]()
        except Exception:   # the other configs still report; exit says so
            failed.append(name)
            print(f"# bench {name} failed:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failed:
        sys.exit(f"bench configs failed: {failed}")


if __name__ == "__main__":
    main()
