"""TransformerLM train-step tuning matrix (needs the chip: run it
through the chip tool).

Sweeps flash-attention block sizes and batch/seq shapes for the bench.py
transformer config and prints tokens/sec + MFU per point, so the bench
can pin the best configuration.

Usage:  python scripts/transformer_tuning.py [matrix|blocks|profile]
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from bigdl_tpu.models.transformer import (TransformerLM,        # noqa: E402
                                          TransformerConfig,
                                          lm_cross_entropy)
from bigdl_tpu.optim import SGD                                 # noqa: E402
from bigdl_tpu.observability.profile import specs               # noqa: E402

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


def measure(B, T, n_layers=8, d_model=1024,
            n_heads=8, d_ff=4096, k=5, trials=3, remat=False):
    cfg = TransformerConfig(vocab_size=32000, d_model=d_model,
                            n_heads=n_heads, n_layers=n_layers, d_ff=d_ff,
                            max_len=max(T, 2048), dropout=0.0,
                            dtype="bfloat16", remat=remat)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    method = SGD(learning_rate=0.1)
    opt_state = method.init_state(params)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 32000, (B, T)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)
    key = jax.random.PRNGKey(1)

    @jax.jit
    def many(params, opt_state, tokens, targets):
        def body(carry, i):
            p, o = carry

            def loss_fn(pp):
                logits, _ = model.run(pp, tokens, training=True,
                                      rng=jax.random.fold_in(key, i))
                return lm_cross_entropy(logits, targets)
            loss, grads = jax.value_and_grad(loss_fn)(p)
            p, o = method.update(grads, p, o)
            return (p, o), loss
        (p, o), losses = lax.scan(body, (params, opt_state), jnp.arange(k))
        return p, o, losses

    p, o, losses = many(params, opt_state, tokens, targets)
    float(jnp.sum(losses))
    l = lat()
    per = []
    for _ in range(trials):
        t0 = time.perf_counter()
        p, o, losses = many(params, opt_state, tokens, targets)
        float(jnp.sum(losses))
        per.append((time.perf_counter() - t0 - l) / k)
    sec = float(np.median(per))
    tok_s = B * T / sec
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    flops_per_tok = 6 * n_params + 12 * n_layers * d_model * T
    mfu = tok_s * flops_per_tok / PEAK_FLOPS * 100
    return tok_s, mfu


def matrix():
    ok = 0
    for B, T in ((8, 2048), (16, 2048), (4, 4096), (32, 1024)):
        try:
            tok_s, mfu = measure(B, T)
            print(f"B={B:3d} T={T:5d}: {tok_s:10.0f} tok/s  mfu={mfu:5.1f}%",
                  flush=True)
            ok += 1
        except Exception as e:
            print(f"B={B:3d} T={T:5d}: failed {type(e).__name__}: {e}",
                  flush=True)
    # a sweep where NOTHING measured is a wedge, not a result — exit
    # non-zero so tpu_queue does not sentinel it as complete (per-point
    # failures like an OOM corner stay best-effort)
    if ok == 0:
        sys.exit(1)


def blocks():
    # block sizes are consumed inside models/transformer via
    # flash_attention defaults; patch them per point
    import bigdl_tpu.models.transformer as tr
    orig = tr.flash_attention
    ok = 0
    for bq, bk in ((128, 128), (256, 256), (128, 512), (512, 512),
                   (256, 512)):
        tr.flash_attention = (lambda q, k, v, bq=bq, bk=bk, **kw:
                              orig(q, k, v, block_q=bq, block_k=bk,
                                   **{x: y for x, y in kw.items()
                                      if x not in ("block_q", "block_k")}))
        try:
            tok_s, mfu = measure(8, 2048)
            print(f"bq={bq:3d} bk={bk:3d}: {tok_s:10.0f} tok/s  "
                  f"mfu={mfu:5.1f}%", flush=True)
            ok += 1
        except Exception as e:
            print(f"bq={bq:3d} bk={bk:3d}: failed {type(e).__name__}: {e}",
                  flush=True)
    tr.flash_attention = orig
    if ok == 0:
        sys.exit(1)


def profile():
    import os
    tok_s, mfu = measure(8, 2048, k=2, trials=1)
    print(f"warm: {tok_s:.0f} tok/s mfu={mfu:.1f}%")
    os.makedirs("/tmp/tpu_trace_tr", exist_ok=True)
    with jax.profiler.trace("/tmp/tpu_trace_tr"):
        measure(8, 2048, k=2, trials=1)
    print("trace written to /tmp/tpu_trace_tr", flush=True)


if __name__ == "__main__":
    cmd = sys.argv[1] if len(sys.argv) > 1 else "matrix"
    {"matrix": matrix, "blocks": blocks, "profile": profile}[cmd]()
