"""Collective-traffic analysis for DistriOptimizer steps: compile the real dp / fsdp train step on a virtual mesh and
read bytes-on-wire per step out of the partitioned HLO, giving
BASELINE.md's scaling-efficiency row a measured basis (the reference
sizes its all-reduce the same way from AllReduceParameter block counts,
parameters/AllReduceParameter.scala:222).

Usage:  python scripts/collective_volume.py [dp] [model]
        dp: mesh size (default 8; 16 works via more virtual devices)
        model: resnet50 | lenet | mlp (default resnet50)

Prints one JSON line:
  {"dp": N, "model": ..., "collective_bytes_per_step": B,
   "grad_bytes": G, "flops_per_step": F, "bytes_per_flop": r,
   "min_ici_gbps_for_95pct": bw}

`min_ici_gbps_for_95pct` = bandwidth needed so collective time stays
under 5% of compute time at 197 TFLOP/s bf16 peak x 40% MFU — the
condition for >=0.95 scaling efficiency with non-overlapped collectives
(overlap only lowers the requirement).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":    # importable from tests without argv/env side effects
    dp = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    model_name = sys.argv[2] if len(sys.argv) > 2 else "resnet50"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={dp}")
else:
    dp, model_name = 8, "mlp"

import numpy as np
import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.observability.profile.specs import lookup
from bigdl_tpu.optim import SGD
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib

# one parser, shared with the runtime telemetry (SpmdTrainer's
# account_collectives) so the test budget and the live numbers can't drift
from bigdl_tpu.observability.collectives import (
    hlo_collective_ops as _hlo_collective_ops)


def collective_bytes(hlo_text, n_shards):
    """Per-chip bytes moved over the interconnect per step, from the
    partitioned HLO's collective ops.

    Ring costs per chip for S bytes of result/input over a ring of n
    (n = the op's replica-group size, NOT the global device count —
    a tp=2 all-reduce on an 8-chip mesh rides rings of 2):
      all-reduce:      2*S*(n-1)/n   (reduce-scatter + all-gather)
      all-gather:        S*(n-1)/n   (S = full gathered size)
      reduce-scatter:    S*(n-1)/n   (S = full pre-scatter size)
      collective-permute: S
    """
    return _hlo_collective_ops(hlo_text, n_shards)


def build(model_name):
    if model_name == "resnet50":
        from bigdl_tpu.models import resnet
        model = resnet.build(class_num=1000, depth=50, dataset="imagenet",
                             format="NHWC")
        x = np.zeros((dp, 224, 224, 3), np.float32)
        y = np.ones((dp,), np.float32)
        crit = nn.ClassNLLCriterion()
    elif model_name == "lenet":
        from bigdl_tpu.models import lenet
        model = lenet.build(class_num=10)
        x = np.zeros((dp, 1, 28, 28), np.float32)
        y = np.ones((dp,), np.float32)
        crit = nn.ClassNLLCriterion()
    else:
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 10), nn.LogSoftMax())
        x = np.zeros((dp, 64), np.float32)
        y = np.ones((dp,), np.float32)
        crit = nn.ClassNLLCriterion()
    return model, crit, x, y


def main():
    mesh = mesh_lib.create_mesh({"dp": dp})
    model, crit, x, y = build(model_name)
    opt = DistriOptimizer(model, (x, y), crit, batch_size=dp, mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    params, _ = model.init_params(0)
    optim = opt._wrap_optim(params)
    step_fn, _ = opt._build_step(params, optim)
    opt_state = optim.init_state(params)
    model_state = model.init_params(0)[1] or {}
    rng = jax.random.PRNGKey(0)
    lowered = step_fn.lower(params, opt_state, model_state,
                            jnp.asarray(x), jnp.asarray(y), rng)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    ops = collective_bytes(hlo, dp)
    wire = sum(w for _, _, w in ops)
    grad_bytes = sum(int(np.prod(p.shape)) * 4
                     for p in jax.tree_util.tree_leaves(params))
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    flops = float((cost or {}).get("flops", 0.0))
    # bandwidth so that collective_time <= 5% of compute_time at a
    # TPU v5e's bf16 peak x 40% MFU per chip (a modelling target: this
    # script itself runs on a CPU mesh)
    v5e = lookup("TPU v5e").peak_flops
    compute_s = flops / (v5e * 0.40) if flops else float("nan")
    bw_gbps = (wire / (0.05 * compute_s)) / 1e9 if compute_s and \
        compute_s == compute_s else None
    print(json.dumps({
        "dp": dp, "model": model_name,
        "collective_ops": len(ops),
        "collective_bytes_per_step": round(wire),
        "grad_bytes": grad_bytes,
        "allreduce_theory_bytes": round(2 * grad_bytes * (dp - 1) / dp),
        "flops_per_step": flops,
        "bytes_per_flop": round(wire / flops, 9) if flops else None,
        "min_ici_gbps_for_95pct": round(bw_gbps, 2) if bw_gbps else None,
    }))


if __name__ == "__main__":
    main()
