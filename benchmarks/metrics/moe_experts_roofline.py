"""The routed experts' grouped matmuls (`_moe_experts`) in the decode
step: their roofline seconds (every touched expert's three matrices read
once) over their ops' device time in the trace."""
from benchmarks.flops import sparse_moe
from benchmarks.metrics import _sparse_moe


def read(ctx):
    return _sparse_moe.kernel_roofline(
        ctx, "_moe_experts", lambda cfg, c: sparse_moe.moe_experts_cost(
            cfg, c["moe_pairs"], c["moe_experts_touched"]))
