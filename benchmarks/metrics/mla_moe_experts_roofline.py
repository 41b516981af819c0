"""The routed experts' grouped matmuls (`_moe_experts`) in this cell's
decode step: their roofline seconds (every touched expert's three
matrices read once, the held pairs' rows in and out) over their ops'
device time in the trace."""
from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe


def read(ctx):
    per_step, _ = _mla_moe.traced_rates(ctx)
    return _mla_moe.piece_roofline(
        ctx, "_moe_experts", per_step,
        len(_mla_moe.decode_steps(ctx["trace"])),
        lambda cfg, c: mla_moe.moe_experts_cost(
            cfg, c["moe_pairs"], c["moe_experts_touched"]))
