"""The routed experts' grouped matmuls (`_moe_experts`) in this cell's
decode step: their roofline seconds (every touched expert's three
matrices read once, a layer) over their ops' device time in the trace."""
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    def cost(cfg, c):
        n = cfg["num_hidden_layers"]
        flops, nbytes = window_moe.moe_experts_cost(
            cfg, c["moe_pairs"] / n, c["moe_experts_touched"] / n)
        return n * flops, n * nbytes
    return _window_moe.piece_roofline(ctx, "_moe_experts", cost)
