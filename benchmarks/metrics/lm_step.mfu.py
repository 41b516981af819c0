"""Whole training step's share of the chip's bf16 peak: FLOPs a token
needs (forward + backward, causal attention once, no recomputation) times
the tokens per second of this run's whole window."""
from benchmarks.flops import lm


def read(ctx):
    f = ctx["facts"]
    if "tokens_per_s" not in f:
        return None
    flops = lm.train_flops_per_token(f["config"], f["seq_len"])
    chips = ctx["cell"].chips
    return 100.0 * flops * f["tokens_per_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
