"""Decode step's share of its memory roofline: the bytes a step must read
(non-expert weights and the head once, the experts the step's tokens
touched, the live index keys, the min(context, top-k) K and V rows a
slot: all from the program's counters over the traced seconds) at the
chip's HBM rate, over the median device duration of the decode step."""
import statistics

from benchmarks.flops import sparse_moe
from benchmarks.metrics import _sparse_moe


def read(ctx):
    per_step = _sparse_moe.traced_counts(ctx)
    steps = _sparse_moe.decode_steps(ctx["trace"])
    if per_step is None or not steps:
        return None
    nbytes = sparse_moe.decode_step_bytes(
        ctx["facts"]["config"], per_step["moe_experts_touched"],
        per_step["sparse_rows_scored"], per_step["sparse_rows_attended"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] \
        / statistics.median(steps)
