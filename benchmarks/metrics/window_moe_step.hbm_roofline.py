"""Decode step's share of its memory roofline: the bytes a step must read
(non-expert weights and the head once, the experts the step's tokens
touched, the K and V rows its queries may see, min(context, window) in a
window layer: all from the program's counters over the traced seconds) at
the chip's HBM rate, over the median device duration of the decode
step."""
import statistics

from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    if not _window_moe.is_cell(ctx):
        return None
    per_step = _window_moe.traced_counts(ctx)
    steps = _window_moe.decode_steps(ctx["trace"])
    if per_step is None or not steps or "attn_rows_live" not in per_step:
        return None
    nbytes = window_moe.decode_step_bytes(
        ctx["facts"]["config"], per_step["moe_experts_touched"],
        _window_moe.rows_attended(per_step))
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] \
        / statistics.median(steps)
