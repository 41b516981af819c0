"""Decode step's share of its memory roofline: the bytes a step must read
(what every chip holds of the layers and the head once, the routed
experts the step's tokens touched, the latent rows its queries may see:
from the program's counters over the traced seconds) at the chip's HBM
rate, over the median device duration of the decode step."""
import statistics

from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe


def read(ctx):
    if not _mla_moe.is_cell(ctx):
        return None
    per_step, _ = _mla_moe.traced_rates(ctx)
    steps = _mla_moe.decode_steps(ctx["trace"])
    if per_step is None or not steps or "mla_rows_live" not in per_step:
        return None
    nbytes = mla_moe.decode_step_bytes(
        ctx["facts"]["config"], per_step["moe_experts_touched"],
        per_step["mla_rows_live"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] \
        / statistics.median(steps)
