"""The serving window's share of the chip's bf16 peak: forward FLOPs of
every token prefilled and decoded (attention over min(context, window)
keys in window layers and the whole context in global ones, top-k experts
a token) over the time from the window's opening to its last token."""
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    f = ctx["facts"]
    if not f.get("served") or not _window_moe.is_cell(ctx):
        return None
    flops = sum(window_moe.sequence_flops(f["config"], n_prompt, n_new)
                for n_prompt, n_new in f["served"] if n_new)
    return 100.0 * flops / f["busy_span_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"])
