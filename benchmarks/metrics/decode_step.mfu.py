"""The serving window's share of the chip's bf16 peak: forward FLOPs of
every token prefilled and decoded (each query against the keys it sees)
over the time from the window's opening to its last token."""
from benchmarks.flops import lm


def read(ctx):
    f = ctx["facts"]
    if not f.get("served"):
        return None
    cfg, flops = f["config"], 0.0
    for n_prompt, n_new in f["served"]:
        flops += lm.forward_flops_sequence(cfg, n_prompt)
        flops += sum(lm.decode_flops(cfg, n_prompt + i)
                     for i in range(1, n_new))
    return 100.0 * flops / f["busy_span_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"])
