"""The serving window's share of the chip's bf16 peak: forward FLOPs of
every token prefilled and decoded (attention over min(context, top-k)
keys, the indexer over the whole context, top-k experts a token) over the
time from the window's opening to its last token."""
from benchmarks.flops import sparse_moe


def read(ctx):
    f = ctx["facts"]
    if not f.get("served") or "sa_config" not in f.get("config", {}):
        return None
    flops = sum(sparse_moe.sequence_flops(f["config"], n_prompt, n_new)
                for n_prompt, n_new in f["served"] if n_new)
    return 100.0 * flops / f["busy_span_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"])
