"""The serving window's share of the chip's bf16 peak: forward FLOPs of
every token prefilled and decoded (attention by the absorbed count over
the rows a decode step's queries may see and by the up-projected count in
a chunk, routed experts by the held pairs: all from the program's
counters over the window) over the time from the window's opening to its
last token: the share of the whole step."""
from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe


def read(ctx):
    f = ctx["facts"]
    if not f.get("served") or not _mla_moe.is_cell(ctx) \
            or "mla_rows_live" not in f:
        return None
    return 100.0 * mla_moe.window_flops(f["config"], f["served"], f) \
        / f["busy_span_s"] / (ctx["cell"].chips
                              * ctx["peaks"]["bf16_flops_per_s"])
