"""Of the cached rows that were live in the window layers of the window's
decode steps, the share their queries may see: `attn/rows_attended_window`
over those layers' part of `attn/rows_live` (which counts every layer)."""
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    f = ctx["facts"]
    if not f.get("attn_rows_live") or not _window_moe.is_cell(ctx):
        return None
    m = window_moe.dims(f["config"])
    if not m["n_window"]:
        return None
    live = f["attn_rows_live"] * m["n_window"] / m["n_layers"]
    return 100.0 * f["attn_rows_attended_window"] / live
