"""Pages the live slots hold, a layer, over what one kind of table for
every layer would hold: the global kind's pages in use are what a table
as long as the context takes, the window kind's what a ring takes; from
the two gauges, sampled over the traced seconds."""
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    samples, traced = ctx["facts"].get("counter_samples"), ctx["probe"].traced
    if not samples or not traced or not _window_moe.is_cell(ctx):
        return None
    inside = [s for t, s in samples if traced[0] <= t <= traced[1]
              and "kv_pages_in_use_global" in s]
    glob = sum(s["kv_pages_in_use_global"] for s in inside)
    ring = sum(s["kv_pages_in_use_window"] for s in inside)
    if not glob:
        return None
    m = window_moe.dims(ctx["facts"]["config"])
    return 100.0 * (glob * m["n_global"] + ring * m["n_window"]) \
        / (glob * m["n_layers"])
