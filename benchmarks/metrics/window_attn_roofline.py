"""The decode step's attention (`_window_attend`: every layer's gather and
attention over its kind's table): its roofline seconds (the K and V rows
the queries may see, by the counters, read once) over its ops' device
time in the trace.  The count does not depend on the route: a kernel that
reads only those rows moves this towards 100."""
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe


def read(ctx):
    return _window_moe.piece_roofline(
        ctx, "_window_attend", lambda cfg, c: window_moe.attend_cost(
            cfg, _window_moe.rows_attended(c)))
