"""p95 over ticks of the tick's `decode.admit` (next to nothing in most):
what an admission's prefill adds to the tail token of every live slot."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read(ctx, "decode_tick.admit_ms_p95")
