"""Median over ticks of (tick - `decode.sync`): the host's serial part of
a token, everything that is not waiting for the step."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read(ctx, "decode_tick.host_ms_p50")
