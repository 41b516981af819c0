"""Median `decode.tick`: the engine's own gap between tokens, to stand
beside the client's `tpot_ms_p50`."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read(ctx, "decode_tick.ms_p50")
