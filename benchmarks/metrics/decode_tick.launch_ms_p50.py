"""Median over ticks of `decode.schedule` + `decode.stage` +
`decode.dispatch`: slot bookkeeping, the small uploads and the launch."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read(ctx, "decode_tick.launch_ms_p50")
