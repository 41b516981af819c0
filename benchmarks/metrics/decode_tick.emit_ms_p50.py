"""Median `decode.emit`: per-token host work on the way out (counters,
the ledger fold, a queue put per live slot, finishes)."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read(ctx, "decode_tick.emit_ms_p50")
