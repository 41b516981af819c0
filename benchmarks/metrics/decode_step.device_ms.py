"""Median device duration of the decode-step program in the trace;
beside TPOT it shows the host's part of a token."""
from benchmarks.metrics import _decode_program


def read(ctx):
    s = _decode_program.device_seconds(ctx["trace"])
    return None if s is None else 1e3 * s
