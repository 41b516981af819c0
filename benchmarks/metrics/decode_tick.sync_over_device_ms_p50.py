"""Median `decode.sync` minus the decode program's median device time in
the trace: what the per-token sync costs beyond the device's own step
(launch latency, the copy back, the wake-up)."""
from benchmarks.metrics import _decode_program, _ticks


def read(ctx):
    sync = _ticks.read(ctx, "decode_sync.ms_p50")
    device = _decode_program.device_seconds(ctx["trace"])
    if sync is None or device is None:
        return None
    return sync - 1e3 * device
