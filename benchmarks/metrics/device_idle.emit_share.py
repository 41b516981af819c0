"""The device's idle time, in gaps of 20 us or more, that lies under
`decode.emit` (tokens handed to their streams) once the engine's spans
are on the device trace's clock, in percent of all of it
(`_device_clock`)."""
from benchmarks.metrics import _device_clock


def read(ctx):
    return _device_clock.share(ctx, "emit")
