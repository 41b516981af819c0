"""The device's idle time, in gaps of 20 us or more, that lies under the
host's uploads and launches (`decode.schedule`, `.stage`, `.dispatch`,
`prefill.stage`, `.dispatch`) once the engine's spans are on the device
trace's clock, in percent of all of it (`_device_clock`)."""
from benchmarks.metrics import _device_clock


def read(ctx):
    return _device_clock.share(ctx, "launch")
