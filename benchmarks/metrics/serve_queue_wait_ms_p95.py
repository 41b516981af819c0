"""p95 over the window's requests of `decode.queue`: arrival to the
start of the request's prefill, up to one tick when slots are free."""
from benchmarks.metrics import _ticks


def read(ctx):
    return _ticks.read_queue_wait(ctx)
