"""Forward flash kernel's share of its roofline (device trace)."""
from benchmarks.metrics import _flash


def read(ctx):
    return _flash.roofline_share(ctx, "fwd")
