"""Backward flash kernels' (dk/dv + dq) share of their roofline."""
from benchmarks.metrics import _flash


def read(ctx):
    return _flash.roofline_share(ctx, "bwd")
