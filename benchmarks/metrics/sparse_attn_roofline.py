"""The gather of the selected rows and their attention (`_sparse_attend`)
in the decode step: its roofline seconds (the selected K and V rows read
once) over its ops' device time in the trace."""
from benchmarks.flops import sparse_moe
from benchmarks.metrics import _sparse_moe


def read(ctx):
    return _sparse_moe.kernel_roofline(
        ctx, "_sparse_attend", lambda cfg, c: sparse_moe.sparse_attend_cost(
            cfg, c["sparse_rows_attended"]))
