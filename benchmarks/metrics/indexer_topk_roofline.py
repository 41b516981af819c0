"""The indexer's scoring and top-k (`_index_topk`) in the decode step:
its roofline seconds (every live index key read once, 16 x 64 MACs a key)
over its ops' device time in the trace."""
from benchmarks.flops import sparse_moe
from benchmarks.metrics import _sparse_moe


def read(ctx):
    return _sparse_moe.kernel_roofline(
        ctx, "_index_topk", lambda cfg, c: sparse_moe.index_topk_cost(
            cfg, c["sparse_rows_scored"]))
