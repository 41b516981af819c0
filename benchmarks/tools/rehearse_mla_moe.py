"""`tools/rehearse_sparse_moe.py` for a serving cell whose runner builds
its model through `mla_moe_program`: compile the decode step or the
prefill chunk at real size for a DESCRIBED v5e chip (no chip attached)
and print memory_analysis().  By hand, on the CPU:

    JAX_PLATFORMS=cpu python benchmarks/tools/rehearse_mla_moe.py WORKLOAD decode|chunk [hlo-out-file]

That tool's `compile_program` asks `sparse_moe_program` for the model;
here it is handed this cell's builder in its place for the call.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.runners import mla_moe_program, sparse_moe_program
from benchmarks.tools import rehearse_sparse_moe

if __name__ == "__main__":
    sparse_moe_program.build_model = mla_moe_program.build_model
    rehearse_sparse_moe.main(*sys.argv[1:])
