"""bigdl_tpu.kernels — hand-written Pallas TPU kernels for hot paths the
XLA fusion heuristics leave on the table.

The kernels lower through Mosaic.  CPU tests and CPU smokes execute the
*kernel code path itself*, not a shadow implementation, through the
Pallas interpreter — which only an explicit hook turns on
(``fused_optim._FORCE_INTERPRET``; ``tests/conftest.py`` sets it), so a
run on the chip can never quietly interpret.  (The attention kernel
predates this package and lives in :mod:`bigdl_tpu.ops.flash_attention`.)
"""
from .fused_optim import fused_adam_update, fused_sgd_update

__all__ = ["fused_adam_update", "fused_sgd_update"]
