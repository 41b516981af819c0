"""Hand-tuned TPU kernels (Pallas) and their blockwise-XLA routes.

The reference gets its hot-loop speed from Intel MKL primitives
(spark/dl ... tensor/TensorNumeric + the mkl native wrappers); on TPU the
equivalent role is played by Pallas kernels feeding the MXU, with pure-XLA
blockwise routes so every op also runs (and is differentiable) on CPU;
``attention_path`` says which one a shape takes.
"""
# keep a non-shadowed module alias: the next line rebinds the package
# attribute `flash_attention` to the *function*, so consumers that need
# module internals (_Config, _INTERPRET) import this alias
from . import flash_attention as flash_attention_mod  # noqa: F401
from .flash_attention import (flash_attention, attention_reference,
                              attention_path)
from . import paged_attention as paged_attention_mod  # noqa: F401
from .paged_attention import paged_attention, paged_attention_path

__all__ = ["flash_attention", "attention_reference", "attention_path",
           "flash_attention_mod", "paged_attention",
           "paged_attention_path", "paged_attention_mod"]
