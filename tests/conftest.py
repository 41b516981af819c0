"""Test env: a virtual 8-device CPU mesh.

Multi-chip sharding is validated on virtual CPU devices
(xla_force_host_platform_device_count=8); the chip itself is exercised by
``chip_smoke.py`` through the chip tool, never by pytest.
``JAX_PLATFORMS=cpu`` plus the ``XLA_FLAGS`` below, set before jax is
imported, is the whole CPU recipe.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

from bigdl_tpu.kernels import fused_optim
from bigdl_tpu.utils.engine import enable_compile_cache

# Persistent compilation cache: recompiles dominate suite wall time.
# Subprocess tests (multiprocess/dryrun workers) inherit the directory
# through JAX_COMPILATION_CACHE_DIR.
os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()

# The fused optimizer kernels lower through Mosaic unless told otherwise;
# on CPU the Pallas interpreter is the only way their bodies run.
fused_optim._FORCE_INTERPRET = True
