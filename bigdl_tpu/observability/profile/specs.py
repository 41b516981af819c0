"""Device peak-spec table: the denominators of every efficiency number.

MFU, HBM-bandwidth utilization and "how close to the memory wall" all
divide a measured quantity by a *hardware peak*.  This table is the
single source of those peaks (no script or benchmark carries its own
constant), resolved from ``jax.local_devices()[0].device_kind`` and
overridable per run via environment variables:

  ``BIGDL_PEAK_FLOPS``            peak dense FLOP/s (the MFU denominator)
  ``BIGDL_PEAK_HBM_BW``           peak HBM bytes/s
  ``BIGDL_HBM_CAPACITY_BYTES``    HBM capacity in bytes

Peaks are *per jax device* (a TensorCore on v2/v3, a chip on v4+) in
the dtype the MXU actually runs — bf16 for TPUs, bf16/fp16 tensor-core
for GPUs.  Unknown device kinds (including plain CPU) resolve to a
spec with ``None`` peaks: derived ratios are then reported as
explicitly *unavailable* rather than silently wrong.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class DeviceSpec:
    """Hardware peaks for one jax device.  ``None`` = unknown — callers
    must degrade to an explicit unavailable marker, never guess."""
    name: str
    peak_flops: Optional[float] = None      # dense FLOP/s (MXU dtype)
    peak_hbm_bw: Optional[float] = None     # bytes/s
    hbm_capacity: Optional[float] = None    # bytes

    def complete(self) -> bool:
        return None not in (self.peak_flops, self.peak_hbm_bw,
                            self.hbm_capacity)


_GIB = 1024.0 ** 3

# substring-matched against a lowercased device_kind, FIRST match wins
# (order matters: "tpu v5p" must match before "tpu v5").  Sources:
# published TPU/GPU datasheets; per-core numbers for v2/v3 where a jax
# device is one TensorCore.
_TABLE = (
    ("tpu v5p",    DeviceSpec("TPU v5p", 459e12, 2765e9, 95 * _GIB)),
    ("tpu v5 lite", DeviceSpec("TPU v5e", 197e12, 819e9, 16 * _GIB)),
    ("tpu v5e",    DeviceSpec("TPU v5e", 197e12, 819e9, 16 * _GIB)),
    ("tpu v5",     DeviceSpec("TPU v5p", 459e12, 2765e9, 95 * _GIB)),
    ("tpu v4",     DeviceSpec("TPU v4", 275e12, 1228e9, 32 * _GIB)),
    ("tpu v3",     DeviceSpec("TPU v3 core", 61.5e12, 450e9, 16 * _GIB)),
    ("tpu v2",     DeviceSpec("TPU v2 core", 22.5e12, 350e9, 8 * _GIB)),
    ("h100",       DeviceSpec("H100", 989e12, 3352e9, 80 * _GIB)),
    ("a100",       DeviceSpec("A100", 312e12, 2039e9, 80 * _GIB)),
    ("v100",       DeviceSpec("V100", 125e12, 900e9, 16 * _GIB)),
)

_ENV_FIELDS = (("BIGDL_PEAK_FLOPS", "peak_flops"),
               ("BIGDL_PEAK_HBM_BW", "peak_hbm_bw"),
               ("BIGDL_HBM_CAPACITY_BYTES", "hbm_capacity"))


def lookup(device_kind: str) -> DeviceSpec:
    """Table lookup by device kind; unknown kinds get a no-peaks spec
    named after themselves (so reports still say WHAT was measured)."""
    kind = str(device_kind).lower()
    for needle, spec in _TABLE:
        if needle in kind:
            return spec
    return DeviceSpec(str(device_kind))


def _apply_env(spec: DeviceSpec) -> DeviceSpec:
    for var, field_name in _ENV_FIELDS:
        raw = os.environ.get(var)
        if not raw:
            continue
        try:
            spec = replace(spec, **{field_name: float(raw)})
        except ValueError:
            pass        # a malformed override must not kill training
    return spec


def device_spec(device=None) -> DeviceSpec:
    """The spec for ``device`` (default: first local jax device) with
    env overrides applied.  Never raises: a backend that fails to
    initialize yields an ``unknown`` spec, and env overrides still
    apply (the CPU-CI escape hatch for exercising real MFU numbers)."""
    kind = "unknown"
    try:
        if device is None:
            import jax
            device = jax.local_devices()[0]
        kind = device.device_kind
    except Exception:
        pass
    return _apply_env(lookup(kind))


def require_chip() -> Tuple[Dict[str, object], DeviceSpec]:
    """The gate of the measured path (``bench.py``, ``chip_smoke.py``):
    the device as jax reports it — ``{"platform", "kind", "count"}`` —
    and its spec, or a ``RuntimeError``.  Unlike :func:`device_spec`
    this raises: a measurement never falls back to the CPU and never
    divides by a peak the table does not know."""
    import jax
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"no TPU: jax's default backend is {jax.default_backend()!r}; "
            "this runs on the chip (through the chip tool) or not at all")
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    spec = device_spec(devices[0])
    if not spec.complete():
        raise RuntimeError(
            f"no peaks for device kind {info['kind']!r} in "
            f"observability/profile/specs.py ({spec})")
    return info, spec


def peak_flops(default: Optional[float] = None) -> Optional[float]:
    """Resolved peak FLOP/s: env override > device table > ``default``.
    The scripts' one-liner replacement for their hardcoded constants."""
    spec = device_spec()
    return spec.peak_flops if spec.peak_flops is not None else default
