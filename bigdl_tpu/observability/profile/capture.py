"""Static cost/memory capture from compiled XLA executables.

XLA already knows, at compile time, exactly what a program will do:
``cost_analysis()`` reports FLOPs and bytes accessed, and
``memory_analysis()`` the peak-HBM budget (argument / output / temp /
generated-code sizes).  The BigDL paper's whole evaluation is "how
close to the roofline do we run" — these numbers ARE the roofline
inputs, so they get harvested once per compile (a trace + analysis
pass, never per step) and attached to the Recorder:

  * :func:`capture_compiled` — harvest one executable into a plain
    dict, with every missing backend capability recorded in an
    ``unavailable`` list instead of raising.
  * :func:`aot_capture` — lower a jitted fn at the given args' avals
    (``ShapeDtypeStruct`` — lowering never touches, let alone donates,
    the real buffers) and capture its compiled form.
  * :class:`StepCostModel` — compiled cost + a
    :class:`~bigdl_tpu.observability.profile.specs.DeviceSpec`;
    ``scalars(dur)`` derives the per-step efficiency ratios
    (``perf/mfu``, ``perf/hbm_bw_util``, ``mem/peak_hbm_bytes``) the
    Recorder folds into every step record.
  * :func:`capture_and_attach` — the one-stop wiring used by
    Optimizer / SpmdTrainer: capture, attach the cost model, set the
    gauges, emit one out-of-band ``profile`` record.  Never raises.
  * :func:`install_device_memory_poller` — live ``mem/device.*``
    gauges from ``jax.local_devices()`` ``memory_stats()``, refreshed
    on every Recorder snapshot (i.e. every /metrics scrape).
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional

from .specs import DeviceSpec, device_spec

#: memory_analysis attributes worth keeping, recorder-key by XLA name
_MEM_FIELDS = (("argument_size_in_bytes", "argument_bytes"),
               ("output_size_in_bytes", "output_bytes"),
               ("temp_size_in_bytes", "temp_bytes"),
               ("generated_code_size_in_bytes", "generated_code_bytes"),
               ("alias_size_in_bytes", "alias_bytes"))


def capture_enabled() -> bool:
    """``BIGDL_PROFILE_CAPTURE=0`` kills static cost capture for runs
    where even one extra trace+compile per step-build is unwelcome."""
    return os.environ.get("BIGDL_PROFILE_CAPTURE", "1").lower() \
        not in ("0", "false", "off")


def _finite(v) -> Optional[float]:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


def capture_compiled(compiled) -> Dict[str, Any]:
    """Harvest cost/memory analysis from one compiled executable.

    Returns a plain JSON-able dict; capabilities the backend doesn't
    expose land in ``unavailable`` (a list of missing analysis names)
    rather than raising — TPU/CPU expose both today, but a backend
    is allowed to expose neither."""
    out: Dict[str, Any] = {}
    unavailable = []

    ca = None
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    # jax returns one properties-dict per device program; all replicas
    # run the same program, so the first entry is THE answer
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        flops = _finite(ca.get("flops"))
        if flops is not None:
            out["flops"] = flops
        bytes_accessed = _finite(ca.get("bytes accessed"))
        if bytes_accessed is not None:
            out["bytes_accessed"] = bytes_accessed
        transcendentals = _finite(ca.get("transcendentals"))
        if transcendentals:
            out["transcendentals"] = transcendentals
    if "flops" not in out:
        unavailable.append("cost_analysis")

    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    mem_ok = False
    if ma is not None:
        for attr, key in _MEM_FIELDS:
            v = _finite(getattr(ma, attr, None))
            if v is not None:
                out[key] = v
                mem_ok = True
        if mem_ok:
            # aliased (donated) buffers are counted in both argument and
            # output sizes but occupy HBM once
            out["peak_hbm_bytes"] = (
                out.get("argument_bytes", 0.0)
                + out.get("output_bytes", 0.0)
                + out.get("temp_bytes", 0.0)
                + out.get("generated_code_bytes", 0.0)
                - out.get("alias_bytes", 0.0))
    if not mem_ok:
        unavailable.append("memory_analysis")

    if unavailable:
        out["unavailable"] = unavailable
    return out


def aot_capture(jitted, *args, avals: bool = True) -> Dict[str, Any]:
    """Lower ``jitted`` at ``args``' avals and capture its compiled
    cost.  Lowering uses ``ShapeDtypeStruct``s so no real buffer is
    read or donated; XLA's compile cache serves the executable when the
    same signature was (or will be) dispatched.  ``avals=False`` lowers
    with the arrays themselves, whose shardings abstract avals would
    drop (lowering never reads or donates them either).  Raises on
    backends without the AOT API — callers that must not fail go
    through :func:`capture_and_attach`."""
    import jax

    def aval(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf
    if avals:
        args = jax.tree_util.tree_map(aval, args)
    return capture_compiled(jitted.lower(*args).compile())


class StepCostModel:
    """Compiled per-step cost + device peaks -> derived per-step ratios.

    ``scalars(dur)`` is called by ``Recorder.end_step`` with the step's
    wall duration and must stay pure arithmetic (it runs under the
    recorder lock).  Every ratio whose numerator or denominator is
    unknown is replaced by an explicit ``*_unavailable`` marker scalar:
    a dashboard that shows nothing is ambiguous, one that shows
    "unavailable" is a statement.
    """

    __slots__ = ("cost", "spec")

    def __init__(self, cost: Dict[str, Any], spec: Optional[DeviceSpec]
                 = None):
        self.cost = dict(cost or {})
        self.spec = spec if spec is not None else DeviceSpec("unknown")

    def scalars(self, dur: Optional[float]) -> Dict[str, float]:
        out: Dict[str, float] = {}
        flops = self.cost.get("flops")
        if flops is not None and dur and self.spec.peak_flops:
            out["perf/mfu"] = flops / dur / self.spec.peak_flops
        elif flops is not None and dur:
            # compiled FLOPs known but no peak for this device: report
            # the achieved rate so the number is still actionable
            out["perf/flops_per_sec"] = flops / dur
            out["perf/mfu_unavailable"] = 1.0
        else:
            out["perf/mfu_unavailable"] = 1.0
        ba = self.cost.get("bytes_accessed")
        if ba is not None and dur and self.spec.peak_hbm_bw:
            out["perf/hbm_bw_util"] = ba / dur / self.spec.peak_hbm_bw
        else:
            out["perf/hbm_bw_util_unavailable"] = 1.0
        peak = self.cost.get("peak_hbm_bytes")
        if peak is not None:
            out["mem/peak_hbm_bytes"] = peak
            if self.spec.hbm_capacity:
                out["mem/peak_hbm_frac"] = peak / self.spec.hbm_capacity
        else:
            out["mem/peak_hbm_bytes_unavailable"] = 1.0
        return out


def attach_cost(recorder, cost: Dict[str, Any],
                kind: str = "train_step", spec: Optional[DeviceSpec]
                = None, **fields) -> StepCostModel:
    """Wire an already-captured cost dict into ``recorder``: attach a
    :class:`StepCostModel` (per-step ``perf/mfu`` etc.), set the
    ``mem/peak_hbm_bytes`` / ``profile/flops_per_step`` gauges /metrics
    renders, and emit one out-of-band ``profile`` record for JSONL
    sinks / ``trace_summary profile``."""
    if spec is None:
        spec = device_spec()
    model = StepCostModel(cost, spec)
    recorder.set_cost_model(model)
    peak = cost.get("peak_hbm_bytes")
    if isinstance(peak, (int, float)):
        recorder.gauge("mem/peak_hbm_bytes", peak)
    flops = cost.get("flops")
    if isinstance(flops, (int, float)):
        recorder.gauge("profile/flops_per_step", flops)
    recorder.emit_record("profile", kind=kind, device=spec.name,
                         peak_flops=spec.peak_flops,
                         peak_hbm_bw=spec.peak_hbm_bw,
                         hbm_capacity=spec.hbm_capacity, cost=cost,
                         **fields)
    return model


def capture_and_attach(recorder, jitted, args, kind: str = "train_step",
                       avals: bool = True, **fields) -> StepCostModel:
    """Capture ``jitted``'s compiled cost at ``args`` (:func:`aot_capture`)
    and attach it (:func:`attach_cost`).  NEVER raises — a backend
    without the analysis APIs yields a record whose cost says so."""
    try:
        with recorder.span("profile.capture"):
            cost = aot_capture(jitted, *args, avals=avals)
    except Exception as e:      # AOT API missing / lowering failed
        cost = {"unavailable": ["capture_failed"], "error": repr(e)}
    return attach_cost(recorder, cost, kind=kind, **fields)


# -- live device-memory gauges --------------------------------------------- #
def poll_device_memory(recorder):
    """One poll: ``mem/device.<id>.{bytes_in_use,peak_bytes_in_use,
    bytes_limit}`` gauges per local device, or a single
    ``mem/device.stats_unavailable`` marker on backends (CPU) whose
    ``memory_stats()`` returns nothing."""
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return
    got_any = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        got_any = True
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            v = _finite(stats.get(key))
            if v is not None:
                recorder.gauge(f"mem/device.{d.id}.{key}", v)
    if not got_any:
        recorder.gauge("mem/device.stats_unavailable", 1.0)


def install_device_memory_poller(recorder):
    """Attach :func:`poll_device_memory` as a recorder gauge poller
    (idempotent: repeated ``set_telemetry`` calls install it once)."""
    if poll_device_memory not in getattr(recorder, "_gauge_pollers", ()):
        recorder.add_gauge_poller(poll_device_memory)
    return recorder
