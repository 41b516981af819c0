"""bigdl_tpu.observability.profile — cost/memory attribution.

The PR-1/PR-4 telemetry stack measures *what happened* (spans,
counters, latency percentiles).  This package adds *attribution* —
what fraction of the hardware a step uses and where each serving
request's latency went:

  * :mod:`specs` — device peak table (TPU v2–v5p, A100/H100/V100;
    env-overridable): the one source of peaks; ``require_chip()`` is
    the measured path's gate (a TPU with known peaks, or an error).
  * :mod:`capture` — XLA ``cost_analysis``/``memory_analysis`` harvest
    from compiled executables, the :class:`StepCostModel` deriving
    per-step ``perf/mfu`` / ``perf/hbm_bw_util`` /
    ``mem/peak_hbm_bytes``, and live ``mem/device.*`` gauges.
  * :mod:`trace` — per-request trace IDs, span timelines and the
    Chrome-trace/Perfetto exporter behind ``ServingEngine.
    dump_chrome_trace()`` and the ``/trace`` endpoint.

Everything degrades gracefully: a backend without the analysis APIs
produces explicit ``unavailable`` markers, never wrong numbers and
never an exception on the training path.
"""
from __future__ import annotations

from .specs import DeviceSpec, device_spec, lookup, peak_flops
from .capture import (StepCostModel, aot_capture, attach_cost,
                      capture_and_attach, capture_compiled,
                      capture_enabled, install_device_memory_poller,
                      poll_device_memory)
from .trace import (RequestTrace, TraceRing, chrome_trace_events,
                    dump_chrome_trace)

__all__ = [
    "DeviceSpec", "device_spec", "lookup", "peak_flops",
    "StepCostModel", "aot_capture", "attach_cost", "capture_and_attach",
    "capture_compiled", "capture_enabled",
    "install_device_memory_poller", "poll_device_memory",
    "RequestTrace", "TraceRing", "chrome_trace_events",
    "dump_chrome_trace",
]
