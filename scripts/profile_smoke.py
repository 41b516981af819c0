"""Cost/memory attribution smoke: MFU scalars + /trace, end to end on CPU.

What it proves in a few seconds:

  1. a CPU training run with telemetry emits one ``profile`` record
     (XLA compiled FLOPs + peak-HBM capture) and every step record
     carries ``perf/mfu`` (the env peak override makes it computable on
     CPU) and ``mem/peak_hbm_bytes``
  2. ``/metrics`` exposes the new ``bigdl_mem_peak_hbm_bytes`` /
     ``bigdl_profile_flops_per_step`` gauges
  3. a served request stream produces Chrome-trace JSON on ``/trace``
     whose admit→reply spans pair B/E correctly and share one trace ID,
     with a deadline-shed request carrying its terminal cause
  4. ``trace_summary.py profile`` renders the capture

The LAST stdout line is one parseable JSON summary
(``"metric": "profile_smoke"``); exit 0 only if every assertion held.

    python scripts/profile_smoke.py
"""
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# a fictional-but-plausible CPU peak makes perf/mfu computable here;
# a caller-provided override (e.g. CI exercising a real value) wins
os.environ.setdefault("BIGDL_PEAK_FLOPS", "1e12")
os.environ.setdefault("BIGDL_PEAK_HBM_BW", "5e10")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.nn.module import Module  # noqa: E402
from bigdl_tpu.observability import JsonlSink, Recorder  # noqa: E402
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger  # noqa: E402
from bigdl_tpu.serving import (LoadShedError, ModelRegistry,  # noqa: E402
                               ServingEngine)


def fetch(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class Scale(Module):
    def init(self, rng):
        return {self.name: {"weight": jnp.ones(())}}

    def apply(self, params, x, ctx):
        return x * params[self.name]["weight"]


def main():
    failure = []
    tmp = tempfile.mkdtemp(prefix="profile_smoke_")
    jsonl = os.path.join(tmp, "telemetry.jsonl")

    # -- 1. training run: capture + per-step efficiency scalars ---------- #
    rng = np.random.RandomState(0)
    x = rng.randn(96, 8).astype(np.float32)
    y = (rng.randint(0, 3, 96) + 1).astype(np.float32)
    model = nn.Sequential(nn.Linear(8, 3), nn.LogSoftMax())
    opt = (LocalOptimizer(model, (x, y), nn.ClassNLLCriterion(),
                          batch_size=16)
           .set_optim_method(SGD(learning_rate=0.1))
           .set_end_when(Trigger.max_epoch(1))
           .set_telemetry(Recorder(sinks=[JsonlSink(jsonl, flush_every=1)],
                                   annotate=False)))
    srv = opt.serve_metrics(port=0, watchdog=False)
    opt.optimize()

    recs = [json.loads(ln) for ln in open(jsonl) if ln.strip()]
    profiles = [r for r in recs if r.get("type") == "profile"]
    steps = [r for r in recs if r.get("type") == "step"]
    if len(profiles) != 1:
        failure.append(f"expected 1 profile record, got {len(profiles)}")
    cost = (profiles[0].get("cost") or {}) if profiles else {}
    if not cost.get("flops"):
        failure.append(f"no compiled flops in capture: {cost}")
    n_mfu = sum(isinstance(s["scalars"].get("perf/mfu"), (int, float))
                for s in steps)
    n_marked = sum(s["scalars"].get("perf/mfu_unavailable") == 1.0
                   for s in steps)
    if n_mfu + n_marked != len(steps) or not steps:
        failure.append(f"perf/mfu (or marker) missing: {n_mfu}+{n_marked}"
                       f" of {len(steps)} steps")
    if n_mfu == 0:
        failure.append("env peak set but no step carried a real perf/mfu")
    n_hbm = sum(isinstance(s["scalars"].get("mem/peak_hbm_bytes"),
                           (int, float))
                or s["scalars"].get("mem/peak_hbm_bytes_unavailable")
                == 1.0 for s in steps)
    if n_hbm != len(steps):
        failure.append("mem/peak_hbm_bytes (or marker) missing from "
                       f"{len(steps) - n_hbm} steps")

    # -- 2. /metrics gauges ---------------------------------------------- #
    code, metrics = fetch(srv.url("/metrics"))
    for needle in ("bigdl_mem_peak_hbm_bytes",
                   "bigdl_profile_flops_per_step"):
        if code != 200 or needle not in metrics:
            failure.append(f"/metrics missing {needle} (HTTP {code})")
    srv.stop()

    # -- 3. serving: /trace round-trip ----------------------------------- #
    reg = ModelRegistry()
    reg.register("m", Scale(), input_shape=(4,))
    eng = ServingEngine(reg, max_batch=8, max_delay_ms=2.0)
    eng.warmup()
    esrv = eng.serve_metrics(port=0)
    for _ in range(3):
        eng.predict("m", np.ones((2, 4), np.float32), timeout=30)
    try:
        f = eng.submit("m", np.ones((2, 4), np.float32), deadline_ms=0.0)
        time.sleep(0.02)
        f.result(timeout=30)
        failure.append("deadline-0 request was not shed")
    except LoadShedError:
        pass
    deadline = time.time() + 10
    while len(eng.trace_ring) < 4 and time.time() < deadline:
        time.sleep(0.01)

    code, body = fetch(esrv.url("/trace"))
    doc = json.loads(body) if code == 200 else {}
    evs = doc.get("traceEvents", [])
    opens, by_tid = {}, {}
    for e in evs:
        if e.get("ph") == "B":
            key = (e["tid"], e["name"])
            if key in opens:
                failure.append(f"unbalanced B {key}")
            opens[key] = e["ts"]
            by_tid.setdefault(e["tid"], []).append(
                (e["name"], e["args"].get("trace_id")))
        elif e.get("ph") == "E":
            if opens.pop((e["tid"], e["name"]), None) is None:
                failure.append(f"E without B: {e['name']}")
    if opens:
        failure.append(f"unclosed spans: {sorted(opens)}")
    full = [spans for spans in by_tid.values()
            if [n for n, _ in spans] == ["admit", "queue", "batch_gather",
                                         "compute", "reply"]]
    if not full:
        failure.append(f"no admit→reply request track in /trace: "
                       f"{ {t: [n for n, _ in s] for t, s in by_tid.items()} }")
    elif len({tid for _, tid in full[0]}) != 1:
        failure.append("admit→reply spans do not share one trace id")
    shed = [spans for spans in by_tid.values()
            if any(n == "shed" for n, _ in spans)]
    if not shed:
        failure.append("shed request left no terminal-cause track")
    bucket_costs = len(reg.get("m").cost)
    if bucket_costs == 0:
        failure.append("no per-bucket serving cost captured at warmup")
    eng.shutdown(drain=True)

    # -- 4. trace_summary renders the capture ----------------------------- #
    p = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "trace_summary.py"),
         "profile", jsonl],
        capture_output=True, text=True, timeout=60)
    if p.returncode != 0 or "train step" not in p.stdout:
        failure.append(f"trace_summary profile failed (rc={p.returncode}):"
                       f" {p.stdout[-200:]} {p.stderr[-200:]}")

    summary = {"metric": "profile_smoke", "ok": not failure,
               "steps": len(steps), "mfu_steps": n_mfu,
               "flops_per_step": cost.get("flops"),
               "peak_hbm_bytes": cost.get("peak_hbm_bytes"),
               "trace_tracks": len(by_tid),
               "bucket_costs": bucket_costs,
               "failures": failure}
    print(json.dumps(summary))
    return 0 if not failure else 1


if __name__ == "__main__":
    sys.exit(main())
