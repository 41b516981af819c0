"""Health-layer smoke: live introspection + NaN fault + flight recorder.

What it proves, end to end, on CPU in a few seconds:

  1. a trainer with ``serve_metrics()`` answers ``/metrics`` (valid
     Prometheus text) and ``/healthz`` (ok) WHILE training runs
  2. a NaN injected into one batch (a scaled-input fault at
     ``--inject-step``) trips the sentinel at exactly that step and
     raises ``DivergenceError``
  3. the crash flight recorder leaves a ``flight_<ts>.json`` containing
     the divergence events and the preceding ring of step records

Scrapes go through real ``curl`` when available (the CI path), else
urllib.  The LAST stdout line is one parseable JSON summary
(``"metric": "health_smoke"``); exit 0 only if every assertion held.

    python scripts/health_smoke.py [--steps 50] [--inject-step 30]
"""
import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.data.dataset import DataSet  # noqa: E402
from bigdl_tpu.data.minibatch import MiniBatch  # noqa: E402
from bigdl_tpu.observability import (DivergenceError, InMemorySink,  # noqa: E402
                                     Recorder)
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger  # noqa: E402


def fetch(url):
    """(status, body) via curl when present — the CI job's literal
    'curl the endpoints' — else urllib."""
    if shutil.which("curl"):
        p = subprocess.run(
            ["curl", "-s", "-o", "-", "-w", "\n%{http_code}", url],
            capture_output=True, text=True, timeout=10)
        body, _, code = p.stdout.rpartition("\n")
        return int(code or 0), body
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class SlowedPoisonedDataSet:
    """Wraps an array dataset: ~delay_ms per batch (so the scraper has a
    live run to probe) and a NaN scaled into batch ``inject_at``'s
    input — the fault that must surface as a step-K health event."""

    def __init__(self, inner, inject_at, delay_ms):
        self.inner = inner
        self.inject_at = inject_at
        self.delay = delay_ms / 1e3

    def data(self, train=True, epoch=None):
        try:
            it = self.inner.data(train=train, epoch=epoch)
        except TypeError:
            it = self.inner.data(train=train)
        for i, mb in enumerate(it):
            if self.delay:
                time.sleep(self.delay)
            if i == self.inject_at:
                x = np.array(mb.get_input())
                x[0] *= np.nan               # scaled-input fault
                mb = MiniBatch(x, mb.get_target())
            yield mb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50,
                    help="batches in the run (one epoch)")
    ap.add_argument("--inject-step", type=int, default=30,
                    help="1-based step whose batch gets the NaN")
    ap.add_argument("--step-delay-ms", type=float, default=20.0)
    ap.add_argument("--port", type=int, default=0,
                    help="introspection port (0 = ephemeral)")
    ap.add_argument("--out-dir", default=None,
                    help="flight-dump dir (default: a fresh tempdir)")
    args = ap.parse_args()
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="health_smoke_")

    batch = 16
    rng = np.random.RandomState(0)
    x = rng.randn(batch * args.steps, 8).astype(np.float32)
    y = (rng.randint(0, 3, batch * args.steps) + 1).astype(np.float32)
    ds = SlowedPoisonedDataSet(
        DataSet.minibatch_arrays(x, y, batch, shuffle=False),
        inject_at=args.inject_step - 1, delay_ms=args.step_delay_ms)
    model = nn.Sequential(nn.Linear(8, 3), nn.LogSoftMax())
    sink = InMemorySink()
    opt = (LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                          batch_size=batch)
           .set_optim_method(SGD(learning_rate=0.1))
           .set_end_when(Trigger.max_epoch(1))
           .set_telemetry(Recorder(sinks=[sink], annotate=False))
           .set_health(policy="raise", flight_dir=out_dir,
                       install_crash_hooks=False))
    srv = opt.serve_metrics(port=args.port)
    print(f"introspection server on {srv.url('')}")

    failure = []

    def train():
        try:
            opt.optimize()
            failure.append("training finished WITHOUT diverging")
        except DivergenceError as e:
            print(f"divergence raised as expected: {e}")
        except Exception as e:          # noqa: BLE001
            failure.append(f"unexpected error: {e!r}")

    t = threading.Thread(target=train)
    t.start()

    # -- scrape while the run is alive and still healthy ----------------- #
    deadline = time.time() + 60
    while time.time() < deadline:
        code, body = fetch(srv.url("/healthz"))
        h = json.loads(body) if body else {}
        if code == 200 and (h.get("last_step") or 0) >= 3:
            break
        time.sleep(0.05)
    else:
        failure.append("run never reached step 3 with a healthy /healthz")
        h = {}
    live_step = h.get("last_step")
    if not h.get("ok"):
        failure.append(f"/healthz not ok mid-run: {h}")
    code, metrics = fetch(srv.url("/metrics"))
    if code != 200 or "bigdl_records_total" not in metrics:
        failure.append(f"/metrics bad (HTTP {code})")
    for line in metrics.strip().splitlines():
        if not (line.startswith("#") or " " in line):
            failure.append(f"unparseable exposition line: {line!r}")
    code, body = fetch(srv.url("/records?n=2&type=step"))
    if code != 200 or not json.loads(body):
        failure.append("/records returned nothing")

    t.join(timeout=120)
    srv.stop()

    # -- post-mortem assertions ------------------------------------------ #
    events = [r for r in sink.records if r.get("type") == "health_event"]
    ev_steps = {e["condition"]: e["step"] for e in events}
    if ev_steps.get("non_finite_loss") != args.inject_step:
        failure.append(f"expected non_finite_loss at step "
                       f"{args.inject_step}, got events {ev_steps}")
    dumps = sorted(glob.glob(os.path.join(out_dir, "flight_*.json")))
    if len(dumps) != 1:
        failure.append(f"expected exactly one flight dump, got {dumps}")
    else:
        with open(dumps[0]) as f:
            dump = json.load(f)
        if dump.get("reason") != "divergence":
            failure.append(f"dump reason {dump.get('reason')!r}")
        if not any(e.get("condition") == "non_finite_loss"
                   for e in dump.get("events", [])):
            failure.append("divergence event missing from flight dump")
        ring_steps = [r.get("step") for r in dump.get("records", [])
                      if r.get("type") == "step"]
        if not ring_steps or ring_steps[-1] != args.inject_step:
            failure.append(f"ring records end at {ring_steps[-1:]}, "
                           f"expected {args.inject_step}")

    summary = {"metric": "health_smoke", "ok": not failure,
               "scraped_at_step": live_step,
               "event_step": ev_steps.get("non_finite_loss"),
               "flight_dumps": len(dumps),
               "failures": failure}
    print(json.dumps(summary))
    return 0 if not failure else 1


if __name__ == "__main__":
    sys.exit(main())
