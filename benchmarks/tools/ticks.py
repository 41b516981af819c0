"""The decode tick's timeline of one run, read every way the readers
could read it:

    python3 benchmarks/tools/ticks.py --workload W --seed N \\
        [--seconds 30] [--trace 0|1]
    python3 benchmarks/tools/ticks.py --synthetic 10000

One run of the cell through its own runner, as `run.py` makes it but
without the reference's comparison.  Untraced (`--trace 0`) it prints the
tick metrics over the whole window; traced, over the whole window and
over its head, the part before the profiler starts, and the sync's
overhang over the decode program's device time.  Beside them the
client's `tpot_ms_p50` of the same run, each leaf's median, and how much
of a tick the leaves cover.  PERF.md section 6 holds the readings the
readers' interval (`metrics/_ticks.py` `HEAD_ONLY`) was chosen from.

`--synthetic N` runs no engine and needs no chip: N ticks of the eight
spans through the same Recorder calls, and the microseconds a tick they
cost on this host.  One JSON line, appended to chiprun_out/ticks.jsonl
as well.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def synthetic(n):
    """Microseconds a tick of the spans alone: the parent, six leaves, and
    in every fourth tick a request's queue and prefill spans."""
    from bigdl_tpu.observability import Recorder
    from bigdl_tpu.observability.tracing import Tracer, set_tracer
    from benchmarks.metrics import _ticks
    prev = set_tracer(Tracer(capacity=9 * n))
    try:
        rec = Recorder()
        t0 = time.perf_counter()
        for i in range(n):
            with rec.span(_ticks.TICK, annotate=False) as tick:
                for name in _ticks.LEAVES:
                    with rec.span(name):
                        if name == "decode.admit" and i % 4 == 0:
                            rec.add_span(_ticks.QUEUE, 0.001)
                            with rec.span("decode.prefill", bucket=256):
                                pass
                tick.set(step=i)
        dt = time.perf_counter() - t0
    finally:
        stored = len(set_tracer(prev).store)
    return {"synthetic_ticks": n, "spans": stored,
            "us_per_tick": 1e6 * dt / n}


def one_run(workload, seed, seconds, trace):
    from benchmarks import harness, xplane
    from benchmarks.metrics import _decode_program, _ticks
    t_start = time.perf_counter()
    cell = harness.Cell(workload)
    devices = harness.find_devices(cell.chips)
    harness.set_compile_cache(cell.root)
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
    probe = harness.Probe(t_start, trace, trace_dir)
    runner = cell.runner().Runner(cell, seed, seconds, devices, probe, {})
    runner.run()
    probe.finish_trace()
    out = runner.results()
    store = _ticks.default_store()
    facts = out["facts"]
    row = {"workload": workload, "seed": seed, "seconds": seconds,
           "trace": trace, "device": devices[0].device_kind,
           "attempted": out["attempted"], "failed": out["failed"],
           "end_to_end": out["end_to_end"],
           "tpot_ms_p50": facts.get("tpot_ms_p50"),
           "ttft_ms_p50": facts.get("ttft_ms_p50"),
           "gen_late_ms_p95": facts.get("gen_late_ms_p95"),
           "steps": facts.get("steps"),
           "serve_prefill_ms_mean": 1e3 * facts["prefill_s"]
           / max(facts["prefills"], 1),
           "spans_stored": len(store), "spans_dropped": store.dropped,
           "window": _ticks.summary(store, *_ticks.interval(probe, False))}
    if trace and probe.traced:
        row["head"] = _ticks.summary(store, *_ticks.interval(probe, True))
        summary = xplane.reduce_trace(trace_dir, len(devices))
        device_s = _decode_program.device_seconds(summary)
        row["decode_step.device_ms"] = 1e3 * device_s
        for part in ("window", "head"):
            row[part]["decode_tick.sync_over_device_ms_p50"] = \
                row[part]["decode_sync.ms_p50"] - 1e3 * device_s
        row["idle_share"] = 1.0 - summary["busy_s"] / summary["window_s"]
        row["idle_gaps"] = summary["idle_gaps"][:10]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--synthetic", type=int, default=0)
    a = ap.parse_args()
    if a.synthetic:
        row = synthetic(a.synthetic)
    elif a.workload:
        row = one_run(a.workload, a.seed, a.seconds, a.trace)
    else:
        ap.error("give --workload or --synthetic")
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ticks.jsonl"), "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
