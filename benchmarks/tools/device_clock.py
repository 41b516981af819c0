"""The device's idle time split across the decode engine's host leaves,
and each prefill's leaves, in one run of a serving cell:

    python3 benchmarks/tools/device_clock.py --workload W --seed N \\
        [--seconds 30] [--trace 1] [--check 0]
    python3 benchmarks/tools/device_clock.py --synthetic 20000

One run through `harness.run_cell`: the cell's per-layer metrics as
`run.py` reads them.  `--check 0` leaves out the comparison with the
plain reference: `correct` is then false.  Prints the result line as
`run.py` does, then one JSON row of what `metrics/_device_clock.py`
found beside the shares: the offset between the trace's clocks, the
runs it orders, the idle under each leaf and the remainder, the seconds
the reader took, and the prefill spans' counts, means and medians over
the whole window; the row is appended to chiprun_out/device_clock.jsonl
as well.

`--synthetic N` runs no engine and needs no chip: N prefills of one
`decode.prefill` span with and without its three leaves, through the
engine's Recorder calls, and the microseconds the leaves add to each.
"""
import argparse
import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

SERVING = ["olmo1b-serve-chat", "keye30b-l6-serve-longdoc",
           "smallthinker21b-l8-serve-mixed", "dotsvlm1-l5-serve-docqa"]
PREFILL_SPANS = ("decode.prefill", "prefill.stage", "prefill.dispatch",
                 "prefill.sync")


class Cell(harness.Cell):
    """The cell with the reference's comparison optional, and what the
    run leaves kept: its probe, its facts and the readers' context."""

    def __init__(self, name, compare):
        super().__init__(name)
        self.compare = bool(compare)
        self.probe = self.facts = self.ctx = None

    def runner(self):
        base, cell = super().runner().Runner, self

        class Runner(base):
            def results(self):
                out = super().results()
                cell.probe, cell.facts = self.probe, out["facts"]
                return out

            def check(self, *args, **kw):
                if cell.compare:
                    return super().check(*args, **kw)
                return [{"name": "not_compared", "value": 0.0,
                         "limit": None}]
        return types.SimpleNamespace(Runner=Runner)

    def reader(self, metric):
        from benchmarks.metrics import _device_clock
        read = super().reader(metric).read

        def kept(ctx):
            # the xplane is gone once the readers are done: reduce it now,
            # whichever of the cell's metrics is read first
            self.ctx = ctx
            _device_clock.summary(ctx)
            return read(ctx)
        return types.SimpleNamespace(read=kept)


def prefill_spans(store, lo, hi):
    """{span name: {n, mean_ms, p50_ms}} of the prefill spans that lie
    inside [lo, hi]."""
    out = {}
    for name in PREFILL_SPANS:
        ms = [1e3 * s.duration() for s in store.spans()
              if s.name == name and lo <= s.t0 and s.t1 <= hi]
        if ms:
            out[name] = {"n": len(ms), "mean_ms": statistics.fmean(ms),
                         "p50_ms": statistics.median(ms)}
    return out


def one_run(workload, seed, seconds, trace, compare):
    from benchmarks.metrics import _device_clock, _ticks
    t_start = time.perf_counter()
    cell = Cell(workload, compare)
    line = harness.run_cell(cell, seed, seconds, trace,
                            t_process_start=t_start)
    harness.print_result(line)
    found = _device_clock.summary(cell.ctx) if cell.ctx else None
    f = cell.facts
    row = {"workload": workload, "seed": seed, "trace": trace,
           "correct": line["correct"] if compare else None,
           "metrics": {k: v["value"] for k, v in line["metrics"].items()},
           "idle_share": line["device"].get("idle_share"),
           "idle_gaps": line.get("breakdown", {}).get("idle_gaps"),
           "device_clock": found,
           "prefill_ms_mean": 1e3 * f["prefill_s"]
           / max(f.get("prefill_chunks") or f["prefills"], 1),
           "prefill_spans": prefill_spans(
               _ticks.default_store(),
               *_ticks.interval(cell.probe, head=False))}
    return row


def synthetic(n):
    """Microseconds the three leaves add to a prefill's span."""
    from bigdl_tpu.observability import Recorder
    from bigdl_tpu.observability.tracing import Tracer, set_tracer
    prev = set_tracer(Tracer(capacity=8 * n))
    try:
        rec = Recorder()
        seconds = []
        for leaves in ((), PREFILL_SPANS[1:]):
            t0 = time.perf_counter()
            for i in range(n):
                with rec.span("decode.prefill", slot=0, offset=i):
                    for name in leaves:
                        with rec.span(name):
                            pass
            seconds.append(time.perf_counter() - t0)
    finally:
        set_tracer(prev)
    return {"synthetic_prefills": n,
            "us_per_prefill_span": 1e6 * seconds[0] / n,
            "us_per_three_leaves": 1e6 * (seconds[1] - seconds[0]) / n}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=SERVING)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--check", type=int, choices=(0, 1), default=0)
    ap.add_argument("--synthetic", type=int, default=0)
    a = ap.parse_args()
    if a.synthetic:
        row = synthetic(a.synthetic)
    elif a.workload:
        row = one_run(a.workload, a.seed, a.seconds, a.trace, a.check)
    else:
        ap.error("give --workload or --synthetic")
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "device_clock.jsonl"),
              "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
