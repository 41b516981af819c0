"""The readings a sparse-attention / routed-expert serving cell's rate and
limits are set from, on the chip, at the cell's own size:

    python3 benchmarks/tools/controls_sparse_moe.py --workload W \\
        [--closed-seconds 60] [--plan 0.6x2,0.8x6,0.8x6] [--window 30] \\
        [--controls-on 2]

One process, one warm engine (a run of `run.py` is one process a seed;
what differs from seed to seed is the order of the work, which this
shows as well).  In order:

1. `--closed-seconds` of closed loop, as `tools/calibrate.py` does: as
   many clients as the engine has slots, each sending its next request of
   the cell's own mix when the last completes; completed requests a
   second is the capacity that the traffic file's rate is a share of.
2. For each `SHARExN` of `--plan`, a set of N open-loop windows through
   the cell's own runner at that share of the capacity just measured
   (two figures; `filexN`, or no closed loop: the traffic file's rate),
   every set on the same seeds from the first on: `tpot_ms_p95`, the
   share of token gaps that hold a prefill chunk, TTFT, and the decode
   tick's whole-window readings of `metrics/_ticks.py`; then the set's
   spread (quartile distance over the median).
3. With the engine gone from the chip, the last set's served tokens
   through the plain reference, by the runner's own `gap_table` and
   `compared` (what `check` is made of): the sound program's compared
   numbers on every sampled request, and the same numbers for each of
   `sparse_moe_ref.CONTROLS` put in the program's place on the
   `--controls-on` shortest of them (a control's forward costs as much as
   the reference's).

One JSON line a step, appended to chiprun_out/controls_<workload>.jsonl
as well.
"""
import argparse
import gc
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def two_figures(x):
    e = math.floor(math.log10(abs(x))) - 1
    return round(x / 10 ** e) * 10 ** e


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plan", default="filex6,filex6")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--closed-seconds", type=float, default=60.0)
    ap.add_argument("--controls-on", type=int, default=2)
    a = ap.parse_args()
    from benchmarks import harness, loadgen
    from benchmarks.metrics import _ticks
    from benchmarks.reference import sparse_moe_ref as ref
    from bigdl_tpu.observability import tracing
    cell = harness.Cell(a.workload)
    devices = harness.find_devices(cell.chips)
    harness.set_compile_cache(cell.root)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"controls_{a.workload}.jsonl"), "a")

    def emit(row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    def runner(seed, rate=None):
        scale = {} if rate is None else {"traffic": {"arrivals": dict(
            cell.traffic["arrivals"], rate_per_s=rate)}}
        probe = harness.Probe(time.perf_counter(), False, None)
        return cell.runner().Runner(cell, seed, a.window, devices, probe,
                                    scale)

    t0 = time.perf_counter()
    first = runner(1)
    engine = first.build_engine()
    first.warm()
    emit({"step": "setup", "seconds": time.perf_counter() - t0,
          "rate_per_s": first.tr["arrivals"]["rate_per_s"]})
    capacity = None
    if a.closed_seconds > 0:
        mix = loadgen.make_schedule(first.tr, 1, 600.0,
                                    first.cfg["vocab_size"])
        slots = first.tr["engine"]["slots"]
        done, tokens, secs = loadgen.run_closed_loop(
            slots, a.closed_seconds,
            lambda i, k: mix[(i + k * slots) % len(mix)][1:],
            first.start_stream)
        capacity = done / secs
        emit({"step": "closed_loop", "clients": slots, "seconds": secs,
              "completed": done, "requests_per_s": capacity,
              "tokens_per_s": tokens / secs})
        time.sleep(2.0)      # every client's last request has finished

    checked = []
    for s, part in enumerate(a.plan.split(",")):
        share, n = part.split("x")
        rate = None if share == "file" or capacity is None \
            else two_figures(float(share) * capacity)
        rows, checked = [], []
        for seed in (3001 + 1000003 * i + (2 ** 31 if i % 2 else 0)
                     for i in range(int(n))):
            # this window's spans alone (the default store keeps 65,536)
            tracing.set_tracer(tracing.Tracer(tracing.DEFAULT_CAPACITY))
            r = runner(seed, rate)
            r.adopt_engine(engine)
            r.drive()
            out = r.results()
            f = out["facts"]
            ticks = _ticks.summary(_ticks.default_store(),
                                   *_ticks.interval(r.probe, False))
            rows.append({
                "step": "window", "set": s + 1, "seed": seed,
                "share": share, "rate_per_s": r.tr["arrivals"]["rate_per_s"],
                "attempted": out["attempted"], "failed": out["failed"],
                "tpot_ms_p95": out["end_to_end"]["tpot_ms_p95"],
                "tpot_ms_p50": f["tpot_ms_p50"],
                "gaps_with_chunk_share": f["gaps_with_chunk_share"],
                "ttft_ms_p50": f["ttft_ms_p50"],
                "ttft_ms_p95": f["ttft_ms_p95"],
                "chunk_ms_mean": f["chunk_ms_mean"],
                "mean_live_slots": f["mean_live_slots"],
                "recompiles": f["recompiles"],
                "served": f["served"],
                "ticks": {k: v for k, v in ticks.items()
                          if k.startswith(("decode_", "ticks", "leaf_"))}})
            emit(rows[-1])
            checked.append(r)
        if len(rows) >= 2:
            emit({"step": "spread", "set": s + 1, "share": share,
                  **{k: {"median": statistics.median(r[k] for r in rows),
                         "spread": spread([r[k] for r in rows])}
                     for k in ("tpot_ms_p95", "tpot_ms_p50",
                               "gaps_with_chunk_share")}})
    # the references need the chip's memory: every handle on the engine
    # goes, and with the last its weights and its pool
    first.release()
    for r in checked:
        r.engine = r.model = None
    del engine
    gc.collect()
    for r in checked:
        t1 = time.perf_counter()
        never = sum(1 for q in r.reqs if q.error == "never finished")
        table = r.gap_table(ref.CONTROLS, a.controls_on)
        vals = lambda name: {c["name"]: c["value"]
                             for c in r.compared(table, name, never)[:4]}
        emit({"step": "compared", "seed": r.seed,
              "requests": [[len(q.prompt), len(q.tokens)] for q in sorted(
                  r.sample(), key=lambda q: len(q.prompt))],
              "limits": r.tr["limits"], "program": vals("served"),
              "program_on_controls_requests": r.gap_over_bf16(
                  table[:a.controls_on]),
              **{f"control_{name}": vals(name) for name in ref.CONTROLS},
              "seconds": time.perf_counter() - t1})


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
