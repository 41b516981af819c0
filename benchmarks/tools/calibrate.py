"""The rate a serving cell offers, found once, on the chip:

    python3 benchmarks/tools/calibrate.py --workload W \\
        [--closed-seconds 25] [--fractions 0.7,0.5] [--seeds 6] \\
        [--window 30] [--check 1]

One process, one warm engine.  First a closed loop: as many clients as
the engine has slots, each sending its next request of the cell's own mix
when the last one completes; completed requests per second is the
capacity.  Then, for each fraction of it (rounded to two figures), open
loops of --window seconds on --seeds seeds through the cell's own runner,
the rate overriding the traffic file's: TTFT and TPOT tails a seed, and
their spread (quartile distance over median) a fraction.  With --check
each window's served tokens also go through the plain reference and its
fp8 control: the readings the cell's limits are set from.  The chosen
rate is written into the traffic file by hand, with this tool's reading
beside it.  One JSON line a step, appended to
chiprun_out/calibrate_<workload>.jsonl as well.
"""
import argparse
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
    ap.add_argument("--closed-seconds", type=float, default=25.0)
    ap.add_argument("--fractions", default="0.7,0.5")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--check", type=int, default=1)
    a = ap.parse_args()
    from benchmarks import harness, loadgen
    from benchmarks.reference import lm_ref
    cell = harness.Cell(a.workload)
    devices = harness.find_devices(cell.chips)
    harness.set_compile_cache(cell.root)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"calibrate_{a.workload}.jsonl"), "a")

    def emit(row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    def runner(seed, seconds, rate=None):
        scale = {}
        if rate is not None:
            scale = {"traffic": {"arrivals": dict(cell.traffic["arrivals"],
                                                  rate_per_s=rate)}}
        probe = harness.Probe(time.perf_counter(), False, None)
        return cell.runner().Runner(cell, seed, seconds, devices, probe,
                                    scale)

    t0 = time.perf_counter()
    first = runner(1, a.closed_seconds)
    engine = first.build_engine()
    first.warm()
    # the closed loop sends the mix's own sizes, one after another
    mix = loadgen.make_schedule(first.tr, 1, 600.0, first.cfg["vocab_size"])
    slots = first.tr["engine"]["slots"]

    def next_request(i, k):
        _, prompt, max_new = mix[(i + k * slots) % len(mix)]
        return prompt, max_new

    done, tokens, secs = loadgen.run_closed_loop(
        slots, a.closed_seconds, next_request, first.start_stream)
    capacity = done / secs
    emit({"step": "closed_loop", "clients": slots, "seconds": secs,
          "completed": done, "requests_per_s": capacity,
          "tokens_per_s": tokens / secs,
          "setup_s": time.perf_counter() - t0 - secs})
    time.sleep(5.0)          # what the closed loop left in flight drains

    seeds = [1001 + 1000003 * i + (2 ** 31 if i % 2 else 0)
             for i in range(a.seeds)]
    for frac in (float(f) for f in a.fractions.split(",")):
        rate = two_figures(frac * capacity)
        rows = []
        for seed in seeds:
            t1 = time.perf_counter()
            r = runner(seed + int(frac * 100), a.window, rate)
            r.adopt_engine(engine)
            r.drive()
            out = r.results()
            f = out["facts"]
            row = {"step": "open_loop", "fraction": frac, "rate_per_s": rate,
                   "seed": r.seed, "attempted": out["attempted"],
                   "failed": out["failed"],
                   "ttft_ms_p95": f["ttft_ms_p95"],
                   "ttft_ms_p50": f["ttft_ms_p50"],
                   "tpot_ms_p95": out["end_to_end"]["tpot_ms_p95"],
                   "tpot_ms_p50": f["tpot_ms_p50"],
                   "gen_late_ms_p95": f["gen_late_ms_p95"],
                   "mean_live_slots": f["mean_live_slots"]}
            if a.check:
                vals = lambda cs: {c["name"]: c["value"] for c in cs}
                row["program"] = vals(r.check())
                row["control_fp8"] = vals(r.check(quant=lm_ref.fp8))
            row["seconds"] = time.perf_counter() - t1
            emit(row)
            rows.append(row)
        if len(rows) >= 2:
            emit({"step": "spread", "fraction": frac, "rate_per_s": rate,
                  **{k: {"median": statistics.median(r[k] for r in rows),
                         "spread": spread([r[k] for r in rows])}
                     for k in ("ttft_ms_p95", "tpot_ms_p95", "tpot_ms_p50")}})


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
