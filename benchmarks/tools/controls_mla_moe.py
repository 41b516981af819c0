"""The readings the latent-attention serving cell's rate and limits are set
from, on the chip, at the cell's own size:

    python3 benchmarks/tools/controls_mla_moe.py --workload W \\
        [--closed-seconds 60] [--plan 0.8x6,0.8x6] [--window 30] \\
        [--judge 7] [--controls-windows 1] [--controls-on 1]

`tools/controls_window_moe.py`'s three steps for this cell's runner and
reference (that tool names the window cell's counters and its ring, and
is not this PR's to edit; its `representative_draw` is used).  One
process, one warm engine:

1. `--closed-seconds` of closed loop: as many clients as the engine has
   slots, each sending its next request of the cell's own mix when the
   last completes; completed requests a second is the capacity that the
   traffic file's rate is a share of.
2. For each `SHARExN` of `--plan`, a set of N open-loop windows through
   the cell's own runner at that share of the capacity just measured (two
   figures; `filexN`, or no closed loop: the traffic file's rate), every
   window on a seed of its own: `tpot_ms_p95`, the share of token gaps
   that hold a prefill chunk, TTFT, the decode tick's whole-window
   readings; then the set's spread (quartile distance over the median).
   A rate other than the file's offers `representative_draw`'s draw,
   chosen by what the draw holds and never by how steady it reads.
3. With the engine gone from the chip, the served tokens of the last
   `--judge` windows through the plain reference by the runner's own
   `gap_table` and `compared`; then, on the last `--controls-windows` of
   them, each of `mla_moe_ref.controls` put in the program's place on the
   `--controls-on` longest requests; every line says whether the program,
   and each control, came out `correct` by the cell's limits
   (`harness.compared_ok`, as `run.py` decides it).

One JSON line a step, appended to chiprun_out/controls_<workload>.jsonl.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FIRST_DRAW = 20261004          # the day the cell was made


def representative_draw(traffic, seconds, first=FIRST_DRAW):
    """`controls_window_moe.representative_draw` for a cache with no ring:
    the first `draw_seed` from `first` on whose window holds the requests
    (within one) and the prompt and reply tokens (within a tenth) that the
    rate and the mix expect.  -> (draw_seed, its facts, the expectation)."""
    from benchmarks.tools import controls_window_moe
    return controls_window_moe.representative_draw(
        traffic, traffic["engine"]["max_context"], seconds, first)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plan", default="filex6,filex6")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--closed-seconds", type=float, default=60.0)
    ap.add_argument("--judge", type=int, default=7)
    ap.add_argument("--controls-windows", type=int, default=1)
    ap.add_argument("--controls-on", type=int, default=1)
    a = ap.parse_args()
    import numpy as np
    from benchmarks import harness, loadgen
    from benchmarks.metrics import _ticks
    from benchmarks.tools.controls_sparse_moe import spread, two_figures
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

    draws = {cell.traffic["arrivals"]["rate_per_s"]:
             cell.traffic["arrivals"]["draw_seed"]}

    def runner(seed, rate=None):
        if rate is not None and rate not in draws:
            draws[rate], got, want = representative_draw(
                dict(cell.traffic, arrivals=dict(
                    cell.traffic["arrivals"], rate_per_s=rate)), a.window)
            emit({"step": "draw", "rate_per_s": rate,
                  "draw_seed": draws[rate], "holds": got, "expected": want})
        scale = {} if rate is None else {"traffic": {"arrivals": dict(
            cell.traffic["arrivals"], rate_per_s=rate,
            draw_seed=draws[rate])}}
        probe = harness.Probe(time.perf_counter(), False, None)
        return cell.runner().Runner(cell, seed, a.window, devices, probe,
                                    scale)

    t0 = time.perf_counter()
    first = runner(1)
    engine = first.build_engine()
    first.warm()
    emit({"step": "setup", "seconds": time.perf_counter() - t0,
          "rate_per_s": first.tr["arrivals"]["rate_per_s"],
          "memory_peak_bytes": harness.memory_peak_bytes(devices),
          "stats": {k: engine.stats()[k] for k in (
              "kv_kinds", "attn_route", "chunk_attn_route")}})
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
    for s, part in enumerate((p for p in a.plan.split(",") if p), 1):
        share, n = part.split("x")
        rate = None if share == "file" or capacity is None \
            else two_figures(float(share) * capacity)
        rows = []
        for i in range(int(n)):
            seed = 3001 + 7000019 * (s - 1) + 1000003 * i \
                + (2 ** 31 if i % 2 else 0)
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
                "step": "window", "set": s, "seed": seed, "share": share,
                "rate_per_s": r.tr["arrivals"]["rate_per_s"],
                "draw_seed": r.tr["arrivals"]["draw_seed"],
                "attempted": out["attempted"], "failed": out["failed"],
                "tpot_ms_p95": out["end_to_end"]["tpot_ms_p95"],
                "tpot_ms_p50": f["tpot_ms_p50"],
                "gaps_with_chunk_share": f["gaps_with_chunk_share"],
                "ttft_ms_p50": f["ttft_ms_p50"],
                "ttft_ms_p95": f["ttft_ms_p95"],
                "chunk_ms_mean": f["chunk_ms_mean"],
                "mean_live_slots": f["mean_live_slots"],
                "recompiles": f["recompiles"],
                "busy_span_s": f["busy_span_s"], "served": f["served"],
                "counters": {k: f[k] for k in (
                    "steps", "tokens", "prefill_chunks", "moe_pairs",
                    "moe_pairs_routed", "moe_prefill_pairs",
                    "moe_prefill_pairs_routed", "moe_experts_touched",
                    "mla_rows_live", "mla_chunk_rows_live",
                    "mla_chunk_rows_visible")},
                "ticks": {k: v for k, v in ticks.items()
                          if k.startswith(("decode_", "ticks", "leaf_"))}})
            emit(rows[-1])
            checked.append(r)
        if len(rows) >= 2:
            emit({"step": "spread", "set": s, "share": share,
                  **{k: {"median": statistics.median(r[k] for r in rows),
                         "spread": spread([r[k] for r in rows])
                         if statistics.median(r[k] for r in rows) else None}
                     for k in ("tpot_ms_p95", "tpot_ms_p50",
                               "gaps_with_chunk_share")}})
    # the references need the chip's memory: every handle on the engine
    # goes, and with the last its weights and its pool
    first.release()
    for r in checked:
        r.engine = r.model = None
    del engine
    gc.collect()
    judged = checked[-a.judge:] if a.judge > 0 else []
    # every window's program first, the newest first; then the controls
    passes = [(r, False) for r in reversed(judged)] + [
        (r, True) for r in reversed(judged[-a.controls_windows:])
        if a.controls_windows > 0]
    for r, with_controls in passes:
        t1 = time.perf_counter()
        never = sum(1 for q in r.reqs if q.error == "never finished")
        controls = r.controls() if with_controls else {}
        table = r.gap_table(controls, a.controls_on)

        def vals(name):
            held = r.compared(table, name, never)[:4]
            return dict({c["name"]: c["value"] for c in held},
                        correct=harness.compared_ok(held))
        at_served = float(np.concatenate(
            [row["bf16"][-len(row["served"]):] for row in table]).mean())
        at_rows = float(np.concatenate(
            [row["bf16"] for row in table]).mean())
        emit({"step": "compared", "seed": r.seed,
              "requests": [[row["n_tokens"] - len(row["served"]),
                            len(row["served"])] for row in table],
              "limits": r.tr["limits"], "program": vals("served"),
              "noise_served_over_rows": at_served / at_rows
              if at_rows else None,
              **{f"control_{name}": vals(name) for name in controls},
              "seconds": time.perf_counter() - t1})


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
