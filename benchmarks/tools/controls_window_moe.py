"""The readings the window / global attention serving cell's rate and
limits are set from, on the chip, at the cell's own size:

    python3 benchmarks/tools/controls_window_moe.py --workload W \\
        [--closed-seconds 60] [--plan 0.8x6,0.8x6] [--window 30] \\
        [--seeds 1662538020] [--rejudge chiprun_out/judged_W.npz] \\
        [--judge 7] [--controls-windows 3] [--controls-on 1]

`tools/controls_sparse_moe.py`'s three steps for this cell's runner and
reference (that tool knows the Keye reference's controls by name and is
not this PR's to edit).  One process, one warm engine:

1. `--closed-seconds` of closed loop, as `tools/calibrate.py` does: as
   many clients as the engine has slots, each sending its next request of
   the cell's own mix when the last completes; completed requests a
   second is the capacity that the traffic file's rate is a share of.
2. For each `SHARExN` of `--plan`, a set of N open-loop windows through
   the cell's own runner at that share of the capacity just measured
   (two figures; `filexN`, or no closed loop: the traffic file's rate),
   every window of every set on a seed of its own: `tpot_ms_p95`, the
   share of token gaps that hold a prefill chunk, TTFT, the decode
   tick's whole-window readings of `metrics/_ticks.py`; then the set's
   spread (quartile distance over the median).  A rate other than the
   file's offers `representative_draw`'s draw, which is chosen by what
   the draw holds and never by how steady it reads.  `--seeds` are
   windows at the file's rate on seeds given by name (one that the
   driver's check read not correct), before the plan's.
3. With the engine gone from the chip, the served tokens of the last
   `--judge` windows through the plain reference by the runner's own
   `gap_table` and `compared` (what `check` is made of), the newest
   first; then, on the last `--controls-windows` of them, each of
   `window_moe_ref.controls` put in the program's place on the
   `--controls-on` longest requests (a control's forward costs as much
   as the reference's); every line says
   whether the program, and each control, came out `correct` by the
   cell's limits (`harness.compared_ok`, as `run.py` decides it).
   `--rejudge` adds, before them, the windows an earlier call of this
   tool kept in its `judged_W.npz` (the judged requests' served tokens
   by seed): a yardstick or a limit can be read anew on tokens that were
   served once.  Each line also sets the reference's own noise at the
   served positions beside the same at the rows it is read at
   (`noise_served_over_rows`: about 1 if the prompt's end is as noisy
   as the reply).

One JSON line a step, appended to chiprun_out/controls_<workload>.jsonl
as well; the judged tokens' gaps go to chiprun_out/gaps_<workload>_<seed>.npz.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FIRST_DRAW = 20261003          # the day the cell was made


def draw_facts(traffic, window, seconds):
    """What `seconds` of `traffic`'s draw hold: requests, prompt and
    reply tokens, requests longer than the ring of a layer of `window`
    rows, prompts shorter than one chunk."""
    from benchmarks import loadgen
    eng = traffic["engine"]
    ring = eng["page_size"] * (-(-window // eng["page_size"])
                               + eng["prefill_chunk"] // eng["page_size"] + 1)
    sched = loadgen.make_schedule(traffic, 0, seconds, 2)
    return {"requests": len(sched),
            "prompt_tokens": sum(len(p) for _, p, _ in sched),
            "reply_tokens": sum(o for _, _, o in sched),
            "past_ring": sum(len(p) + o > ring for _, p, o in sched),
            "under_chunk": sum(len(p) < eng["prefill_chunk"]
                               for _, p, _ in sched)}


def representative_draw(traffic, window, seconds, first=FIRST_DRAW,
                        tries=1000):
    """The first `draw_seed` from `first` on whose window holds what the
    rate and the mix expect: the requests within one, prompt and reply
    tokens within a tenth, the requests past the ring and the prompts
    under a chunk within one (the comparison judges two requests past the
    ring, so a draw has to hold them).  The expectation is the mean of a
    long draw of the same file.  Nothing here looks at a time or a
    spread.  -> (draw_seed, its facts, the expectation)."""
    long = draw_facts(dict(traffic, arrivals=dict(
        traffic["arrivals"], draw_seed=0)), window, 2000.0 * seconds)
    want = {k: v / 2000.0 for k, v in long.items()}
    room = {"requests": 1.0, "prompt_tokens": 0.1 * want["prompt_tokens"],
            "reply_tokens": 0.1 * want["reply_tokens"], "past_ring": 1.0,
            "under_chunk": 1.0}
    for seed in range(first, first + tries):
        got = draw_facts(dict(traffic, arrivals=dict(
            traffic["arrivals"], draw_seed=seed)), window, seconds)
        if all(abs(got[k] - want[k]) <= room[k] for k in want):
            return seed, got, want
    raise ValueError(f"no representative draw in {tries} from {first}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plan", default="filex6,filex6")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--closed-seconds", type=float, default=60.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rejudge", default="")
    ap.add_argument("--judge", type=int, default=7)
    ap.add_argument("--controls-windows", type=int, default=3)
    ap.add_argument("--controls-on", type=int, default=1)
    a = ap.parse_args()
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
                    cell.traffic["arrivals"], rate_per_s=rate)),
                cell.config["sliding_window_size"], a.window)
            emit({"step": "draw", "rate_per_s": rate,
                  "draw_seed": draws[rate], "holds": got, "expected": want})
        scale = {} if rate is None else {"traffic": {"arrivals": dict(
            cell.traffic["arrivals"], rate_per_s=rate,
            draw_seed=draws[rate])}}
        probe = harness.Probe(time.perf_counter(), False, None)
        return cell.runner().Runner(cell, seed, a.window, devices, probe,
                                    scale)

    t0 = time.perf_counter()
    # the one engine's weights: the first seed given by name, else seed 1
    named = [int(x) for x in a.seeds.split(",") if x]
    first = runner(named[0] if named else 1)
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
    plan = ([f"filex{len(named)}"] if named else []) \
        + [part for part in a.plan.split(",") if part]
    for s, part in enumerate(plan, 0 if named else 1):
        share, n = part.split("x")
        rate = None if share == "file" or capacity is None \
            else two_figures(float(share) * capacity)
        rows = []
        for seed in named if s == 0 else (
                3001 + 7000019 * (s - 1) + 1000003 * i
                + (2 ** 31 if i % 2 else 0) for i in range(int(n))):
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
                "step": "window", "set": s, "seed": seed,
                "share": share, "rate_per_s": r.tr["arrivals"]["rate_per_s"],
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
                "busy_span_s": f["busy_span_s"],
                "served": f["served"],
                "counters": {k: f[k] for k in (
                    "steps", "tokens", "moe_pairs", "moe_experts_touched",
                    "attn_rows_live", "attn_rows_attended_window",
                    "attn_rows_attended_global", "kv_pages_recycled")},
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
    stats = engine.stats()
    first.release()
    for r in checked:
        r.engine = r.model = None
    del engine
    gc.collect()
    import numpy as np
    judged = checked[-a.judge:] if a.judge > 0 else []
    # what is judged, kept: another precision or control can be tried on
    # the same served tokens without serving them again (--rejudge)
    np.savez(os.path.join(ROOT, "chiprun_out", f"judged_{a.workload}.npz"),
             engine_seed=first.seed,
             **{f"s{r.seed}_{j}_{len(q.prompt)}": np.concatenate(
                 [q.prompt, np.asarray(q.tokens, np.int32)])
                for r in judged for j, q in enumerate(r.sample())})
    if a.rejudge:
        kept = {}
        with np.load(a.rejudge) as z:
            key = harness.seed_key(int(z["engine_seed"]))
            for name in set(z.files) - {"engine_seed"}:
                seed, _, n_prompt = name[1:].split("_")
                kept.setdefault(int(seed), []).append(types.SimpleNamespace(
                    prompt=z[name][:int(n_prompt)], ok=True, error=None,
                    tokens=z[name][int(n_prompt):].tolist()))
        for seed, reqs in kept.items():
            r = runner(seed)
            r.key, r.reqs, r.stats = key, reqs, stats
            judged.insert(0, r)
    # every window's program first, the newest first; then the controls
    passes = [(r, False) for r in reversed(judged)] + [
        (r, True) for r in reversed(judged[-a.controls_windows:])
        if a.controls_windows > 0]
    for r, with_controls in passes:
        t1 = time.perf_counter()
        never = sum(1 for q in r.reqs if q.error == "never finished")
        controls = r.controls() if with_controls else {}
        table = r.gap_table(controls, a.controls_on)
        # every judged token's gap, kept: a limit is set from all of them
        np.savez(os.path.join(
            ROOT, "chiprun_out", f"gaps_{a.workload}_{r.seed}"
            f"{'_controls' if with_controls else ''}.npz"),
                 **{f"r{j}_{row['n_tokens']}_{name}": np.asarray(gaps)
                    for j, row in enumerate(table)
                    for name, gaps in row.items() if name != "n_tokens"})

        def vals(name):
            held = r.compared(table, name, never)[:6]
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
              "noise_rows": [len(row["bf16"]) for row in table],
              **{f"control_{name}": vals(name) for name in controls},
              "seconds": time.perf_counter() - t1})


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
