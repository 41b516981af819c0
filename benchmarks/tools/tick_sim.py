"""A model of the decode tick loop, for whoever sets a serving cell's rate:
how steady `tpot_ms_p95` CAN be over sets of six windows of a draw, before
any chip time is spent.  On the CPU, in seconds:

    python3 benchmarks/tools/tick_sim.py --workload W [--rate 1.36] \\
        [--draws 4] [--sets 20]

The loop is `DecodeEngine`'s as the chunked cells drive it: requests first
come, first served; a tick runs one chunk of the oldest waiting prompt if
there is one, then one decode step over the live slots; a reply's gap is
the time between two of its steps.  The three costs are MEASURED numbers
of the cell, given here, not modelled (`TICK_MS`: a line fitted to six
chip windows of PR 33's program at three rates, PR 36, PERF.md section
5).  What comes out is a count from a model: the share of
sets of six whose spread passes half the metric's bound, the median p95
and the share of gaps that hold a chunk.  It is never a device metric,
and it chooses nothing: `controls_window_moe.representative_draw` names
the draws, by what they hold.
"""
import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# a chunk at the host; a decode tick with no slot live; a live slot more.
# Builder's chip runs, PR 36 (chiprun_out/controls_<cell>.jsonl): six
# windows of tools/controls_window_moe.py at 0.68, 1.0 and 1.4 requests/s,
# two each; the chunk is the median of their mean `decode.prefill` spans
# (26.17-27.06 ms), the tick a line fitted by least squares to each
# window's median `decode.tick` (8.82-13.64 ms) against its mean live
# slots (2.98-8.15; residuals within 0.63 ms)
TICK_MS = {"smallthinker21b-l8-serve-mixed": (26.3, 7.01, 0.81)}
CLOCK_MS = 0.4                 # what the host's clock adds to a p95


def simulate(schedule, slots, chunk, chunk_ms, tick_ms, slot_ms):
    """[(due_s, prompt, max_new)] -> (gaps in ms, whether each held a
    chunk), every request served to its end."""
    sched = sorted(schedule, key=lambda r: r[0])
    t, nxt, waiting, part, live, last = 0.0, 0, [], None, {}, {}
    gaps, held, done = [], [], 0
    while done < len(sched):
        while nxt < len(sched) and sched[nxt][0] <= t:
            waiting.append(nxt)
            nxt += 1
        chunked = False
        if part is None and waiting and len(live) < slots:
            part = [waiting.pop(0), 0]
        if part is not None:
            t += chunk_ms / 1e3
            chunked = True
            part[1] += chunk
            if part[1] >= len(sched[part[0]][1]):     # its first token
                live[part[0]], last[part[0]] = sched[part[0]][2] - 1, t
                part = None
        if live:
            t += (tick_ms + slot_ms * len(live)) / 1e3
            for r in list(live):
                gaps.append(1e3 * (t - last[r]))
                held.append(chunked)
                last[r] = t
                live[r] -= 1
                if live[r] <= 0:
                    del live[r]
                    done += 1
        elif not chunked:
            t = sched[nxt][0] if nxt < len(sched) else t + 0.01
    return np.asarray(gaps), np.asarray(held)


def study(traffic, vocab, costs, seconds=30.0, sets=20, seed=2):
    """`sets` sets of six seeds of `traffic`'s draw."""
    from benchmarks import loadgen
    from benchmarks.tools.controls_sparse_moe import spread
    rng = np.random.default_rng(seed)
    eng = traffic["engine"]
    p95, share = [], []
    for _ in range(6 * sets):
        gaps, held = simulate(loadgen.make_schedule(
            traffic, int(rng.integers(1, 2 ** 31 + 5000)), seconds, vocab),
            eng["slots"], eng["prefill_chunk"], *costs)
        p95.append(float(np.percentile(gaps, 95)) + rng.normal(0, CLOCK_MS))
        share.append(float(held.mean()))
    spreads = [spread(p95[i:i + 6]) for i in range(0, len(p95), 6)]
    return {"tpot_ms_p95_median": statistics.median(p95),
            "set_spread_mean": statistics.mean(spreads),
            "sets_over_half_bound": sum(s > 0.025 for s in spreads) / sets,
            "gaps_with_chunk_share": [min(share), statistics.median(share),
                                      max(share)]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float)
    ap.add_argument("--draws", type=int, default=4)
    ap.add_argument("--sets", type=int, default=20)
    a = ap.parse_args()
    from benchmarks import harness
    from benchmarks.tools.controls_window_moe import representative_draw
    cell = harness.Cell(a.workload)
    arr = dict(cell.traffic["arrivals"])
    arr["rate_per_s"] = a.rate or arr["rate_per_s"]
    first = None
    for _ in range(a.draws):
        arr["draw_seed"], holds, _ = representative_draw(
            dict(cell.traffic, arrivals=arr),
            cell.config["sliding_window_size"], 30.0,
            **({} if first is None else {"first": first}))
        first = arr["draw_seed"] + 1
        print(json.dumps({
            "rate_per_s": arr["rate_per_s"], "draw_seed": arr["draw_seed"],
            "holds": holds, **study(dict(cell.traffic, arrivals=arr), 1000,
                                    TICK_MS[a.workload], sets=a.sets)}),
              flush=True)


if __name__ == "__main__":
    main()
