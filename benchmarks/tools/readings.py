"""Readings a cell's limits are set from, many seeds in one process:

    python benchmarks/tools/readings.py --workload W --seeds 1,2,3 \\
        [--seconds 5] [--control 1] [--faults 1] [--leaves 1]

For each seed: the program through the cell's own runner (a short
window), the plain reference, and each compared number; with --control
the same numbers for the reference computed in fp8 (the precision below
bfloat16) put in the program's place; with --faults (training cells) for
the reference with half of every batch left out; with --leaves every
leaf's norms of each, for a look by hand.  One JSON line a seed,
appended to chiprun_out/readings_<workload>.jsonl as well.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def half_batch(batch):
    import jax.numpy as jnp
    h = batch[0].shape[0] // 2
    return tuple(jnp.concatenate([jnp.asarray(a)[:h]] * 2) for a in batch)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--leaves", type=int, default=0)
    a = ap.parse_args()
    from benchmarks import harness
    from benchmarks.reference import lm_ref
    from benchmarks.runners import compare
    cell = harness.Cell(a.workload)
    devices = harness.find_devices(cell.chips)
    harness.set_compile_cache(cell.root)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"readings_{a.workload}.jsonl"), "a")
    engine = None
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        probe = harness.Probe(t0, False, None)
        r = cell.runner().Runner(cell, seed, a.seconds, devices, probe, {})
        if hasattr(r, "adopt_engine"):       # one warm engine, many seeds
            if engine is None:
                engine = r.build_engine()
            else:
                r.adopt_engine(engine)
            r.drive()
            out = r.results()
        else:
            r.run()
            out = r.results()
            r.release()
        row = {"workload": a.workload, "seed": seed,
               "end_to_end": out["end_to_end"], "failed": out["failed"],
               "attempted": out["attempted"]}
        vals = lambda cs: {c["name"]: c["value"] for c in cs}
        stat = cell.traffic.get("leaf_statistic", "worst")
        kern = getattr(r, "kernels", None)
        if hasattr(r, "reference"):            # a training cell
            ref = r.reference()
            row["program"] = vals(compare.training(r.first, ref, {}, stat,
                                                    kern))
            row["ref_losses"] = ref["losses"]
            row["worst_grad_leaves"] = compare.leaf_gaps(
                r.first["grad_norms"], ref["grad_norms"])
            row["worst_dparam_leaves"] = compare.leaf_gaps(
                r.first["dparam_norms"], ref["dparam_norms"])
            norms = lambda d: {k: d[k] for k in ("grad_norms",
                                                 "dparam_norms")}
            leaves = {"program": norms(r.first), "reference": norms(ref)}
            if a.control:
                ctrl = r.reference(quant=lm_ref.fp8)
                row["control_fp8"] = vals(compare.training(ctrl, ref, {},
                                                           stat, kern))
                leaves["control_fp8"] = norms(ctrl)
            if a.faults:
                row["fault_half_batch"] = vals(compare.training(
                    r.reference(alter=half_batch), ref, {}, stat, kern))
            if a.leaves:
                row["leaves"] = leaves
        else:
            row["program"] = vals(r.check())
            if a.control:
                row["control_fp8"] = vals(r.check(quant=lm_ref.fp8))
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
