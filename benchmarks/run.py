"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell in a new process: load, warm the cell's own shapes,
measure for --seconds, compare what the timed path produced with the plain
reference, print the result as the last line of stdout.  No chip, or fewer
than the cell asks for: an error and no result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmarks import harness
    try:
        cell = harness.Cell(a.workload)
        line = harness.run_cell(cell, a.seed, a.seconds, a.trace,
                                t_process_start=T_PROCESS_START)
    except harness.BenchmarkError as e:
        print(f"benchmark error: {e}", file=sys.stderr)
        return 2
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (decode loop, recorder pollers) must
    # not hold the exit; every thread the benchmark started is joined
    os._exit(code)
