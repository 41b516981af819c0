"""Arrivals are seed-determined; every seed offers the same set of gaps
and sizes in another order."""
import json
import os

import numpy as np

from benchmarks import arrivals, loadgen
from _bench_common import ROOT


def traffic():
    with open(os.path.join(ROOT, "benchmarks/traffic/chat-open.json")) as f:
        return json.load(f)


def test_arrivals_are_seed_determined():
    draw = lambda s: list(arrivals.virtual_arrivals(
        np.random.RandomState(s), 4.0, arrivals.TRACES["steady"], 30.0))
    assert draw(5) == draw(5) and draw(5) != draw(6)
    assert all(0 < t < 30.0 for t in draw(5))
    assert abs(len(draw(5)) - 120) < 40
    assert arrivals.mult_at(arrivals.TRACES["burst"], 0.5) == 6.0


def test_every_seed_offers_the_same_work():
    tr = traffic()
    a = loadgen.make_schedule(tr, 1, 45.0, 50304)
    b = loadgen.make_schedule(tr, 2 ** 31 + 99, 45.0, 50304)
    again = loadgen.make_schedule(tr, 1, 45.0, 50304)
    sizes = lambda s: sorted((len(p), m) for _, p, m in s)
    gaps = lambda s: np.sort(np.diff([0.0] + [d for d, _, _ in s]))
    assert sizes(a) == sizes(b) and np.allclose(gaps(a), gaps(b))
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert all((x[1] == y[1]).all() and x[0] == y[0] and x[2] == y[2]
               for x, y in zip(a, again))
    spec_p, spec_o = tr["prompt_len"], tr["output_len"]
    assert all(spec_p["min"] <= len(p) <= spec_p["max"]
               and spec_o["min"] <= m <= spec_o["max"] for _, p, m in a)
    assert abs(a[-1][0] - b[-1][0]) < 1e-9 and a[-1][0] < 45.0


def test_open_loop_times_from_due_and_counts_failures():
    class Stream:
        def __init__(self, n):
            self.n = n

        def tokens(self):
            for i in range(self.n):
                yield i

    def start(prompt, max_new):
        if len(prompt) == 3:
            raise RuntimeError("refused")
        return Stream(max_new if len(prompt) != 2 else max_new - 1)

    import time
    sched = [(0.0, np.zeros(1, np.int32), 3), (0.02, np.zeros(2, np.int32), 3),
             (0.04, np.zeros(3, np.int32), 3)]
    t0 = time.perf_counter()
    reqs = loadgen.run_open_loop(sched, start, t0, drain_s=5.0)
    assert [r.ok for r in reqs] == [True, False, False]
    assert reqs[2].error.startswith("RuntimeError")
    assert all(r.sent >= r.due for r in reqs)
    assert abs(reqs[1].due - t0 - 0.02) < 1e-9
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3.0


def test_closed_loop_counts_what_completes_in_time():
    """tools/calibrate.py's capacity reading: each client sends its next
    request when its last one completes; only requests that end inside
    the time count."""
    import time

    class Stream:
        def tokens(self):
            time.sleep(0.02)
            yield from range(5)

    asked = []

    def next_request(i, k):
        asked.append((i, k))
        return np.zeros(4, np.int32), 5

    done, tokens, secs = loadgen.run_closed_loop(
        3, 0.5, next_request, lambda prompt, max_new: Stream())
    assert tokens == 5 * done and 0.4 < secs <= 0.5 + 1e-6
    assert 3 * 10 <= done <= 3 * 25
    assert {i for i, _ in asked} == {0, 1, 2}


def test_lengths_from_a_published_mean():
    """A mix states its lengths by `median` or by the `mean` its source
    prints; before clipping both give the same lognormal."""
    by_mean = {"mean": 161.31, "sigma": 1.0, "min": 1, "max": 10 ** 6}
    by_median = dict(by_mean, median=161.31 / np.exp(0.5))
    del by_median["mean"]
    a = loadgen._lengths(np.random.RandomState(0), by_mean, 20000)
    b = loadgen._lengths(np.random.RandomState(0), by_median, 20000)
    assert (a == b).all() and abs(a.mean() / 161.31 - 1) < 0.05
    tr = traffic()
    assert "arXiv" in tr["lengths_source"] and "mean" in tr["prompt_len"]
    assert tr["engine"]["max_context"] >= (tr["prompt_len"]["max"]
                                           + tr["output_len"]["max"])
