"""The one general load generator for served traffic.

A traffic mix is a data file: an arrival shape and rate, and clipped
lognormal prompt and output lengths, each given by its `median` or by the
`mean` a published source states (median = mean / exp(sigma^2 / 2)).  `make_schedule` draws the mix's
canonical arrivals and sizes from the file's own `draw_seed` (seeded
Poisson in virtual time, arrivals.py) and lets `--seed` only PERMUTE
them and draw the prompts' token ids: every seed offers the same set of
gaps and sizes in another order, so seeds change the order of the work
and not its amount.  With `"replay": true` among the arrivals the seed
draws the token ids alone and every seed replays the draw's own
timeline.  `run_open_loop` paces the schedule by the wall clock
(the pacing loop of scripts/serve_bench.py `run_open_loop`, copied), one
short-lived reader thread per request, each request timed from when it
was DUE, and reports how late the generator itself ran.
"""
import threading
import time

import numpy as np

from benchmarks import arrivals


def _lengths(rng, spec, n):
    median = spec["median"] if "median" in spec \
        else spec["mean"] / np.exp(spec["sigma"] ** 2 / 2.0)
    x = rng.lognormal(np.log(median), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_schedule(traffic, seed, seconds, vocab_size):
    """[(due_s, prompt ids, max_new_tokens)] for one run."""
    arr = traffic["arrivals"]
    base = np.random.RandomState(arr["draw_seed"])
    times = np.array(list(arrivals.virtual_arrivals(
        base, arr["rate_per_s"], arrivals.TRACES[arr["shape"]], seconds)))
    n = len(times)
    gaps = np.diff(np.concatenate([[0.0], times]))
    p_len = _lengths(base, traffic["prompt_len"], n)
    o_len = _lengths(base, traffic["output_len"], n)
    rng = np.random.default_rng(seed)
    if arr.get("replay"):
        order = np.arange(n)
    else:
        if arr["shape"] == "steady":
            # under a phased shape the order of the gaps is the shape itself
            gaps = rng.permutation(gaps)
        order = rng.permutation(n)
    due = np.cumsum(gaps)
    return [(float(due[i]),
             rng.integers(0, vocab_size, int(p_len[j]), dtype=np.int32),
             int(o_len[j])) for i, j in enumerate(order)]


class Request:
    __slots__ = ("due", "sent", "prompt", "max_new", "tokens", "stamps",
                 "error", "thread")

    def __init__(self, due, prompt, max_new):
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.sent = None
        self.tokens, self.stamps = [], []
        self.error = None
        self.thread = None

    @property
    def ok(self):
        return self.error is None and len(self.tokens) == self.max_new


def _read(req, stream):
    try:
        for tok in stream.tokens():
            req.stamps.append(time.perf_counter())
            req.tokens.append(int(tok))
    except Exception as e:                       # shed, closed, failed
        req.error = f"{type(e).__name__}: {e}"


def run_open_loop(schedule, start_stream, t0, drain_s=60.0):
    """Offer `schedule` from wall time t0.  `start_stream(prompt, max_new)`
    returns an object whose .tokens() yields tokens as they are made.
    Waits up to drain_s past the last arrival for every answer.
    -> [Request] with absolute due/sent times."""
    reqs = []
    for due, prompt, max_new in schedule:
        r = Request(t0 + due, prompt, max_new)
        while True:
            lag = r.due - time.perf_counter()
            if lag <= 0:
                break
            time.sleep(min(lag, 0.01))
        r.sent = time.perf_counter()
        try:
            stream = start_stream(prompt, max_new)
        except Exception as e:                   # refused at the door
            r.error = f"{type(e).__name__}: {e}"
        else:
            r.thread = threading.Thread(target=_read, args=(r, stream),
                                        daemon=True)
            r.thread.start()
        reqs.append(r)
    deadline = time.perf_counter() + drain_s
    for r in reqs:
        if r.thread is not None:
            r.thread.join(max(deadline - time.perf_counter(), 0.0))
            if r.thread.is_alive():
                r.error = "never finished"
    return reqs


def run_closed_loop(n_clients, seconds, next_request, start_stream):
    """Calibration only (tools/calibrate.py): n_clients each send their
    next request when the last one completes.
    -> (completed requests, tokens, seconds)."""
    done = [0, 0]
    lock = threading.Lock()
    t_end = time.perf_counter() + seconds

    def client(i):
        k = 0
        while time.perf_counter() < t_end:
            prompt, max_new = next_request(i, k)
            k += 1
            n = sum(1 for _ in start_stream(prompt, max_new).tokens())
            if time.perf_counter() < t_end:
                with lock:
                    done[0] += 1
                    done[1] += n

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 120)
    return done[0], done[1], min(time.perf_counter(), t_end) - t0


def percentile(values, q):
    """Nearest-rank-interpolated percentile of a non-empty list."""
    return float(np.percentile(np.asarray(values, np.float64), q))
