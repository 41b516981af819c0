"""One clock for the decode engine's spans and the device's runs, and
every idle gap of the device split across the host leaves under it.

In a profiler trace the device plane and the host plane do not share a
clock: the device's events are stamped a millisecond or two off the
host's, by an amount that changes from trace to trace (PERF.md section
7).  The engine's programs are the anchors.  A run of
the decode step starts no earlier than the `decode.dispatch` that
launched it began, and ends no later than the `decode.sync` that waited
for it ended (the sync returns a copy that follows the run); a prefill
program's run lies the same way between `prefill.dispatch` and
`prefill.sync`.  So each run and each anchor of its program bound the
offset delta (device clock minus host clock) from both sides: the run's
end minus the sync's end from below, the run's start minus the
dispatch's start from above.  The delta that the most runs agree on is
taken, from the middle of the range they share, and only where it
orders at least `MIN_ORDERED` of the runs that have an anchor.

Each idle gap of device 0 of `xplane.SHORT_GAP_NS` or more (the same
gaps as the `breakdown`'s) is then moved onto the host's clock and split
across the leaves it overlaps, by the overlap, never handed whole to
one; what no leaf covers is the remainder (`decode.admit`'s own time
outside a prefill, time between ticks, time under no span).  The leaves
are those of one engine's thread, which never overlap.

Beside the shares, `reduce` gives the range of deltas that order the
most runs (how tightly the anchors fix it), the shares at either end
of that range, and, per program, the medians of the host's time from a
dispatch's start to its run's start and from the run's end to the
sync's end, on the one clock.

`summary(ctx)` loads the traced run's xplane once for every reader of
the run.  It says None where there is nothing to read: no trace, no
device plane, fewer than `MIN_RUNS` runs with an anchor, no delta that
orders them, or prefills that ran without their leaves (a program from
before `prefill.stage` / `.dispatch` / `.sync`).
"""
import bisect
import collections
import re
import statistics
import time

from benchmarks import xplane

ANCHORS = {"decode": ("decode.dispatch", "decode.sync"),
           "prefill": ("prefill.dispatch", "prefill.sync")}
# the leaves a gap is split across, by what the host was doing
PARTS = {"launch": ("decode.schedule", "decode.stage", "decode.dispatch",
                    "prefill.stage", "prefill.dispatch"),
         "sync": ("decode.sync", "prefill.sync"),
         "emit": ("decode.emit",)}
LEAF_PART = {leaf: part for part, leaves in PARTS.items() for leaf in leaves}
# a ladder bucket's program (`jit_prefill_512`) or the chunk program
PREFILL_PROGRAM = re.compile(r"prefill_(chunk|\d+)")
MIN_ORDERED = 0.95
MIN_RUNS = 10
MAX_OFFSET_NS = 50e6          # no anchor further than this from its run


def module_runs(device):
    """{kind: sorted [(start, end)]} of one device plane's program runs:
    the decode step is the module with `fn` in its name that ran most
    often (`_decode_program.py`'s rule), a prefill any module of a
    prefill program."""
    by_name = collections.defaultdict(list)
    for name, s, d in xplane._events(device, xplane.MODULE_LINES):
        by_name[name].append((s, s + d))
    fns = [n for n in by_name if "fn" in n]
    decode = max(fns, key=lambda n: len(by_name[n])) if fns else None
    return {"decode": sorted(by_name[decode]) if decode else [],
            "prefill": sorted(r for n, runs in by_name.items()
                              if PREFILL_PROGRAM.search(n) for r in runs)}


def anchors(host, dispatch, sync):
    """[(start of a dispatch, end of the first sync after it)] from the
    host events sorted by start."""
    out, opened = [], None
    for name, s, d in host:
        if name == dispatch:
            opened = s
        elif name == sync and opened is not None:
            out.append((opened, s + d))
            opened = None
    return out


def offset(runs, anchored):
    """(delta, runs ordered, runs paired, (lo, hi)): delta in ns from the
    middle of the range (lo, hi) that the most runs share, each run
    inside an anchor of its own program there; runs paired is, per
    program, the fewer of its runs and its anchors."""
    bounds = []
    for kind, found in runs.items():
        an = anchored[kind]
        starts = [a0 for a0, _ in an]
        for s, e in found:
            lo = bisect.bisect_left(starts, s - MAX_OFFSET_NS)
            hi = bisect.bisect_right(starts, s + MAX_OFFSET_NS)
            # one run counts once at any delta, whichever anchor holds it
            bounds.extend(xplane.union((e - a1, s - a0) for a0, a1
                                       in an[lo:hi] if e - a1 <= s - a0))
    paired = sum(min(len(runs[k]), len(anchored[k])) for k in runs)
    # a sweep over the ranges' ends; at one delta a range opens before
    # one closes (the ranges are closed)
    ends = sorted([(lo, 0) for lo, _ in bounds]
                  + [(hi, 1) for _, hi in bounds])
    best, count, delta, span = 0, 0, None, None
    for i, (x, closes) in enumerate(ends):
        count += -1 if closes else 1
        if count > best:
            best, span = count, (x, ends[i + 1][0])
            delta = sum(span) / 2.0
    return delta, best, paired, span


def lags(found, anchored, delta):
    """Host ms, medians over the runs that an anchor holds at delta: from
    the dispatch's start to the run's, and from the run's end to the
    sync's end."""
    starts = [a0 for a0, _ in anchored]
    head, tail = [], []
    for s, e in found:
        i = bisect.bisect_right(starts, s - delta) - 1
        if i >= 0 and e - delta <= anchored[i][1]:
            head.append(s - delta - anchored[i][0])
            tail.append(anchored[i][1] - (e - delta))
    return {"dispatch_to_run_ms": statistics.median(head) / 1e6,
            "run_to_sync_end_ms": statistics.median(tail) / 1e6} \
        if head else None


def idle_gaps(planes, device):
    """Device 0's gaps between its merged ops, with the trace's head and
    tail, as `xplane.reduce_planes` finds them."""
    merged = xplane.union((s, s + d) for _, s, d in
                          xplane._events(device, xplane.OP_LINES))
    if not merged:
        return []
    times = [(s, s + d) for p in planes for line in p["lines"]
             for _, s, d in line["events"]]
    lo, hi = min(s for s, _ in times), max(e for _, e in times)
    return [(lo, merged[0][0])] \
        + [(a[1], b[0]) for a, b in zip(merged, merged[1:])] \
        + [(merged[-1][1], hi)]


def split_gaps(gaps, leaves, delta):
    """({leaf: ns of idle under it}, ns of idle in all): each gap of
    SHORT_GAP_NS or more, moved by -delta onto the host's clock, split
    across the leaves [(name, start, end)] it overlaps, by the overlap."""
    leaves = sorted(leaves, key=lambda x: x[1])
    starts = [s for _, s, _ in leaves]
    ends = [e for _, _, e in leaves]        # sorted too: no two overlap
    out, idle = collections.Counter(), 0.0
    for g0, g1 in gaps:
        if g1 - g0 < xplane.SHORT_GAP_NS:
            continue
        idle += g1 - g0
        h0, h1 = g0 - delta, g1 - delta
        for name, s, e in leaves[bisect.bisect_right(ends, h0):
                                 bisect.bisect_left(starts, h1)]:
            out[name] += min(h1, e) - max(h0, s)
    return out, idle


def reduce(planes):
    """What the readers take from one trace, or None without a device
    plane: the offset, how many runs it orders, the idle under each leaf
    and `shares` {launch, sync, emit, rest} in percent of the idle in
    gaps of SHORT_GAP_NS or more (None where the module's doc says)."""
    devices = sorted((p for p in planes if xplane._is_device(p)),
                     key=lambda p: p["name"])
    if not devices:
        return None
    host = sorted((e for p in planes if not p["name"].startswith("/device:")
                   for line in p["lines"] for e in line["events"]),
                  key=lambda e: e[1])
    names = {e[0] for e in host}
    runs = module_runs(devices[0])
    anchored = {k: anchors(host, *pair) for k, pair in ANCHORS.items()}
    delta, ordered, paired, span = offset(runs, anchored)
    out = {"offset_ms": None if delta is None else delta / 1e6,
           "offset_range_ms": span and [x / 1e6 for x in span],
           "runs": {k: len(v) for k, v in runs.items()},
           "runs_paired": paired, "runs_ordered": ordered, "shares": None}
    if "decode.prefill" in names and "prefill.sync" not in names:
        out["why"] = "prefills ran without their leaves"
    elif paired < MIN_RUNS or ordered < MIN_ORDERED * paired:
        out["why"] = f"no offset orders {MIN_ORDERED:.0%} of {paired} runs"
    if "why" in out:
        return out
    out["lags"] = {k: lags(runs[k], anchored[k], delta) for k in runs}
    leaves = [(n, s, s + d) for n, s, d in host if n in LEAF_PART]
    gaps = idle_gaps(planes, devices[0])
    by_leaf, idle = split_gaps(gaps, leaves, delta)
    out["idle_s"] = idle / 1e9
    out["idle_by_leaf_s"] = {n: v / 1e9 for n, v in by_leaf.most_common()}
    if idle > 0:
        out["shares"] = shares(by_leaf, idle)
        # how far the anchors' slack moves them: at either end of the range
        out["shares_at_range_ends"] = [
            shares(*split_gaps(gaps, leaves, d)) for d in span]
    return out


def shares(by_leaf, idle):
    """{launch, sync, emit, rest}: percent of the idle under each part."""
    out = {part: 100.0 * sum(by_leaf[n] for n in members) / idle
           for part, members in PARTS.items()}
    out["rest"] = 100.0 - sum(out.values())
    return out


def summary(ctx):
    """`reduce` of the traced run's newest xplane, loaded once for all the
    readers of a run (kept in `ctx`), with the seconds that took."""
    if "device_clock" not in ctx:
        probe = ctx["probe"]
        path = xplane.newest_xplane(probe.trace_dir) \
            if getattr(probe, "traced", None) else None
        t0 = time.perf_counter()
        found = reduce(xplane.load_planes(path)) if path else None
        if found is not None:
            found["reader_s"] = time.perf_counter() - t0
        ctx["device_clock"] = found
    return ctx["device_clock"]


def share(ctx, part):
    found = summary(ctx)
    return found["shares"][part] if found and found["shares"] else None
