"""From a profiler trace (.xplane.pb) to the numbers the benchmark reports.

`load_planes` reads the file with nothing but JAX into plain lists;
`reduce_planes` is pure arithmetic over them, so a synthetic trace tests
it.  Busy time is the UNION of the intervals in which an operation ran on
a device (overlapping events are not counted twice), averaged over the
chips used; the window is the extent of the whole trace, host and device;
each idle gap of device 0 is attributed to the host event that covers most
of it.
"""
import bisect
import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
NAME_CHARS = 120
# an op that only holds other ops (its body's events are in the trace
# too): busy while they run, but not an entry of the ranking
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
SHORT_GAP_NS = 20_000         # gaps under 20 us are launch latency, pooled


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def load_planes(path):
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}]"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _is_device(plane):
    name = plane["name"]
    return name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):] \
        .split(" ")[0].isdigit() and "SparseCore" not in name


def _events(plane, line_names):
    for line in plane["lines"]:
        if line["name"] in line_names:
            yield from line["events"]


def _attribute(gaps, host_events):
    """{host event name or 'unattributed': ns of gap it covers most}."""
    out = collections.Counter()
    host_events = sorted(host_events, key=lambda e: e[1])
    starts = [e[1] for e in host_events]
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            out["short_gaps"] += g1 - g0
            continue
        best, best_cover = "unattributed", 0.0
        # every host event that starts before the gap ends may overlap it
        hi = bisect.bisect_left(starts, g1)
        for name, s, d in host_events[:hi]:
            cover = min(g1, s + d) - max(g0, s)
            # prefer the tightest event among those covering equally much
            if cover > best_cover:
                best, best_cover = name, cover
        out[best] += g1 - g0
    return out


def reduce_planes(planes, n_devices=1):
    devices = sorted((p for p in planes if _is_device(p)),
                     key=lambda p: p["name"])[:n_devices]
    if not devices:
        return None
    lo, hi = float("inf"), float("-inf")
    for p in planes:
        for line in p["lines"]:
            for _, s, d in line["events"]:
                lo, hi = min(lo, s), max(hi, s + d)
    window_ns = hi - lo
    ops = collections.defaultdict(lambda: [0, 0.0])
    busy_ns = []
    first_union = None
    for p in devices:
        spans = []
        for name, s, d in _events(p, OP_LINES):
            ops[name][0] += 1
            ops[name][1] += d
            spans.append((s, s + d))
        merged = union(spans)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_union is None:
            first_union = merged
    modules = collections.defaultdict(list)
    for name, s, d in _events(devices[0], MODULE_LINES):
        modules[name].append(d / 1e9)
    gaps = [(a[1], b[0]) for a, b in zip(first_union, first_union[1:])]
    if first_union:
        gaps = [(lo, first_union[0][0])] + gaps + [(first_union[-1][1], hi)]
    host = [e for p in planes if not p["name"].startswith("/device:")
            for line in p["lines"] for e in line["events"]]
    by_host = _attribute(gaps, host)
    n = len(devices)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        # the trace names an op by its whole HLO line: keep its head
        "device_ops": [[k[:NAME_CHARS], v[1] / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1][1])
                       if not CONTAINER.search(k)],
        "kernels": [(k, v[0], v[1] / 1e9) for k, v in ops.items()],
        "modules": dict(modules),
        "idle_gaps": [[k[:NAME_CHARS], v / 1e9]
                      for k, v in by_host.most_common()],
    }


def reduce_trace(trace_dir, n_devices=1):
    path = newest_xplane(trace_dir)
    return reduce_planes(load_planes(path), n_devices) if path else None


def digest(trace_dir, top=40):
    """What a trace holds, for a first look by hand: planes, lines, and
    the commonest event names of each line."""
    out = []
    for p in load_planes(newest_xplane(trace_dir)):
        out.append(f"plane {p['name']!r}")
        for line in p["lines"]:
            tot = collections.Counter()
            for name, _, d in line["events"]:
                tot[name] += d
            out.append(f"  line {line['name']!r}: {len(line['events'])} "
                       f"events, {len(tot)} names")
            for name, d in tot.most_common(top):
                out.append(f"    {d / 1e6:10.3f} ms  {name[:150]}")
    return "\n".join(out)
