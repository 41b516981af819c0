"""The benchmark harness: everything that is the same for every cell.

A cell is an entry of `workloads` in BENCHMARK.json.  It names a
configuration (its file is given under `configs`) and a traffic mix (a
data file `<path>/traffic/<traffic>.json` under one of `paths`).  The
traffic file names its `runner`, found as `<path>/runners/<runner>.py`;
each per-layer metric is a reader `<path>/metrics/<name>.py`.  Nothing
here knows a cell, a model or a metric by name: a later PR adds files and
entries and edits none.

One run (`run_cell`): look for the chip, set the compile cache, hand the
runner a `Probe`, let it set up and drive its window, read the device's
peak memory, have the runner free the program's state, and only then run
the comparison with the plain reference.  With `--trace 1` a helper
thread traces a few seconds inside the window and the per-layer readers
reduce the trace, the runner's spans and counters to metrics.
"""
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE_START_S = 2.0      # into the window, so the first steps are steady
TRACE_LENGTH_S = 3.0


class BenchmarkError(Exception):
    """The run cannot give a result (no chip, malformed cell, ...)."""


# --------------------------------------------------------------------- #
# cells from data
# --------------------------------------------------------------------- #
def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _find(root, paths, *parts):
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.exists(cand):
            return cand
    raise BenchmarkError(
        f"{os.path.join(*parts)} is under none of {list(paths)}")


def load_module(path):
    """A runner or a metric reader by file path (metric names hold dots,
    so they are not importable module names)."""
    name = "_bench_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with everything its run needs, read from files."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.paths = self.spec["paths"]
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise BenchmarkError(
                f"no workload {name!r}; have {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfgs = {c["name"]: c for c in self.spec["configs"]}
        self.config_entry = cfgs[self.workload["config"]]
        self.config = _read_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = _read_json(_find(
            root, self.paths, "traffic", self.workload["traffic"] + ".json"))
        self.end_to_end = [m for m in self.spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]

    def runner(self):
        return load_module(_find(self.root, self.paths, "runners",
                                 self.traffic["runner"] + ".py"))

    def reader(self, metric_name):
        return load_module(_find(self.root, self.paths, "metrics",
                                 metric_name + ".py"))


# --------------------------------------------------------------------- #
# device, peaks, cache
# --------------------------------------------------------------------- #
def set_compile_cache(root=ROOT):
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment already places it (then nothing is set in code)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(os.path.abspath(root), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips, require_chip=True):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise BenchmarkError(
            f"JAX found no accelerator (platform {devs[0].platform!r}); "
            "the benchmark has no CPU route")
    if len(devs) < chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s), JAX sees "
                             f"{len(devs)}")
    return devs[:chips]


def load_peaks(device_kind, strict=True):
    table = _read_json(os.path.join(os.path.dirname(__file__), "peaks.json"))
    if device_kind in table:
        return table[device_kind]
    if strict:
        raise BenchmarkError(f"device kind {device_kind!r} is not in "
                             "peaks.json; add it with its source")
    return None


def seed_key(seed):
    """A JAX key from any whole number (the driver's pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def memory_peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# --------------------------------------------------------------------- #
# the window and its trace
# --------------------------------------------------------------------- #
class Probe:
    """What the harness gives a runner: where the window opens and
    closes, and (with trace) the profiler around a few of its seconds."""

    def __init__(self, t_process_start, trace, trace_dir):
        self.t_process_start = t_process_start
        self.trace = bool(trace)
        self.trace_dir = trace_dir
        self.setup_s = None
        self.t_open = None
        self.t_close = None
        self.traced = None            # (t_start, t_stop) host perf_counter
        self.marks = []               # [(label, seconds since the start)]
        self._thread = None
        self._error = None

    def mark(self, label):
        """A phase of set-up has ended (what moves `setup_s` shows in the
        result line's `setup_phases`)."""
        self.marks.append((label, time.perf_counter() - self.t_process_start))

    def window_open(self):
        """The first instant of the window; set-up ends here."""
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_process_start
        self.marks.append(("window_open", self.setup_s))
        if self.trace:
            self._thread = threading.Thread(target=self._trace_some,
                                            name="bench-trace", daemon=True)
            self._thread.start()
        return self.t_open

    def window_close(self):
        """After the last work of the window is ready on the device."""
        self.t_close = time.perf_counter()
        return self.t_close

    def _trace_some(self):
        import jax
        try:
            time.sleep(TRACE_START_S)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host TraceMe spans only
            opts.host_tracer_level = 2
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t0 = time.perf_counter()
            time.sleep(TRACE_LENGTH_S)
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.traced = (t0, t1)
        except Exception as e:           # reported by finish_trace
            self._error = e

    def finish_trace(self):
        if self._thread is not None:
            self._thread.join(timeout=300)
            if self._thread.is_alive():
                raise BenchmarkError("the profiler did not stop")
        if self._error is not None:
            raise BenchmarkError(f"tracing failed: {self._error!r}")


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
def compared_ok(compared):
    """Every compared number is finite and within its limit (entries whose
    limit is None are observed only), and something was compared."""
    held = [c for c in compared if c.get("limit") is not None]
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in held) and bool(held)


def run_cell(cell, seed, seconds, trace, t_process_start=None,
             require_chip=True, scale=None):
    """Drive one run of `cell`; returns the result line's dict.  `scale`
    (tests only) overrides sizes of the traffic and configuration so a
    CPU can hold a run; the command line never sets it."""
    t_process_start = t_process_start or time.perf_counter()
    devices = find_devices(cell.chips, require_chip)
    set_compile_cache(cell.root)
    kind = devices[0].device_kind
    peaks = load_peaks(kind, strict=require_chip) \
        or load_peaks("TPU v5 lite")
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
    probe = Probe(t_process_start, trace, trace_dir)
    probe.mark("devices")             # imports and the chip, found
    runner = cell.runner().Runner(cell, int(seed), float(seconds), devices,
                                  probe, scale or {})
    runner.run()                      # set-up, then the window
    if probe.t_open is None or probe.t_close is None:
        raise BenchmarkError("the runner never opened or closed its window")
    probe.finish_trace()
    peak = memory_peak_bytes(devices)
    out = runner.results()            # end_to_end, attempted, failed, facts
    runner.release()                  # the program's state leaves the chip
    compared = runner.check()         # the plain reference, now that it fits
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": compared_ok(compared) and out.get("sound", True),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"])}
    if trace:
        from benchmarks import xplane
        summary = xplane.reduce_trace(trace_dir, len(devices)) \
            if probe.traced else None
        if summary is None or summary["busy_s"] <= 0:
            raise BenchmarkError("the traced window holds no device "
                                 "operation")
        ctx = {"cell": cell, "peaks": peaks, "trace": summary,
               "facts": out["facts"], "probe": probe,
               "end_to_end": out["end_to_end"]}
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        device["idle_share"] = 1.0 - summary["busy_s"] / summary["window_s"]
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = {"device_ops": summary["device_ops"][:10],
                             "idle_gaps": summary["idle_gaps"][:10]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(out["end_to_end"], setup_s=probe.setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
        line["device"] = device
    line["setup_phases"] = {k: round(v, 3) for k, v in probe.marks}
    line["observed"] = {c["name"]: c["value"] for c in compared
                        if c.get("limit") is None}
    line["compared"] = {
        c["name"]: {k: c[k] for k in ("value", "limit", "leaf") if k in c}
        for c in compared if c.get("limit") is not None}
    return line


def print_result(line, out=sys.stdout, err=sys.stderr):
    """The compared numbers as the last lines of stderr, the result as the
    last line of stdout."""
    if line.get("setup_phases"):
        print("setup phases (s since the start): " + ", ".join(
            f"{k} {v:.1f}" for k, v in line["setup_phases"].items()),
            file=err)
    for name, value in line.get("observed", {}).items():
        print(f"observed {name} = {value:.6g} (no limit: PERF.md)", file=err)
    for name, c in line["compared"].items():
        verdict = "ok" if (math.isfinite(c["value"])
                           and c["value"] <= c["limit"]) else "OVER"
        where = f" at {c['leaf']}" if c.get("leaf") else ""
        print(f"compared {name} = {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{verdict}{where}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
