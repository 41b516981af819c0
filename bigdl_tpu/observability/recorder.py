"""Thread-safe telemetry recorder (≙ optim/Metrics.scala grown up).

One :class:`Recorder` instance aggregates four primitive kinds:

  counters    monotonically increasing totals (bytes reduced, stall
              seconds, records seen) — ``inc``
  gauges      last-written values (queue depth, bytes-per-step) —
              ``gauge``
  spans       wall-clock timed regions (``with rec.span("data_fetch")``),
              accumulated per step, mirrored as
              ``jax.profiler.TraceAnnotation`` so they line up with
              device events on an XLA trace, and kept one by one (name,
              start, end, parent) in the process default
              :class:`~.tracing.Tracer`'s store, where a reader with no
              handle on the recorder finds them after the run
  histograms  per-step value distributions kept as count/min/max/
              sum/sumsq plus a bounded recent-sample window for
              p50/p95/p99 quantiles — ``observe``; read back via
              ``hist_quantiles``/``hist_summary``

``start_step``/``end_step`` bracket one training iteration; ``end_step``
folds everything recorded since ``start_step`` into a *step record*
(a plain dict) and hands it to every sink.  A disabled recorder's
methods return immediately and ``span`` hands back a shared no-op
context manager, so instrumentation can stay in the hot path
unconditionally.

``trace_every(n, log_dir)`` captures a full XLA profiler trace of every
n-th step — the on-demand deep view to the step records' always-on
shallow view.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any, Dict, List, Optional

from . import context as _trace_clock
from . import tracing as _tracing

# ids of recorder spans: a counter, not uuid4 (a decode tick mints eight)
_span_ids = itertools.count(1)


class _NullSpan:
    """Shared no-op context manager for disabled recorders."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass

    def discard(self):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One timed region.  Its interval takes in the span's own
    bookkeeping (the start is read first and the end last), so spans
    opened back to back leave no gap that nothing accounts for."""

    __slots__ = ("_rec", "_name", "_annotate", "_trace_id", "_args",
                 "_t0", "_ann", "_ctx", "_keep")

    def __init__(self, rec: "Recorder", name: str, annotate: bool,
                 trace_id: Optional[str], args: Dict[str, Any]):
        self._rec = rec
        self._name = name
        self._annotate = annotate
        self._trace_id = trace_id
        self._args = args
        self._ann = None
        self._keep = True

    def set(self, **args):
        """Attach arguments known only once the region is under way."""
        self._args.update(args)

    def discard(self):
        """Leave nothing behind: no sum, no stored span (the region
        turned out not to be what its name says)."""
        self._keep = False

    def __enter__(self):
        # one trace clock across the repo (context.trace_now =
        # time.monotonic); perf_counter here used to skew merged
        # Perfetto timelines against the serving TraceRing's stamps
        self._t0 = _trace_clock.trace_now()
        self._ctx = self._rec._open_span(self._trace_id)
        if self._annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation(self._name)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._close_span(self._ctx)
        if self._keep:
            self._rec._record_span(self._name, self._ctx, self._t0,
                                   _trace_clock.trace_now(), self._args)
        return False


class Recorder:
    """Aggregates telemetry and emits one record per training step.

    ``sinks`` is any iterable of objects with ``emit(record: dict)``
    (see :mod:`~bigdl_tpu.observability.sinks`).  ``annotate`` mirrors
    spans onto the jax profiler timeline (cheap; only meaningful while
    a trace is being captured).  Every span is also kept as a
    :class:`~.tracing.Span` in the process default tracer's store.
    """

    def __init__(self, sinks=(), enabled: bool = True,
                 annotate: bool = True, hist_sample_cap: int = 2048,
                 keep_records: int = 256, keep_series: int = 0,
                 series_clock=None):
        self._lock = threading.Lock()
        self.sinks = list(sinks)
        self._enabled = bool(enabled)
        self.annotate = bool(annotate)
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        # pending per-step state, reset by end_step
        self._spans: Dict[str, float] = {}
        self._span_counts: Dict[str, int] = {}
        # stored spans: the trace they belong to unless a span names
        # its own, and per thread the contexts of the spans now open
        # (the innermost is the parent of the next one)
        self._trace_id = f"{next(_span_ids):032x}"
        self._open = threading.local()
        self._scalars: Dict[str, float] = {}
        self._hists: Dict[str, List[float]] = {}
        # bounded raw-sample window per histogram so percentiles
        # (p50/p95/p99 — the serving-latency SLO numbers) are available;
        # the moment/extremum fields above stay exact over ALL samples,
        # the quantiles cover the most recent `hist_sample_cap`
        self.hist_sample_cap = int(hist_sample_cap)
        self._hist_samples: Dict[str, deque] = {}
        self._step: Optional[int] = None
        self._step_t0: Optional[float] = None
        self._n_records = 0
        self._trace_cfg = None        # (every_n, log_dir)
        self._tracing = False
        # flight-recorder ring: the last `keep_records` emitted records
        # (step + out-of-band), kept regardless of sinks so a crash dump
        # and the /records endpoint work even for a sink-less recorder
        self.keep_records = int(keep_records)
        self._ring: deque = deque(maxlen=max(self.keep_records, 1))
        # liveness: wall time the current step opened / the last step
        # closed — what /healthz and the stall watchdog read
        self._step_started_wall: Optional[float] = None
        self._last_step_end: Optional[float] = None
        self._last_step_index: Optional[int] = None
        # cost attribution (observability.profile): a StepCostModel
        # whose scalars(dur) fold perf/mfu, perf/hbm_bw_util and
        # mem/peak_hbm_bytes into every step record
        self._cost_model = None
        # goodput attribution (observability.goodput): a GoodputLedger
        # end_step folds span totals into (device-second buckets) and
        # mirrors as goodput/* gauges; same no-new-host-syncs
        # discipline as the cost model
        self._ledger = None
        # gauge pollers: callables(recorder) refreshed before each
        # snapshot()/end_step() — live device-memory stats and friends
        self._gauge_pollers: List = []
        # opt-in time series: keep_series > 0 attaches a SeriesStore
        # (that many points per metric) fed by end_step and
        # series_tick(); series_clock injects virtual time for
        # deterministic windowed math in tests
        self.series = None
        if keep_series:
            from .timeseries import SeriesStore
            self.series = SeriesStore(capacity=int(keep_series),
                                      clock=series_clock)
        # opt-in Prometheus histogram buckets: name (or "prefix/*"
        # family) -> sorted upper bounds; per-bin counts live beside
        # _hists and share its per-step lifecycle
        self._hist_bucket_spec: Dict[str, tuple] = {}
        self._hist_bucket_bounds: Dict[str, Optional[tuple]] = {}
        self._hist_bucket_counts: Dict[str, List[int]] = {}

    # -- enable/disable -------------------------------------------------- #
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, on: bool = True):
        self._enabled = bool(on)
        return self

    def add_sink(self, sink):
        self.sinks.append(sink)
        return self

    def set_cost_model(self, model):
        """Attach a cost model (anything with ``scalars(dur) -> dict``,
        e.g. :class:`~bigdl_tpu.observability.profile.StepCostModel`);
        ``end_step`` folds its derived efficiency scalars into every
        step record.  ``None`` detaches."""
        self._cost_model = model
        return self

    def set_ledger(self, ledger):
        """Attach a :class:`~bigdl_tpu.observability.goodput
        .GoodputLedger`; ``end_step`` folds each step's span totals
        into its buckets and stamps ``goodput/*`` gauges.  ``None``
        detaches."""
        self._ledger = ledger
        return self

    def get_ledger(self):
        """The attached goodput ledger, or None."""
        return self._ledger

    def add_gauge_poller(self, fn):
        """Register ``fn(recorder)`` to refresh live gauges right before
        each ``snapshot()`` / ``end_step()`` — i.e. on every /metrics
        scrape and every step record.  Poller exceptions are swallowed:
        a broken poller must never take down a scrape or the step
        loop."""
        self._gauge_pollers.append(fn)
        return self

    def _run_gauge_pollers(self):
        # OUTSIDE the lock: pollers call self.gauge(), which locks
        for fn in list(self._gauge_pollers):
            try:
                fn(self)
            except Exception:
                pass

    # -- primitives ------------------------------------------------------ #
    def inc(self, name: str, value: float = 1.0) -> float:
        """Add to a monotonic counter; returns the new total."""
        if not self._enabled:
            return 0.0
        with self._lock:
            total = self._counters.get(name, 0.0) + value
            self._counters[name] = total
            return total

    def gauge(self, name: str, value: float):
        """Set a last-value gauge."""
        if not self._enabled:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_value(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def counter_value(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._counters.get(name, default)

    def span_value(self, name: str, default: float = 0.0) -> float:
        """Accumulated seconds of ``name`` in the *pending* step."""
        with self._lock:
            return self._spans.get(name, default)

    def reset_gauges(self, prefix: str = ""):
        """Drop gauges whose name starts with ``prefix`` (used before a
        step-function rebuild so trace-time collective accounting does
        not double-count across recompiles)."""
        with self._lock:
            for k in list(self._gauges):
                if k.startswith(prefix):
                    del self._gauges[k]

    def scalar(self, name: str, value):
        """Record a per-step scalar (loss, grad-norm, lr, ...).  Device
        scalars are accepted and converted at ``end_step``."""
        if not self._enabled:
            return
        with self._lock:
            self._scalars[name] = value

    def observe(self, name: str, value: float):
        """Add one observation to the step's histogram for ``name``."""
        if not self._enabled:
            return
        v = float(value)
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                self._hists[name] = [1, v, v, v, v * v]
            else:
                h[0] += 1
                h[1] = min(h[1], v)
                h[2] = max(h[2], v)
                h[3] += v
                h[4] += v * v
            s = self._hist_samples.get(name)
            if s is None:
                s = self._hist_samples[name] = deque(
                    maxlen=self.hist_sample_cap)
            s.append(v)
            if self._hist_bucket_spec:
                bounds = self._resolve_buckets(name)
                if bounds is not None:
                    c = self._hist_bucket_counts.get(name)
                    if c is None:
                        c = self._hist_bucket_counts[name] = \
                            [0] * (len(bounds) + 1)
                    c[bisect_left(bounds, v)] += 1

    # -- Prometheus histogram buckets (opt-in) --------------------------- #
    def set_hist_buckets(self, spec: Dict[str, Any]):
        """Opt histograms into cumulative ``_bucket`` exposition.
        ``spec`` maps an exact histogram name — or a ``"prefix/*"``
        family — to its ``le`` upper bounds (sorted ascending; ``+Inf``
        is implicit).  Exact names beat families; within families the
        longest prefix wins.  Buckets are counted at ``observe`` time,
        so ``_bucket`` lines stay exactly consistent with ``_count``
        instead of being re-derived from the bounded sample window."""
        with self._lock:
            self._hist_bucket_spec = {
                str(k): tuple(sorted(float(b) for b in v))
                for k, v in spec.items()}
            self._hist_bucket_bounds.clear()
            self._hist_bucket_counts.clear()
        return self

    def _resolve_buckets(self, name: str) -> Optional[tuple]:
        # caller holds the lock
        if name in self._hist_bucket_bounds:
            return self._hist_bucket_bounds[name]
        bounds = self._hist_bucket_spec.get(name)
        if bounds is None:
            best = -1
            for pat, b in self._hist_bucket_spec.items():
                if pat.endswith("/*") and len(pat) > best \
                        and name.startswith(pat[:-1]):
                    bounds, best = b, len(pat)
        self._hist_bucket_bounds[name] = bounds
        return bounds

    def hist_buckets(self, name: str):
        """``(bounds, per_bin_counts)`` for an opted-in histogram with
        observations this step, else ``None``.  ``per_bin_counts`` has
        ``len(bounds) + 1`` entries (the last is the overflow bin);
        renderers cumulate them into ``le``-labeled samples."""
        with self._lock:
            c = self._hist_bucket_counts.get(name)
            if c is None:
                return None
            return (self._hist_bucket_bounds.get(name), list(c))

    def hist_quantiles(self, name: str, qs=(50.0, 95.0, 99.0)
                       ) -> Optional[Dict[str, float]]:
        """``{"p50": ..., "p95": ..., "p99": ...}`` over the pending
        histogram's sample window, or None if nothing was observed.
        Long-running consumers (the serving engine, the /metrics
        endpoint) read this without a step loop; ``end_step`` folds the
        same numbers into the step record.  Unknown or empty names
        return ``None`` — never raise — so health endpoints can probe
        histograms that may not have been observed yet."""
        try:
            with self._lock:
                s = self._hist_samples.get(name)
                samples = sorted(s) if s else None
        except TypeError:        # unhashable name: nothing recorded under it
            return None
        if not samples:
            return None
        return {f"p{q:g}": _quantile(samples, q) for q in qs}

    def hist_summary(self, name: str) -> Optional[Dict[str, float]]:
        """count/min/max/mean plus p50/p95/p99 of the pending histogram;
        ``None`` (never an exception) for unknown/empty names."""
        try:
            with self._lock:
                h = self._hists.get(name)
                if h is None or not h[0]:
                    return None
                s = self._hist_samples.get(name)
                samples = sorted(s) if s else []
        except TypeError:        # unhashable name
            return None
        out = {"count": int(h[0]), "min": h[1], "max": h[2],
               "mean": h[3] / max(h[0], 1), "sumsq": h[4]}
        if samples:
            out.update({f"p{q:g}": _quantile(samples, q)
                        for q in (50.0, 95.0, 99.0)})
        return out

    def hist_names(self) -> List[str]:
        """Names with at least one observation in the pending step."""
        with self._lock:
            return list(self._hists)

    def span(self, name: str, *, annotate: bool = True,
             trace_id: Optional[str] = None, **args):
        """Context manager timing a region into the current step and
        into the default tracer's store.  Its parent there is the span
        open on this thread when it begins; ``trace_id`` files it under
        another trace than the recorder's own (a request's), ``args``
        ride on the stored span.  ``annotate=False`` keeps a span that
        only groups others off the profiler's timeline, where it would
        cover every gap its children explain.  The handle's ``set()``
        adds arguments later and ``discard()`` drops the span."""
        if not self._enabled:
            return _NULL_SPAN
        return _Span(self, name, annotate and self.annotate, trace_id, args)

    def add_span(self, name: str, seconds: float, *,
                 trace_id: Optional[str] = None, **args):
        """Record an externally-timed duration as a span whose interval
        ends now."""
        if not self._enabled:
            return
        t1 = _trace_clock.trace_now()
        self._record_span(name, self._span_context(trace_id),
                          t1 - seconds, t1, args)

    def _open_stack(self) -> list:
        """Contexts of the spans now open on this thread."""
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _span_context(self, trace_id: Optional[str]):
        """Identity of a span that begins on this thread now: a child
        of the innermost open span, in its trace unless told another."""
        stack = self._open_stack()
        parent = stack[-1] if stack else None
        if trace_id is None:
            trace_id = parent.trace_id if parent is not None \
                else self._trace_id
        return _trace_clock.TraceContext(
            trace_id, f"{next(_span_ids):016x}",
            None if parent is None else parent.span_id)

    def _open_span(self, trace_id: Optional[str]):
        ctx = self._span_context(trace_id)
        self._open.stack.append(ctx)
        return ctx

    def _close_span(self, ctx):
        stack = self._open.stack
        if stack[-1] is ctx:
            stack.pop()
        else:                       # spans closed out of order
            stack.remove(ctx)

    def _record_span(self, name: str, ctx, t0: float, t1: float,
                     args: Dict[str, Any]):
        with self._lock:
            self._spans[name] = self._spans.get(name, 0.0) + (t1 - t0)
            self._span_counts[name] = self._span_counts.get(name, 0) + 1
        # the tracer is looked up now, so set_tracer redirects spans
        _tracing.get_tracer().store.add(
            _tracing.Span(name, ctx, t0, t1, args=args))

    # -- step lifecycle -------------------------------------------------- #
    def start_step(self, step: Optional[int] = None):
        if not self._enabled:
            return
        with self._lock:
            self._step = step
            self._step_t0 = _trace_clock.trace_now()
            self._step_started_wall = time.time()
        if self._ledger is not None:
            try:
                # close out the inter-step gap (background phase) so
                # fold_step attributes only this step's own interval;
                # outside our lock — recorder/ledger locks never nest
                self._ledger.note_step_begin()
            except Exception:
                pass        # attribution must never kill the step loop
        self._maybe_start_trace(step)

    def end_step(self, step: Optional[int] = None,
                 **scalars) -> Optional[Dict[str, Any]]:
        """Close the current step: fold pending spans/scalars/histograms
        plus counter and gauge snapshots into one record, emit it to
        every sink, and reset the per-step state."""
        if not self._enabled:
            return None
        self._maybe_stop_trace()
        self._run_gauge_pollers()
        with self._lock:
            if step is None:
                step = self._step
            dur = (_trace_clock.trace_now() - self._step_t0
                   if self._step_t0 is not None else None)
            pend = dict(self._scalars)
            pend.update(scalars)
            if self._cost_model is not None:
                try:
                    # pure arithmetic over the compiled cost capture —
                    # safe under the lock; explicit scalars win ties
                    for k, v in self._cost_model.scalars(dur).items():
                        pend.setdefault(k, v)
                except Exception:
                    pass        # attribution must never kill a record
            rec: Dict[str, Any] = {
                "type": "step",
                "step": step,
                "time": time.time(),
                "dur": dur,
                "spans": dict(self._spans),
                "span_counts": dict(self._span_counts),
                "scalars": {k: _to_float(v) for k, v in pend.items()},
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
            }
            recs = rec["scalars"].get("records")
            if dur and isinstance(recs, (int, float)) and recs > 0:
                rec["scalars"]["records_per_sec"] = recs / dur
            if self._hists:
                rec["hist"] = {}
                for k, h in self._hists.items():
                    entry = {"count": int(h[0]), "min": h[1], "max": h[2],
                             "mean": h[3] / max(h[0], 1),
                             "sumsq": h[4]}
                    s = self._hist_samples.get(k)
                    if s:
                        samples = sorted(s)
                        entry.update(
                            {f"p{q:g}": _quantile(samples, q)
                             for q in (50.0, 95.0, 99.0)})
                    rec["hist"][k] = entry
            self._spans.clear()
            self._span_counts.clear()
            self._scalars.clear()
            self._hists.clear()
            self._hist_samples.clear()
            self._hist_bucket_counts.clear()
            self._step = None
            self._step_t0 = None
            self._step_started_wall = None
            self._last_step_end = rec["time"]
            self._last_step_index = step
            self._n_records += 1
            self._ring.append(rec)
            sinks = list(self.sinks)
        if self._ledger is not None:
            try:
                # the fold and the gauge mirror both run OUTSIDE the
                # recorder lock (publish takes the ledger lock, then
                # rec.gauge takes ours — strictly sequential, so the
                # two locks never nest in either order)
                self._ledger.fold_step(rec.get("dur"),
                                       rec.get("spans") or {})
                rec["goodput"] = self._ledger.publish(self)
            except Exception:
                pass        # attribution must never kill a record
        if self.series is not None:
            self._feed_series(rec)
        for s in sinks:
            s.emit(rec)
        return rec

    def _feed_series(self, rec: Dict[str, Any]):
        """Append one point per numeric scalar/counter/gauge (and per
        histogram p50/p95/p99, as ``<name>/pXX``) to the attached
        series store at its clock's current time."""
        store = self.series
        t = store.now()
        for k, v in rec.get("scalars", {}).items():
            if isinstance(v, (int, float)):
                store.observe(k, v, t)
        for k, v in rec.get("counters", {}).items():
            store.observe(k, v, t)
        for k, v in rec.get("gauges", {}).items():
            store.observe(k, v, t)
        for k, entry in rec.get("hist", {}).items():
            for q in ("p50", "p95", "p99"):
                if q in entry:
                    store.observe(f"{k}/{q}", entry[q], t)

    def series_tick(self):
        """Snapshot counters, gauges and pending-histogram quantiles
        into the attached series store WITHOUT cutting a step record —
        how sources with no step loop (serving engines) or a periodic
        poller grow a time dimension.  No-op without ``keep_series``."""
        if self.series is None or not self._enabled:
            return None
        snap = self.snapshot()
        rec = {"counters": snap["counters"], "gauges": snap["gauges"],
               "hist": {}}
        for name in self.hist_names():
            qs = self.hist_quantiles(name)
            if qs:
                rec["hist"][name] = qs
        self._feed_series(rec)
        return rec

    def emit_record(self, rec_type: str, **fields):
        """Emit an out-of-band (non-step) record to every sink — e.g.
        the post-drain ``checkpoint_summary`` whose writer-thread
        counters finished after the last step record was cut."""
        if not self._enabled:
            return None
        rec = {"type": rec_type, "time": time.time(), **fields}
        with self._lock:
            self._ring.append(rec)
            sinks = list(self.sinks)
        for s in sinks:
            s.emit(rec)
        return rec

    def abort_step(self):
        """Discard the pending step (e.g. the data iterator ran dry after
        ``start_step``); pending spans/scalars are dropped."""
        if not self._enabled:
            return
        self._maybe_stop_trace()
        with self._lock:
            self._spans.clear()
            self._span_counts.clear()
            self._scalars.clear()
            self._hists.clear()
            self._hist_samples.clear()
            self._hist_bucket_counts.clear()
            self._step = None
            self._step_t0 = None
            self._step_started_wall = None

    # -- on-demand XLA profiles ------------------------------------------ #
    def trace_every(self, n_steps: int, log_dir: str):
        """Capture a ``jax.profiler`` trace of every ``n_steps``-th step
        into ``log_dir`` (open with TensorBoard's profile plugin or
        Perfetto).  ``n_steps=0`` disables."""
        self._trace_cfg = (int(n_steps), log_dir) if n_steps else None
        return self

    def _maybe_start_trace(self, step):
        if self._tracing:
            # the previously traced step raised before end_step/
            # abort_step could close the session: stop the stale trace
            # now, or the profiler stays wedged — silently folding every
            # remaining step into one giant capture — for the rest of
            # the run
            self._maybe_stop_trace()
        cfg = self._trace_cfg
        if cfg is None or step is None or step % cfg[0] != 0:
            return
        import jax
        try:
            jax.profiler.start_trace(cfg[1])
            self._tracing = True
        except Exception:
            # start_trace may have opened a session before raising:
            # never let the flag and the profiler disagree
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._tracing = False

    def _maybe_stop_trace(self):
        if not self._tracing:
            return
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass        # profiling must never kill training
        finally:
            self._tracing = False

    # -- introspection / teardown ---------------------------------------- #
    def snapshot(self) -> Dict[str, Dict[str, float]]:
        self._run_gauge_pollers()
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges)}

    def recent_records(self, n: Optional[int] = None,
                       rec_type: Optional[str] = None
                       ) -> List[Dict[str, Any]]:
        """The last ``n`` records (all kept ones when ``n`` is None) from
        the bounded ring, oldest first; ``rec_type`` filters by the
        record's ``type`` field.  This is the crash flight recorder's
        source and what the /records endpoint serves."""
        with self._lock:
            recs = list(self._ring)
        if rec_type is not None:
            recs = [r for r in recs if r.get("type") == rec_type]
        if n is None:
            return recs
        # n=0 means none (not all); negative/oversized n must not wrap
        n = max(int(n), 0)
        return recs[max(len(recs) - n, 0):] if n else []

    def step_age(self) -> Optional[float]:
        """Seconds since the pending step opened (a step is in flight) or
        since the last step record was cut; ``None`` before any step.
        The liveness signal: a healthy loop keeps this small, a stalled
        one lets it grow without bound."""
        with self._lock:
            started, ended = self._step_started_wall, self._last_step_end
        now = time.time()
        if started is not None:
            return now - started
        if ended is not None:
            return now - ended
        return None

    def step_in_flight(self) -> bool:
        """True between start_step and end_step/abort_step — i.e. the
        current step_age() measures a PENDING step, not idle time."""
        with self._lock:
            return self._step_started_wall is not None

    def last_step(self) -> Optional[int]:
        """Index of the newest completed step (None before the first)."""
        with self._lock:
            return self._last_step_index

    def summary(self) -> str:
        snap = self.snapshot()
        return json.dumps(snap, sort_keys=True)

    def flush(self):
        for s in self.sinks:
            fl = getattr(s, "flush", None)
            if fl is not None:
                fl()
        return self

    def close(self):
        for s in self.sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()


def _to_float(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return v


def _quantile(sorted_samples: List[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default method) over an
    already-sorted list; kept dependency-free so the recorder never
    imports numpy on the hot path."""
    n = len(sorted_samples)
    if n == 0:
        return float("nan")
    if n == 1:
        return sorted_samples[0]
    pos = (q / 100.0) * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return sorted_samples[lo] * (1.0 - frac) + sorted_samples[hi] * frac


# -- process-active recorder ---------------------------------------------- #
# Library internals (DeviceLoader, allreduce accounting) report to the
# process-active recorder when one wasn't passed explicitly; the default
# is a disabled instance so un-instrumented runs pay only a bool check.
_null = Recorder(enabled=False, annotate=False)
_active = _null


def null_recorder() -> Recorder:
    """The shared always-disabled recorder."""
    return _null


def get_recorder() -> Recorder:
    """The process-active recorder (a disabled no-op by default)."""
    return _active


def set_recorder(rec: Optional[Recorder]) -> Recorder:
    """Install ``rec`` as the process-active recorder (``None`` resets
    to the disabled default).  Returns the previous one."""
    global _active
    prev = _active
    _active = rec if rec is not None else _null
    return prev
