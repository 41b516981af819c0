"""Dynamic-batching inference engine.

The serving pipeline, end to end::

    submit(name, x)                       client threads
       └─ BatchingQueue.put              admission control: full -> shed
            └─ batcher thread            one per model
                 gather <= max_batch rows, flush on deadline
                 drop requests whose SLO already expired
                 pad rows -> power-of-two bucket
                 run the bucket's PRE-COMPILED executable
                 scatter results back to per-request futures

Every request therefore executes inside an already-jitted program:
after :meth:`ServingEngine.warmup` a mixed-size request stream hits
**zero** new XLA compilations (the ``serving.recompiles`` counter is
the proof, and a test asserts it stays 0).  Compilation is AOT
(``jit -> lower -> compile``) so an executable can *never* silently
retrace — a shape the cache doesn't know is a counted cache miss, not
a hidden multi-second stall inside a jitted call.

Telemetry goes through the PR-1 observability
:class:`~bigdl_tpu.observability.Recorder`:

  counters    ``serving.requests`` / ``serving.rows`` /
              ``serving.batches`` / ``serving.shed_queue_full`` /
              ``serving.shed_deadline`` / ``serving.recompiles`` /
              ``serving.warmup_compiles`` / ``serving.errors``
  gauges      ``serving.queue_depth.<model>``
  histograms  ``serving.latency_ms`` (p50/p95/p99 via
              ``Recorder.hist_quantiles``), ``serving.batch_fill``

Attribution (observability.profile) rides on top of the metrics:
every admitted request carries a trace ID and a span timeline
(admit → queue → batch_gather → compute → reply, shed requests ending
in a terminal cause span) collected in a bounded ring —
:meth:`ServingEngine.dump_chrome_trace` / the ``/trace`` route render
it as Chrome-trace/Perfetto JSON.  Each AOT-compiled bucket's XLA
cost/memory analysis is harvested at compile time into
``entry.cost[bucket]`` and emitted as a ``profile`` record, so an
operator can read FLOPs-per-bucket next to batch-fill and decide
whether the ladder wastes compute on padding.
"""
from __future__ import annotations

import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as faultplane
from ..observability import Recorder
from .buckets import BucketLadder
from .queue import (BatchingQueue, EngineClosedError, EngineIntrospection,
                    LoadShedError, Request)
from .registry import ModelEntry, ModelRegistry


class ServingEngine(EngineIntrospection):
    """Batches concurrent requests across a :class:`ModelRegistry`.

    ``max_batch``      largest bucket (rounded up to a power of two)
    ``max_delay_ms``   longest a request waits for batch company
    ``max_queue_rows`` admission cap per model, in rows; beyond it
                       requests shed with :class:`LoadShedError`
    ``recorder``       a Recorder; defaults to a fresh enabled one
                       (metrics are part of the serving contract)
    ``trace_requests`` per-request span tracing into a bounded ring of
                       ``trace_capacity`` completed traces (a few
                       appends per request; the /trace export source)
    """

    def __init__(self, registry: ModelRegistry, *, max_batch: int = 32,
                 max_delay_ms: float = 5.0, max_queue_rows: int = 256,
                 recorder: Optional[Recorder] = None,
                 trace_requests: bool = True, trace_capacity: int = 512):
        from ..observability.profile import TraceRing
        self.registry = registry
        self.ladder = BucketLadder(max_batch)
        self.max_delay = float(max_delay_ms) / 1e3
        self.max_queue_rows = int(max_queue_rows)
        self.recorder = recorder if recorder is not None \
            else Recorder(annotate=False)
        if self.recorder.enabled and self.recorder.get_ledger() is None:
            # goodput attribution: each executed batch folds its
            # interval by fill (padding rows are idle capacity), warmup
            # and recompiles land in compile_warmup via ledger phases
            from ..observability.goodput import GoodputLedger
            self.recorder.set_ledger(GoodputLedger(name="serving",
                                                   devices=1))
        self.trace_ring = TraceRing(trace_capacity) if trace_requests \
            else None
        self._queues: Dict[str, BatchingQueue] = {}
        self._threads: Dict[str, threading.Thread] = {}
        self._lock = threading.Lock()
        self._closed = False
        self._http_server = None
        # if the engine is dropped without shutdown(), closing its
        # queues unparks the (weakly-bound) worker threads so they exit
        # instead of waiting forever on work that can never arrive
        self._finalizer = weakref.finalize(self, _close_queues,
                                           self._queues)

    # -- lifecycle -------------------------------------------------------- #
    def warmup(self, name: Optional[str] = None):
        """Pre-compile every bucket for ``name`` (or all models).  This
        is the SLO line in the sand: compiles that happen here are
        ``serving.warmup_compiles``; any compile after it is a counted
        ``serving.recompiles`` — and on a real TPU, a blown deadline."""
        entries = [self.registry.get(name)] if name is not None \
            else self.registry.entries()
        for entry in entries:
            if entry.input_shape is None:
                raise ValueError(
                    f"warmup({entry.name!r}): register with input_shape= "
                    "so dummy batches can be built")
            from ..observability.goodput import ledger_phase
            with self.recorder.span("serving.warmup"), \
                    ledger_phase(self.recorder, "compile_warmup"):
                for bucket in self.ladder:
                    if bucket not in entry.compiled:
                        self._compile(entry, bucket, entry.input_shape,
                                      warm=True)
            entry.warmed = True
        return self

    def telemetry_sources(self):
        """``[("serving", recorder)]`` — the aggregator attachment hook
        (``aggregator.add(engine, name=...)`` scrapes the ``serving.*``
        request/shed/latency families)."""
        return [("serving", self.recorder)]

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop admissions, then either finish queued work (``drain=True``,
        graceful) or fail it fast with :class:`EngineClosedError`."""
        with self._lock:
            self._closed = True
            queues = dict(self._queues)
            threads = dict(self._threads)
            server, self._http_server = self._http_server, None
        if server is not None:
            server.stop()
        for q in queues.values():
            q.close()
        if not drain:
            for q in queues.values():
                _fail_batch(q.dump(),
                            EngineClosedError("engine shut down before "
                                              "this request ran"),
                            ring=self.trace_ring, span="closed")
        for t in threads.values():
            t.join(timeout)
        return self

    # -- request path ----------------------------------------------------- #
    def submit(self, name: str, x, deadline_ms: Optional[float] = None,
               trace_ctx=None) -> Future:
        """Enqueue one request; returns its Future.

        ``x`` is one sample ``input_shape`` or a batch
        ``(n, *input_shape)`` with ``n <= max_batch``.  ``deadline_ms``
        propagates an SLO: requests still queued past it are shed
        instead of executed.  Raises :class:`LoadShedError` immediately
        when the queue is full (backpressure, not tail collapse).
        ``trace_ctx`` (a
        :class:`~bigdl_tpu.observability.context.TraceContext`) lets an
        upstream hop — the ReplicaSet front door — thread its trace id
        into this request's timeline.
        """
        t_admit = time.monotonic()
        entry = self.registry.get(name)
        x, n, single = self._normalize(entry, x)
        if n > self.ladder.max_batch:
            raise ValueError(
                f"submit: {n} rows > max_batch {self.ladder.max_batch}; "
                "use predict() which splits")
        deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        ring = self.trace_ring
        tr = ring.new_trace(entry.name, ctx=trace_ctx) \
            if ring is not None else None
        req = Request(x, n, deadline=deadline, trace=tr)
        if tr is not None:
            tr.meta["rows"] = n
        # the worker always completes req.future (batched); a single-
        # sample caller gets a view that strips the batch dim back off
        fut = _UnbatchingFuture(req.future) if single else req.future
        rec = self.recorder
        rec.inc("serving.requests")
        q = self._ensure_worker(entry)
        if tr is not None:
            # every trace write BEFORE the put: the batcher may pop the
            # request the instant it lands, and the queue handoff is the
            # only ordering between this thread and the worker
            now = time.monotonic()
            tr.add_span("admit", t_admit, now)
            tr.open("queue", now)   # closed by the batcher at pop
        try:
            q.put(req)
        except LoadShedError:
            rec.inc("serving.shed_queue_full")
            if tr is not None:
                now = time.monotonic()
                tr.discard("queue")   # never entered the queue
                tr.terminal("queue_full", now)
                ring.finish(tr)
            raise
        except EngineClosedError:
            if tr is not None:
                tr.discard("queue")
                tr.terminal("engine_closed", time.monotonic(),
                            name="closed")
                ring.finish(tr)
            raise
        rec.gauge(f"serving.queue_depth.{entry.name}", q.depth())
        return fut

    def predict(self, name: str, x, timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None):
        """Synchronous convenience: splits oversized inputs into
        ``max_batch`` chunks, submits them all (they batch and execute
        concurrently), and reassembles the outputs in order."""
        entry = self.registry.get(name)
        x, n, single = self._normalize(entry, x)
        if single:
            return self.submit(name, x[0], deadline_ms=deadline_ms) \
                       .result(timeout)
        futs = [self.submit(name, x[i:i + self.ladder.max_batch],
                            deadline_ms=deadline_ms)
                for i in range(0, n, self.ladder.max_batch)]
        parts = [f.result(timeout) for f in futs]
        if len(parts) == 1:
            return parts[0]
        return jax.tree_util.tree_map(
            lambda *ps: np.concatenate(ps, axis=0), *parts)

    def pending_rows(self) -> int:
        """Rows queued across this engine's models — the queue-depth
        input to replica health scoring and saturation accounting."""
        with self._lock:
            queues = list(self._queues.values())
        return sum(q.depth() for q in queues)

    def max_queue_fill(self) -> float:
        """Fill fraction of this engine's MOST saturated model queue,
        in [0, 1] — the admission-pressure signal replica saturation
        accounting uses.  The max (not a sum over queues) keeps the
        signal stable when queues are created lazily: a brownout
        spinning up the int8 entry's queue must not dilute — or
        double — the denominator it is controlled by."""
        with self._lock:
            queues = list(self._queues.values())
        if not queues:
            return 0.0
        return max(q.depth() for q in queues) / self.max_queue_rows

    def stats(self) -> Dict[str, Any]:
        """One flat dict of the serving counters plus latency
        percentiles and mean batch fill — what ``serve_bench`` prints."""
        rec = self.recorder
        out = {k: rec.counter_value(f"serving.{k}")
               for k in ("requests", "rows", "batches", "shed_queue_full",
                         "shed_deadline", "recompiles", "warmup_compiles",
                         "errors")}
        lat = rec.hist_summary("serving.latency_ms")
        if lat:
            out.update({"p50_ms": lat.get("p50"), "p95_ms": lat.get("p95"),
                        "p99_ms": lat.get("p99"),
                        "mean_latency_ms": lat.get("mean")})
        fill = rec.hist_summary("serving.batch_fill")
        if fill:
            out["batch_fill"] = fill.get("mean")
        return out

    # -- internals -------------------------------------------------------- #
    def _normalize(self, entry: ModelEntry, x):
        """-> (batched ndarray, n_rows, was_single_sample)."""
        x = np.asarray(x, entry.dtype)
        if entry.input_shape is not None:
            if x.shape == tuple(entry.input_shape):
                return x[None], 1, True
            if x.shape[1:] != tuple(entry.input_shape):
                raise ValueError(
                    f"{entry.name}: expected {entry.input_shape} or "
                    f"(n, *{entry.input_shape}), got {x.shape}")
            return x, x.shape[0], False
        if x.ndim == 0:
            raise ValueError("scalar input needs input_shape= at register")
        return x, x.shape[0], False

    def _ensure_worker(self, entry: ModelEntry) -> BatchingQueue:
        with self._lock:
            if self._closed:
                raise EngineClosedError("engine is shut down")
            q = self._queues.get(entry.name)
            if q is None:
                q = BatchingQueue(max_pending_rows=self.max_queue_rows,
                                  max_delay=self.max_delay)
                # the thread holds the engine only weakly: a dropped,
                # never-shut-down engine must be collectable (the
                # finalizer then closes its queues so workers exit)
                t = threading.Thread(
                    target=_worker_loop,
                    args=(weakref.ref(self), entry.name, q,
                          self.ladder.max_batch),
                    daemon=True, name=f"serving-{entry.name}")
                self._queues[entry.name] = q
                self._threads[entry.name] = t
                t.start()
            return q

    def _run_batch(self, entry: ModelEntry, q: BatchingQueue,
                   batch: List[Request]):
        rec = self.recorder
        ring = self.trace_ring
        now = time.monotonic()
        live = []
        for r in batch:
            tr = r.trace
            if tr is not None:
                tr.close("queue", now)
            if r.expired(now):
                rec.inc("serving.shed_deadline")
                if tr is not None:
                    tr.terminal("deadline", now)
                    ring.finish(tr)
                r.future.set_exception(LoadShedError(
                    "deadline", "expired before execution"))
            else:
                if tr is not None:
                    tr.open("batch_gather", now)
                live.append(r)
        if not live:
            return
        rows = sum(r.n for r in live)
        bucket = self.ladder.bucket_for(rows)
        x = np.concatenate([r.x for r in live], axis=0)
        if bucket > rows:
            x = np.concatenate(
                [x, np.zeros((bucket - rows,) + x.shape[1:], x.dtype)],
                axis=0)
        ex = entry.compiled.get(bucket)
        if ex is None:
            # post-warmup compile: the SLO violation the ladder exists
            # to prevent — counted, never silent
            rec.inc("serving.recompiles")
            from ..observability.goodput import ledger_phase
            with ledger_phase(rec, "compile_warmup"):
                ex = self._compile(entry, bucket, x.shape[1:])
        led = rec.get_ledger()
        if led is not None:
            # flush the inter-batch gap to the background phase so the
            # batch fold below attributes only its own interval
            led.note_step_begin()
        t_exec = time.monotonic()
        for r in live:
            tr = r.trace
            if tr is not None:
                # batch/bucket attribution: which company this request
                # kept, and how much padding it paid for
                tr.meta.update(bucket=bucket, batch_rows=rows,
                               batch_requests=len(live))
                tr.close("batch_gather", t_exec)
                tr.open("compute", t_exec)
        # chaos seam: the per-batch compute fault site.  ``err`` fails
        # the batch (counted serving.errors, requests complete
        # exceptionally — a ReplicaSet fails them over), ``delay``
        # wedges this batcher thread the way a stuck device call would
        # (chunked sleep, so it stays abortable) — the shape the
        # replica watchdog's wedge ejection exists for
        faultplane.inject("serving.compute", rec)
        snap = entry.snapshot          # one atomic read per batch
        with rec.span("serving.execute"):
            y = ex(snap.params, snap.state, jnp.asarray(x))
            y = jax.tree_util.tree_map(np.asarray, y)   # host sync point
        done = time.monotonic()
        off = 0
        for r in live:
            tr = r.trace
            if tr is not None:
                tr.close("compute", done)
                tr.open("reply", done)
            sl = jax.tree_util.tree_map(
                lambda a, o=off, n=r.n: a[o:o + n], y)
            off += r.n
            if tr is not None:
                # finish the trace BEFORE completing the future (same
                # contract as _fail_batch and the shed paths): a client
                # unblocked by .result() that immediately scrapes
                # /trace must see its own request
                tr.close("reply", time.monotonic())
                ring.finish(tr)
            r.future.set_result(sl)
            rec.observe("serving.latency_ms", (done - r.arrival) * 1e3)
        rec.inc("serving.batches")
        rec.inc("serving.rows", rows)
        rec.observe("serving.batch_fill", rows / bucket)
        if led is not None:
            # the batch's interval splits by fill: real rows are
            # goodput, padding rows are capacity idling in the bucket
            led.fold_split({"goodput": rows, "idle": bucket - rows})
        rec.gauge(f"serving.queue_depth.{entry.name}", q.depth())

    def _compile(self, entry: ModelEntry, bucket: int, feature_shape,
                 warm: bool = False):
        """AOT-compile ``entry``'s eval fn at ``(bucket, *feature_shape)``
        and cache the executable.  A trace or compile failure
        propagates: warmup reporting success over a model that does not
        build would make the zero-recompile contract vacuous."""
        model = entry.model

        def fn(params, state, xx):
            y, _ = model.run(params, xx, state=state, training=False)
            return y

        snap = entry.snapshot
        dummy = jnp.asarray(np.zeros((bucket,) + tuple(feature_shape),
                                     entry.dtype))
        with self.recorder.span("serving.compile"):
            ex = jax.jit(fn).lower(snap.params, snap.state,
                                   dummy).compile()
        entry.compiled[bucket] = ex
        self._capture_bucket_cost(entry, bucket, ex)
        if entry.input_shape is None:
            entry.input_shape = tuple(feature_shape)
        if warm:
            self.recorder.inc("serving.warmup_compiles")
        return ex

    def _capture_bucket_cost(self, entry: ModelEntry, bucket: int, ex):
        """Harvest XLA cost/memory analysis from a freshly compiled
        bucket executable into ``entry.cost[bucket]`` plus one ``profile``
        record, so per-bucket compute cost is attributable next to the
        batch-fill metrics.  Best-effort: never raises."""
        from ..observability import profile as _profile
        if not _profile.capture_enabled():
            return
        try:
            cost = _profile.capture_compiled(ex)
        except Exception:
            return
        entry.cost[bucket] = cost
        self.recorder.emit_record("profile", kind="serving_bucket",
                                  model=entry.name, bucket=bucket,
                                  cost=cost)


def _close_queues(queues: Dict[str, BatchingQueue]):
    for q in queues.values():
        q.close()


def _worker_loop(engine_ref, name: str, q: BatchingQueue, max_rows: int):
    """One model's batcher.  Holds the engine weakly (see
    ``_ensure_worker``) and re-resolves the registry entry per batch so
    an ``unregister`` + ``register`` under the same name serves the NEW
    model instead of a stale closure capture."""
    while True:
        batch = q.get_batch(max_rows)
        if batch is None:
            return
        if not batch:
            continue
        eng = engine_ref()
        if eng is None:
            q.close()
            # engine (and its trace ring) already collected: the traces
            # die with it, nothing left to export them from
            _fail_batch(batch, EngineClosedError(
                "engine was garbage-collected before this request ran"))
            return
        try:
            try:
                entry = eng.registry.get(name)
            except KeyError as e:
                _fail_batch(batch, e, ring=eng.trace_ring)
                continue
            try:
                eng._run_batch(entry, q, batch)
            except Exception as e:   # the batcher thread must survive
                eng.recorder.inc("serving.errors")
                _fail_batch(batch, e, ring=eng.trace_ring)
        finally:
            del eng       # never hold the engine across a blocking wait


def _fail_batch(batch: List[Request], exc: BaseException, ring=None,
                span: str = "error"):
    """Complete every still-pending request exceptionally AND finish its
    trace with a terminal cause span — the error path is exactly where
    an operator reads /trace, so it must not go dark there.  Requests
    already completed (e.g. deadline-shed inside a failed _run_batch,
    traces already finished) are skipped via future.done()."""
    for r in batch:
        if r.future.done():
            continue
        tr = r.trace
        if ring is not None and tr is not None:
            # finish the trace BEFORE completing the future: a client
            # that reacts to the exception by scraping /trace must see
            # this request's track
            tr.terminal(type(exc).__name__, time.monotonic(), name=span)
            ring.finish(tr)
        r.future.set_exception(exc)


class _UnbatchingFuture(Future):
    """Future view that strips the batch dim the engine added for a
    single-sample submit, so clients get back the shape they sent."""

    def __init__(self, inner: Future):
        super().__init__()
        inner.add_done_callback(self._propagate)

    def _propagate(self, inner: Future):
        e = inner.exception()
        if e is not None:
            self.set_exception(e)
        else:
            self.set_result(jax.tree_util.tree_map(
                lambda a: a[0], inner.result()))
