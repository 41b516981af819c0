"""What every training step host does around its step, once.

The trainers of ``optim/`` and ``parallel/`` inherit
:class:`TelemetryHost`: attaching a Recorder, the health layer,
the live metrics server, and the envelope of one step record — a call
around the dispatch (:meth:`TelemetryHost._dispatch`) and one after it
(:meth:`TelemetryHost._record_step`).  What differs between hosts is a
class attribute or an overridden method below; nothing here asks who is
calling, and nothing is imported from ``optim/``, ``parallel/`` or
``serving/``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import profile as _profile
from .recorder import Recorder, null_recorder, set_recorder


class TelemetryHost:
    """Telemetry state and services of a host that builds one jitted
    step and dispatches it once per iteration."""

    # the counter one step's items add to
    _items_counter = "records_total"
    # lower the step for its cost at the args' avals, or with the placed
    # arrays themselves where avals would drop their shardings
    _capture_at_avals = True
    # a trainer rebuilt over another mesh keeps the recorder's ledger
    # (continuity across replans is the point) at the new size
    _resize_adopted_ledger = False

    def __init__(self):
        self._recorder = None         # None = zero-cost no-op path
        self._trace_ctx = None        # causal TraceContext, if adopted
        self._tracer = None           # None -> process default
        self._telemetry_health = True
        self._with_health = False     # does the built step return health?
        self._seen_sigs = set()       # batch signatures already dispatched
        self._capture_cost = True
        self._cost_pending = False    # once per step build, at first dispatch
        self._health_monitor = None
        self._flight = None
        self._watchdog = None
        self._http_server = None
        self._max_rollbacks = 2

    # -- what a host overrides ------------------------------------------- #
    def _ledger_devices(self) -> int:
        """Devices this host's goodput ledger accounts for."""
        return jax.local_device_count()

    def _rebuild_step(self):
        """The telemetry now attached wants another step variant than
        the one built: a host that holds a built step re-jits it here,
        keeping the training progress."""

    # -- attachment ------------------------------------------------------ #
    def set_telemetry(self, recorder: Recorder, health: bool = True,
                      capture_cost: bool = True):
        """Attach an observability Recorder: every iteration emits one
        step record (spans: data_fetch / h2d / train_step, compile
        detection; scalars: loss, learning rate, records/sec — tokens
        for the LM trainers — plus grad/param/update norms when
        ``health``, computed on device inside the step; that variant is
        another compiled program, so a trainer whose step is already
        built re-jits it, keeping params and optimizer state).  Also
        installs ``recorder`` as the process-active recorder so
        DeviceLoader and collective accounting report to it, and a
        goodput ledger over this host's devices unless the recorder
        brings one (docs/observability.md, "Goodput & badput taxonomy").

        ``capture_cost`` harvests XLA's cost/memory analysis from the
        jitted step (once per step build, an AOT lowering at the first
        batch) so step records carry ``perf/mfu``, ``perf/hbm_bw_util``
        and ``mem/peak_hbm_bytes`` — or explicit ``*_unavailable``
        markers on backends without the analysis APIs — and refreshes
        live ``mem/device.*`` gauges on every record/scrape.
        ``capture_cost=False`` and ``BIGDL_PROFILE_CAPTURE=0`` disable
        both, keeping attribution entirely off the hot path."""
        self._recorder = recorder
        self._telemetry_health = bool(health)
        self._capture_cost = bool(capture_cost)
        if self._capture_cost and _profile.capture_enabled():
            _profile.install_device_memory_poller(recorder)
        if recorder.enabled:
            ledger = recorder.get_ledger()
            if ledger is None:
                from .goodput import GoodputLedger
                recorder.set_ledger(GoodputLedger(
                    name="train", devices=self._ledger_devices()))
            elif self._resize_adopted_ledger:
                ledger.set_devices(self._ledger_devices())
        set_recorder(recorder)
        if self._with_health != self._telemetry_active():
            self._rebuild_step()
        return self

    def set_trace_context(self, ctx, tracer=None):
        """Adopt a causal :class:`~bigdl_tpu.observability.context.
        TraceContext` (e.g. the elastic supervisor's run trace): every
        checkpoint save carries a child of it to the async writer thread
        (queue-wait + write spans under the run's trace id), and a
        trainer with a ``step()`` records a ``train.step`` span under
        it.  ``ctx=None`` detaches.  ``tracer`` overrides the process
        default span store."""
        self._trace_ctx = ctx
        if tracer is not None:
            self._tracer = tracer
        return self

    def _trace_spine(self):
        from . import tracing
        return self._tracer if self._tracer is not None \
            else tracing.get_tracer()

    def set_health(self, policy: str = "warn", flight_dir=None,
                   max_rollbacks: int = 2, stall_factor=None,
                   install_crash_hooks: bool = True, **monitor_kw):
        """Enable numeric-health sentinels over every step record:
        NaN/Inf in loss or gradients, loss-spike (EWMA z-score), and
        gradient-norm explosion — the device checks ride the step's
        existing ``health_scalars`` output, so nothing extra syncs the
        host.  ``policy`` is ``"warn"`` / ``"record"`` / ``"raise"``
        (:class:`~bigdl_tpu.observability.DivergenceError`) /
        ``"rollback"`` (restore the last committed checkpoint — needs
        ``set_checkpoint`` — at most ``max_rollbacks`` times).

        ``flight_dir`` arms the crash flight recorder: the Recorder's
        recent-record ring is dumped atomically to ``flight_<ts>.json``
        there on divergence, unhandled exception, or SIGTERM
        (``install_crash_hooks`` chains excepthook/SIGTERM without
        displacing the PR-3 preemption handler).  ``stall_factor``
        additionally starts a :class:`StallWatchdog` with that p99
        multiplier.  Extra kwargs reach
        :class:`~bigdl_tpu.observability.HealthMonitor`."""
        from .health import FlightRecorder, HealthMonitor, StallWatchdog
        rec = self._own_recorder()
        if flight_dir is not None:
            if self._flight is not None:     # reconfigure: one hook chain
                self._flight.uninstall()
            self._flight = FlightRecorder(rec, flight_dir)
            if install_crash_hooks:
                self._flight.install()
        self._health_monitor = HealthMonitor(
            policy=policy, recorder=rec, flight=self._flight, **monitor_kw)
        self._max_rollbacks = int(max_rollbacks)
        if stall_factor:
            if self._watchdog is not None:   # re-budget: one thread only
                self._watchdog.stop()
            self._watchdog = StallWatchdog(rec,
                                           factor=float(stall_factor)).start()
        if self._http_server is not None:   # set_health after serve_metrics
            self._http_server.monitor = self._health_monitor
            self._http_server.watchdog = self._watchdog \
                or self._http_server.watchdog
        return self

    def telemetry_sources(self):
        """``[("trainer", recorder)]`` — the fleet aggregator's
        attachment hook (``aggregator.add(opt, name="train")``); a
        recorder is created on demand like ``serve_metrics`` does."""
        return [("trainer", self._own_recorder())]

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1",
                      watchdog: bool = True):
        """Start the live introspection HTTP server for this trainer's
        recorder — ``/metrics`` (Prometheus), ``/healthz``, ``/records``
        — on a daemon thread.  ``port=0`` binds an ephemeral port (read
        it back from the returned server's ``.port``).  ``watchdog``
        starts a stall watchdog so ``/healthz`` flips unhealthy when
        the step loop wedges.  Returns the
        :class:`~bigdl_tpu.observability.IntrospectionServer` (call
        ``.stop()`` to shut it down)."""
        from .health import StallWatchdog
        from .http import IntrospectionServer
        rec = self._own_recorder()
        if watchdog and self._watchdog is None:
            self._watchdog = StallWatchdog(rec).start()
        if self._http_server is not None:   # reconfigure: no leaked
            self._http_server.stop()        # thread/socket on the old port
        self._http_server = IntrospectionServer(
            rec, port=port, host=host, watchdog=self._watchdog,
            monitor=self._health_monitor).start()
        return self._http_server

    def _own_recorder(self) -> Recorder:
        """The attached recorder, created on demand."""
        if self._recorder is None:
            self.set_telemetry(Recorder())
        return self._recorder

    def _rec(self) -> Recorder:
        return self._recorder if self._recorder is not None \
            else null_recorder()

    def _telemetry_active(self) -> bool:
        """Should the step being built compute health scalars?  A
        disabled recorder must compile the plain step — the no-op
        guarantee covers device work too."""
        return (self._recorder is not None and self._recorder.enabled
                and self._telemetry_health)

    # -- the step record's envelope -------------------------------------- #
    def _begin_step_build(self) -> bool:
        """A step program is about to be built: its first dispatches
        compile, and its cost is captured at the first of them.  Returns
        whether it is to return health scalars."""
        self._with_health = self._telemetry_active()
        self._seen_sigs.clear()
        self._cost_pending = True
        return self._with_health

    def _accounted(self, step):
        """Wrap the Python function of a step that accounts its
        collectives at trace time (``collectives.account_collective``,
        accumulate semantics), before it is jitted: every TRACE of it
        starts the per-step gauges over.  However many traces run — the
        cost capture's lowering, then the dispatch's own unless jit
        shares the first, a ragged batch, inputs whose sharding type
        changed — a record holds what ONE trace accounts, and a dispatch
        served from an earlier trace keeps that trace's numbers.  Traced
        code is not touched: the reset runs on the host, while tracing."""
        @functools.wraps(step)
        def traced(*args):
            rec = self._rec()
            rec.reset_gauges("collective/")
            rec.reset_gauges("comm/group.")
            return step(*args)
        return traced

    def _first_dispatch_of(self, batch) -> bool:
        sig = tuple((tuple(jnp.shape(l)), str(getattr(l, "dtype", "?")))
                    for l in jax.tree_util.tree_leaves(batch))
        seen = sig in self._seen_sigs
        self._seen_sigs.add(sig)
        return not seen

    def _capture_step_cost(self, step_fn, args):
        """Harvest XLA cost/memory analysis for the jitted step at these
        args (an AOT lowering: no buffer is read or donated) and attach
        the StepCostModel behind ``perf/mfu`` / ``perf/hbm_bw_util`` /
        ``mem/peak_hbm_bytes``.  Never raises; the ``profile.capture``
        span measures the one analysis pass."""
        if self._capture_cost and _profile.capture_enabled():
            _profile.capture_and_attach(
                self._rec(), step_fn, args, kind="train_step",
                avals=self._capture_at_avals)

    def _dispatch(self, step_fn, args, batch):
        """``step_fn(*args)`` under its span; returns ``(the step's own
        results, its health scalars or None)``.  A signature of the
        placed ``batch`` never dispatched before means XLA compiles
        inside the call: the span is ``train_step_compile`` (so
        trace_summary can split compile from execute) and the record
        counts a ``recompile``."""
        rec = self._rec()
        span = "train_step"
        if rec.enabled and self._first_dispatch_of(batch):
            span = "train_step_compile"
            rec.scalar("recompile", 1.0)
            if self._cost_pending:
                # once per step build, at the first (full-batch)
                # signature: a ragged last batch would under-report every
                # following full step
                self._cost_pending = False
                self._capture_step_cost(step_fn, args)
        with rec.span(span):
            out = step_fn(*args)
        return (out[:-1], out[-1]) if self._with_health else (out, None)

    def _record_step(self, step, n_items, loss, health, extra=None):
        """Fold the dispatched iteration into step record ``step`` and
        hand it to the health sentinels.  ``extra`` holds the host's own
        scalars by name.  The record floats ``loss``, which waits for
        the step."""
        rec = self._rec()
        if not rec.enabled:
            return
        for name in ("collective/bytes", "collective/wire_bytes"):
            per_step = rec.gauge_value(name + "_per_step")
            if per_step:
                rec.inc(name + "_total", per_step)
        rec.inc(self._items_counter, n_items)
        rec.scalar("records", n_items)     # records/sec == items/sec
        rec.scalar("loss", loss)
        for name, value in {**(extra or {}), **(health or {})}.items():
            rec.scalar(name, value)
        record = rec.end_step(step)
        if self._health_monitor is not None and record is not None:
            # sentinel checks over the floats end_step already produced;
            # raise/rollback policies surface DivergenceError from here
            self._health_monitor.check_record(record)
