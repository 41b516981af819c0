"""Causal trace spine (ISSUE 19): one trace context across train,
serve, fleet, and autoscale.

Unit coverage: TraceContext immutability + traceparent roundtrip, the
bounded SpanStore, critical-path attribution arithmetic, the merged
Perfetto export and its inverse (``spans_from_chrome``), the one-clock
contract (Recorder spans stamp on ``trace_now``), and the pool→elastic
actuation registry.

Race coverage: cross-thread context propagation under the runtime
racecheck harness (CheckedLock + guard_fields) on the two handoff
paths the tentpole threads — the async checkpoint writer's
Condition/deque and the serving batcher queue.

Acceptance (the two ISSUE-19 criteria):

  * an admission → failover → decode request exports as a SINGLE
    connected Perfetto trace (one trace id across the replica-set
    tracer and multiple engine rings' process rows) with ≥95% of its
    end-to-end latency attributed to named spans;
  * a SIGTERM-shrink run (step → drain → replan → resume) exports as
    one trace, with the autoscale decision that took the trainer's
    device linked BACK to its triggering SLO/occupancy samples and
    FORWARD (caused_by) from the supervisor's transition events.
"""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.analysis.racecheck import (CheckedLock, RaceCheck,
                                          guard_fields, wrap_lock)
from bigdl_tpu.autoscale import AutoscaleController, AutoscalePolicy
from bigdl_tpu.checkpoint.writer import AsyncCheckpointWriter
from bigdl_tpu.elastic import ElasticSupervisor
from bigdl_tpu.fleet import DevicePool
from bigdl_tpu.observability import (InMemorySink, Recorder, SeriesStore,
                                     SLObjective, SLOEngine, SpanStore,
                                     TraceContext, Tracer, critical_path,
                                     merge_perfetto, note_actuation,
                                     set_tracer, spans_from_chrome,
                                     take_actuation, trace_now)
from bigdl_tpu.observability import context as trace_clock_mod
from bigdl_tpu.observability import tracing as trace_spine
from bigdl_tpu.serving import (ModelRegistry, ServingEngine,
                               build_replica_set)


# --------------------------------------------------------------------- #
# context                                                                #
# --------------------------------------------------------------------- #
def test_context_roundtrip_child_and_immutability():
    root = TraceContext.new_root()
    assert len(root.trace_id) == 32 and len(root.span_id) == 16
    assert root.parent_span_id is None
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_span_id == root.span_id
    assert child.span_id != root.span_id
    # W3C traceparent wire roundtrip
    back = TraceContext.from_traceparent(child.to_traceparent())
    assert back.trace_id == child.trace_id
    assert back.span_id == child.span_id
    # the wire format doesn't carry the grandparent hop — by design
    assert back.parent_span_id is None
    again = TraceContext.from_traceparent(child.to_traceparent())
    assert back == again and hash(back) == hash(again)
    # immutable: a context crossing threads can never be half-updated
    with pytest.raises(AttributeError):
        root.trace_id = "f" * 32


def test_span_store_bounded_with_dropped_counter():
    store = SpanStore(capacity=4)
    ctxs = [TraceContext.new_root() for _ in range(6)]
    for i, c in enumerate(ctxs):
        store.add(trace_spine.Span(f"s{i}", c, 0.0, 1.0))
    assert len(store) == 4
    assert store.dropped == 2
    # the survivors are the newest four, queryable by trace
    assert store.by_trace(ctxs[0].trace_id) == []
    assert len(store.by_trace(ctxs[5].trace_id)) == 1
    assert len(store.trace_ids()) == 4


def test_actuation_registry_pop_semantics():
    ctx = TraceContext.new_root()
    note_actuation("jobA", ctx)
    note_actuation("jobA", None)        # None never overwrites
    got = take_actuation("jobA")
    assert got is not None and got.trace_id == ctx.trace_id
    assert take_actuation("jobA") is None       # popped, not peeked


# --------------------------------------------------------------------- #
# critical path                                                          #
# --------------------------------------------------------------------- #
def test_critical_path_innermost_and_untraced():
    # nested: the inner span steals its window from the outer
    cp = critical_path([("outer", 0.0, 10.0), ("inner", 2.0, 5.0)])
    assert cp["total"] == 10.0
    assert cp["attribution"] == {"outer": 7.0, "inner": 3.0}
    assert cp["coverage"] == 1.0
    # a gap between spans charges to (untraced) and dents coverage
    cp = critical_path([("a", 0.0, 4.0), ("b", 6.0, 10.0)])
    assert cp["attribution"]["(untraced)"] == 2.0
    assert abs(cp["coverage"] - 0.8) < 1e-12
    assert critical_path([]) == {"total": 0.0, "attribution": {},
                                 "coverage": 1.0}


def test_merge_perfetto_roundtrips_through_spans_from_chrome():
    t = Tracer()
    ctx = TraceContext.new_root()
    with t.span("outer", ctx, subsystem="x") as sp:
        inner = t.begin("inner", sp.context, subsystem="x")
        inner.end()
    doc = json.loads(merge_perfetto([("one", t)]))
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"one"}
    per_trace = spans_from_chrome(doc)
    assert set(per_trace) == {ctx.trace_id}
    got = sorted(n for n, _, _ in per_trace[ctx.trace_id])
    assert got == ["inner", "outer"]
    cp = critical_path(per_trace[ctx.trace_id])
    # real clock readings: the attribution sums in floating point
    assert cp["coverage"] == pytest.approx(1.0)


def test_http_trace_filter_keeps_one_trace():
    from bigdl_tpu.observability.http import _filter_trace
    t = Tracer()
    a, b = TraceContext.new_root(), TraceContext.new_root()
    t.begin("keep", a, child=False).end()
    t.begin("drop", b, child=False).end()
    doc = _filter_trace(merge_perfetto([("s", t)]), a.trace_id)
    begins = [e for e in doc["traceEvents"] if e["ph"] == "B"]
    ends = [e for e in doc["traceEvents"] if e["ph"] == "E"]
    assert [e["args"]["trace_id"] for e in begins] == [a.trace_id]
    assert len(ends) == len(begins)     # paired: no orphan E rows
    # non-chrome bodies pass through untouched (never a 500)
    assert _filter_trace("not json{", "x") == "not json{"


def test_aggregator_trace_doc_merges_sources():
    from bigdl_tpu.observability import MetricsAggregator
    agg = MetricsAggregator()
    t = Tracer()
    ctx = TraceContext.new_root()
    t.begin("s", ctx, child=False).end()
    agg.add_trace_source("spine", t)
    doc = json.loads(agg.trace_doc())
    assert any(e.get("args", {}).get("trace_id") == ctx.trace_id
               for e in doc["traceEvents"] if e["ph"] == "B")


# --------------------------------------------------------------------- #
# one clock domain                                                       #
# --------------------------------------------------------------------- #
def test_recorder_spans_stamp_on_trace_clock(monkeypatch):
    """The Recorder's step spans and the trace spine must share ONE
    clock (trace_now), or merged timelines skew: patch the clock and
    watch the Recorder read it."""
    fake = [100.0]
    monkeypatch.setattr(trace_clock_mod, "trace_now", lambda: fake[0])
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    rec.start_step(0)
    with rec.span("work"):
        fake[0] = 100.25
    fake[0] = 100.5
    rec.end_step()
    step = [r for r in rec.recent_records() if r.get("type") == "step"][-1]
    assert abs(step["dur"] - 0.5) < 1e-9
    assert abs(step["spans"]["work"] - 0.25) < 1e-9


# --------------------------------------------------------------------- #
# Recorder spans land in the SpanStore                                   #
# --------------------------------------------------------------------- #
@pytest.fixture
def span_store():
    tracer = Tracer(capacity=64)
    prev = set_tracer(tracer)
    yield tracer.store
    set_tracer(prev)


def test_recorder_span_leaves_a_span_with_parent_and_the_sum(span_store):
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    rec.start_step(0)
    with rec.span("outer", annotate=False) as outer:
        with rec.span("work", bucket=8):
            time.sleep(0.002)
        with rec.span("work"):
            time.sleep(0.001)
        outer.set(step=7)
    step = rec.end_step()
    by_name = {}
    for s in span_store.spans():
        assert s.t0 <= s.t1
        by_name.setdefault(s.name, []).append(s)
    (parent,) = by_name["outer"]
    first, second = by_name["work"]
    assert parent.context.parent_span_id is None
    assert parent.args == {"step": 7}
    assert first.args == {"bucket": 8} and second.args is None
    for child in (first, second):
        assert child.context.parent_span_id == parent.context.span_id
        assert child.trace_id == parent.trace_id
        assert parent.t0 <= child.t0 and child.t1 <= parent.t1
    assert first.t1 <= second.t0
    # the step record's sum is the sum of the stored intervals
    assert step["spans"]["work"] == pytest.approx(
        first.duration() + second.duration(), abs=1e-12)
    assert step["span_counts"]["work"] == 2
    assert step["spans"]["outer"] == pytest.approx(parent.duration(),
                                                   abs=1e-12)


def test_recorder_add_span_ends_at_the_call_under_a_given_trace(span_store):
    rec = Recorder(annotate=False)
    with rec.span("admit"):
        before = trace_now()
        rec.add_span("queue", 0.25, trace_id="ab" * 16)
        after = trace_now()
    queue, admit = span_store.spans()
    assert (queue.name, admit.name) == ("queue", "admit")
    assert queue.trace_id == "ab" * 16 != admit.trace_id
    assert queue.context.parent_span_id == admit.context.span_id
    assert before <= queue.t1 <= after
    assert queue.duration() == pytest.approx(0.25)
    assert rec.span_value("queue") == pytest.approx(0.25)
    # a span of another thread has no parent here
    with rec.span("admit"):
        t = threading.Thread(target=lambda: rec.add_span("other", 0.0))
        t.start()
        t.join(5.0)
        assert not t.is_alive()
    other = [s for s in span_store.spans() if s.name == "other"]
    assert other[0].context.parent_span_id is None


def test_recorder_span_ids_are_counted_not_drawn(span_store, monkeypatch):
    import uuid
    monkeypatch.setattr(uuid, "uuid4", lambda: pytest.fail("uuid4 called"))
    rec = Recorder(annotate=False)
    for _ in range(3):
        with rec.span("a"):
            rec.add_span("b", 0.0)
    ids = [s.context.span_id for s in span_store.spans()]
    assert len(set(ids)) == 6 and all(len(i) == 16 for i in ids)
    assert len({s.trace_id for s in span_store.spans()}) == 1


def test_recorder_discarded_and_disabled_spans_leave_nothing(span_store):
    rec = Recorder(annotate=False)
    with rec.span("tick") as tick:
        with rec.span("leaf"):
            pass
        tick.discard()
    assert [s.name for s in span_store.spans()] == ["leaf"]
    assert rec.span_value("tick") == 0.0
    off = Recorder(enabled=False)
    with off.span("tick") as tick:        # the shared null span
        tick.set(step=1)
        tick.discard()
    off.add_span("queue", 1.0)
    assert len(span_store) == 1 and off.span_value("queue") == 0.0


def test_full_store_counts_what_recorder_spans_push_out(span_store):
    rec = Recorder(annotate=False)
    for _ in range(70):
        with rec.span("s"):
            pass
    assert len(span_store) == 64 and span_store.dropped == 6


def test_trace_now_is_monotonic_clock():
    # the documented contract: TRACE_CLOCK is time.monotonic — the
    # serving queue's native clock, so engine trace stamps match free
    assert trace_clock_mod.TRACE_CLOCK is time.monotonic
    a, b = trace_now(), trace_now()
    assert b >= a


# --------------------------------------------------------------------- #
# racecheck: cross-thread propagation                                    #
# --------------------------------------------------------------------- #
class _Job:
    """Checkpoint job carrying a trace context across the writer's
    Condition/deque handoff (the real CheckpointManager attaches the
    same attributes to its closure)."""

    def __init__(self, done):
        self.done = done

    def __call__(self):
        time.sleep(0.002)
        self.done.append(trace_now())


def test_checkpoint_writer_trace_handoff_racecheck():
    rc = RaceCheck()
    tracer = Tracer()
    prev = set_tracer(tracer)
    writer = AsyncCheckpointWriter(max_pending=1)
    # instrumented condition lock: every submit/pop handoff is checked
    writer._cv = threading.Condition(CheckedLock("ckpt.cv", rc))
    wrap_lock(tracer.store, "_lock", rc)
    try:
        ctxs = []

        def submitter():
            for _ in range(4):
                job = _Job([])
                job.trace_ctx = TraceContext.new_root()
                ctxs.append(job.trace_ctx)
                writer.submit(job)

        threads = [threading.Thread(target=submitter) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert writer.wait(timeout=30.0)
        rc.assert_clean()
        # every submitted context produced its queue+write spans on the
        # WRITER thread, under the SUBMITTER's trace id, in clock order
        for ctx in ctxs:
            spans = {s.name: s for s in
                     tracer.store.by_trace(ctx.trace_id)}
            assert set(spans) == {"ckpt.queue", "ckpt.write"}
            q, w = spans["ckpt.queue"], spans["ckpt.write"]
            assert q.t0 <= q.t1 <= w.t0 <= w.t1
            assert q.context.parent_span_id == ctx.span_id
    finally:
        set_tracer(prev)
        writer.close(timeout=10.0)


def make_model():
    m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m.evaluate()
    m.ensure_initialized()
    return m


def make_engine(model):
    reg = ModelRegistry()
    reg.register("m", model, input_shape=(4,))
    return ServingEngine(reg, max_batch=4, max_delay_ms=1.0,
                         max_queue_rows=64,
                         recorder=Recorder(annotate=False))


def test_batcher_trace_handoff_racecheck():
    """Submitter threads open request traces; the batcher thread closes
    them — the adopted upstream contexts must survive the queue handoff
    with no bare writes or lock inversions on the ring."""
    rc = RaceCheck()
    model = make_model()
    eng = make_engine(model)
    wrap_lock(eng.trace_ring, "_lock", rc)
    guard_fields(eng.trace_ring, "_lock", ["dropped"], rc)
    try:
        eng.warmup()
        ctxs, stop = [], threading.Event()

        def submitter():
            for _ in range(8):
                ctx = TraceContext.new_root()
                ctxs.append(ctx)
                eng.submit("m", np.ones((1, 4), np.float32),
                           trace_ctx=ctx.child()).result(30)

        def scraper():
            while not stop.is_set():
                eng.trace_ring.traces()
                time.sleep(0.001)

        reader = threading.Thread(target=scraper)
        reader.start()
        threads = [threading.Thread(target=submitter) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        reader.join()
        rc.assert_clean()
        ring_ids = {tr.trace_id for tr in eng.trace_ring.traces()}
        assert {c.trace_id for c in ctxs} <= ring_ids
    finally:
        eng.shutdown()


# --------------------------------------------------------------------- #
# acceptance 1: admission -> failover -> decode, one connected trace     #
# --------------------------------------------------------------------- #
def make_rs(n=2, **kw):
    kw.setdefault("engine_kw", dict(max_batch=4, max_delay_ms=1.0,
                                    max_queue_rows=16))
    kw.setdefault("health_interval", 0.05)
    kw.setdefault("probe_interval", 0.05)
    model = make_model()
    rs = build_replica_set(model, n, name="m", input_shape=(4,), **kw)
    rs.warmup()
    return model, rs


def test_admission_failover_decode_single_connected_trace():
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.serving import DecodeEngine
    model, rs = make_rs(2, eject_min_requests=100)
    tracer = Tracer()
    rs.tracer = tracer
    # decode engine built (and jitted) BEFORE the traced request so the
    # serve -> decode hop is immediate, like a real pipeline
    lm = T.build("tiny", dropout=0.0, n_layers=1, max_len=32)
    lm.ensure_initialized()
    reg = ModelRegistry()
    reg.register("lm", lm)
    decode = DecodeEngine(reg, "lm", slots=2, page_size=8,
                          max_context=32, max_prompt=8,
                          max_new_tokens=4).warmup()
    try:
        rs.start()
        bad = rs.replicas[0].engine

        def broken(entry, q, batch):
            raise RuntimeError("replica 0 exploded")

        bad._run_batch = broken
        # the first request answers via failover to the survivor
        y = rs.predict("m", np.ones((1, 4), np.float32), timeout=30)
        assert np.shape(y) == (1, 2)
        assert rs.recorder.counter_value("replica/failovers") >= 1

        # the trace that took the failover hop: rs.admit root + failover
        failovers = [s for s in tracer.store.spans()
                     if s.name == "rs.failover"]
        assert failovers, "no failover event recorded on the tracer"
        trace_id = failovers[0].trace_id
        admits = [s for s in tracer.store.by_trace(trace_id)
                  if s.name == "rs.admit"]
        assert len(admits) == 1
        assert admits[0].context.parent_span_id is None     # the root

        # decode leg: the same trace id flows into a DecodeEngine's
        # slot-lifetime trace via ctx adoption
        hop_ctx = admits[0].context.child()
        out = decode.submit("lm", np.array([1, 2, 3], np.int32),
                            trace_ctx=hop_ctx).result(60)
        t_hop_end = trace_now()
        assert len(out) > 3
        # the orchestrator's handoff span: reply -> decode completion
        # (inner decode-ring spans subtract from it, innermost-wins)
        tracer.record(trace_spine.Span(
            "pipeline.handoff", hop_ctx, admits[0].t1, t_hop_end,
            subsystem="serve"))
        # wait for the decode ring to finish stamping the slot trace
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            done = [tr for tr in decode.trace_ring.traces()
                    if tr.trace_id == trace_id and tr.spans]
            if done:
                break
            time.sleep(0.01)

        # merged export: one document, per-source process rows
        sources = [("replicaset", tracer)]
        for i, rep in enumerate(rs.replicas):
            sources.append((f"replica{i}", rep.engine.trace_ring))
        sources.append(("decode", decode.trace_ring))
        doc = json.loads(merge_perfetto(sources))
        pids = {e["pid"] for e in doc["traceEvents"]
                if e["ph"] == "B"
                and e["args"].get("trace_id") == trace_id}
        # the ONE trace id spans the replica-set row, at least one
        # engine ring row, and the decode ring row
        assert len(pids) >= 3, pids

        # every admitted request's trace is complete: a terminal span
        # (reply / shed / error / deadline) closes each ring timeline
        for rep in rs.replicas:
            for tr in rep.engine.trace_ring.traces():
                names = {n for n, _, _, _ in tr.spans}
                assert names & {"reply", "shed", "error", "closed",
                                "deadline"}, names

        # critical path: >=95% of the end-to-end window is named
        per_trace = spans_from_chrome(doc)
        cp = critical_path(per_trace[trace_id])
        assert cp["total"] > 0.0
        assert cp["coverage"] >= 0.95, cp
    finally:
        if decode is not None:
            decode.shutdown()
        rs.shutdown(drain=True)


# --------------------------------------------------------------------- #
# acceptance 2: SIGTERM shrink — step -> drain -> replan -> resume,      #
# autoscale decision linked to its triggering sample                     #
# --------------------------------------------------------------------- #
class _StubTrainer:
    """Millisecond-scale stand-in exposing exactly the seams the
    supervisor drives (telemetry, checkpoint wiring, trace context,
    step/save/load/detach) so the SIGTERM acceptance runs fast.  Steps
    and async checkpoint writes record under the supervisor's trace."""

    def __init__(self, writer):
        self._writer = writer
        self._recorder = None
        self._ckpt_mgr = None
        self._step_count = 0
        self._trace_ctx = None
        self._dir = None

    def set_telemetry(self, rec, **kw):
        self._recorder = rec
        return self

    def set_checkpoint(self, path, **kw):
        self._dir = str(path)
        return self

    def set_trace_context(self, ctx, tracer=None):
        self._trace_ctx = ctx
        return self

    def init(self):
        return self

    def load_checkpoint(self, path):
        state = os.path.join(str(path), "state.json")
        if not os.path.exists(state):
            raise FileNotFoundError(state)
        with open(state) as f:
            self._step_count = json.load(f)["step"]

    def step(self, tokens, targets):
        span = None
        if self._trace_ctx is not None:
            span = trace_spine.get_tracer().begin(
                "train.step", self._trace_ctx, subsystem="train")
        time.sleep(0.001)
        self._step_count += 1
        if span is not None:
            span.end(step=self._step_count - 1)
        return 1.0

    def save_checkpoint(self, path, sync=False, tag=None):
        state = os.path.join(str(path), "state.json")
        step = self._step_count

        class _Write:
            def __call__(self):
                os.makedirs(os.path.dirname(state), exist_ok=True)
                tmp = state + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"step": step}, f)
                os.replace(tmp, state)

        job = _Write()
        if self._trace_ctx is not None:
            job.trace_ctx = self._trace_ctx.child()
        self._writer.submit(job)
        if sync:
            assert self._writer.wait(timeout=30.0)

    def detach(self):
        self._writer.wait(timeout=30.0)


def test_sigterm_shrink_exports_one_connected_trace(tmp_path):
    tracer = Tracer()
    prev = set_tracer(tracer)
    writer = AsyncCheckpointWriter(max_pending=2)
    model, rs = make_rs(1, recorder=Recorder(sinks=[InMemorySink()],
                                             annotate=False))
    pool = DevicePool(devices=[f"d{i}" for i in range(8)])
    pool.claim("train", 8)              # the trainer owns everything
    clk = [0.0]
    store = SeriesStore(clock=lambda: clk[0])
    slo = SLOEngine(store, [SLObjective(
        "ttft", target=0.9, window=60.0, series=("*ttft*",),
        threshold=100.0, burn_alert=2.0)], clock=lambda: clk[0])
    ctl = AutoscaleController(
        rs, lambda: make_engine(model), pool=pool, claimant="serve",
        donor="train", donor_take="head", slo_engine=slo, store=store,
        policy=AutoscalePolicy(idle_ticks=2, cooldown_up=5.0,
                               cooldown_down=20.0, max_step=1))
    sup = ElasticSupervisor(
        lambda mesh: _StubTrainer(writer), str(tmp_path / "ck"),
        {"dp": 8},
        capacity_fn=lambda: len(pool.owned_by("train")),
        recorder=Recorder(sinks=[InMemorySink()], annotate=False),
        ckpt_every=2, replan_every=100, handle_sigterm=True,
        name="train")
    fired = {"done": False}

    def batch(s):
        if s == 3 and not fired["done"]:
            fired["done"] = True
            # SLO burn + saturated occupancy: the autoscaler borrows
            # one of the trainer's devices, then the scheduler SIGTERMs
            # the trainer — the shrink that follows must link back to
            # the decision, and the decision back to its samples
            store.observe("decode/ttft_ms/p99", 500.0)
            store.observe("decode/occupancy", 0.95)
            d = ctl.tick(now=0.0)
            assert d.direction == "up", d
            os.kill(os.getpid(), signal.SIGTERM)
        return np.zeros(1), np.zeros(1)

    try:
        rs.start()
        losses = sup.run(batch, steps=8)
        assert len(losses) == 8

        run_id = sup.trace_ctx.trace_id
        run_spans = tracer.store.by_trace(run_id)
        names = {s.name for s in run_spans}
        # step -> drain -> replan(planning) -> resume, one trace id
        assert {"elastic.planning", "elastic.resuming",
                "elastic.running", "elastic.draining",
                "train.step", "elastic.preemption", "elastic.shrink",
                "elastic.resume", "ckpt.queue",
                "ckpt.write"} <= names, names

        # the decision trace: autoscale.up root + the samples that
        # triggered it as child events (the backward evidence edge)
        ups = [s for s in tracer.store.spans()
               if s.name == "autoscale.up"]
        assert len(ups) == 1
        decision_id = ups[0].trace_id
        samples = [s for s in tracer.store.by_trace(decision_id)
                   if s.name == "slo.sample"]
        kinds = {s.args["kind"] for s in samples}
        assert "slo" in kinds and "occupancy" in kinds, kinds
        # forward edge: the pool move recorded under the decision trace
        moves = [s for s in tracer.store.by_trace(decision_id)
                 if s.name == "pool.transfer"]
        assert moves and moves[0].args["owners"] == ["train", "serve"]

        # the supervisor's transition links caused_by -> the decision
        links = [l for s in run_spans for l in s.links]
        assert (decision_id, ups[0].context.span_id,
                "caused_by") in links, links

        # the actuation also landed in the autoscale_event record
        recs = rs.recorder.recent_records(rec_type="autoscale_event")
        assert any(r.get("trace_id") == decision_id for r in recs)

        # single connected Perfetto export; >=95% of the run window
        # attributed to named spans (contiguous state spans = no gaps)
        doc = json.loads(merge_perfetto([("train", tracer)]))
        per_trace = spans_from_chrome(doc)
        cp = critical_path(per_trace[run_id])
        assert cp["total"] > 0.0
        assert cp["coverage"] >= 0.95, cp
        assert "(untraced)" not in cp["attribution"] \
            or cp["attribution"]["(untraced)"] / cp["total"] <= 0.05
    finally:
        set_tracer(prev)
        rs.shutdown(drain=True)
        writer.close(timeout=10.0)
