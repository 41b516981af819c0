"""Fleet layer: multi-job survival on one shared device pool.

Fast tests cover the fair-share planner, the device-ownership ledger,
admission control (floors win over arrivals), the fleet fault sites,
the SIGTERM fan-out regression, per-job retry attribution, the
aggregated /metrics + /healthz, and the trace_summary fleet renderer.

The SpmdTrainer contention matrix is marked slow like every SpmdTrainer
test; CI runs it (plus the two-job chaos subprocess matrix proving
bit-identical survival) in the dedicated fleet-chaos-smoke job.

Bit-exactness taxonomy under contention (same rules as
docs/checkpointing.md): displacement and same-mesh resume are
bit-identical (asserted in scripts/fleet_chaos_smoke.py); a
shrink/regrow changes partition counts and drifts at the last ulp —
asserted tight-allclose here, never hidden behind loose tolerances.
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import bigdl_tpu.faults as faults
from bigdl_tpu.checkpoint import PreemptionHandler
from bigdl_tpu.elastic import ElasticSupervisor
from bigdl_tpu.fleet import (DevicePool, FleetAdmissionError,
                             FleetScheduler, PoolExhaustedError,
                             enable_shared_compile_cache, min_plan,
                             plan_fleet)
from bigdl_tpu.observability import (InMemorySink, IntrospectionServer,
                                     Recorder, render_prometheus,
                                     render_prometheus_multi)

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_trace_summary():
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(_SCRIPTS, "trace_summary.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)
    return ts


# --------------------------------------------------------------------- #
# fair-share planning                                                    #
# --------------------------------------------------------------------- #
def test_plan_fleet_fair_split_within_tier():
    # two equal jobs on 8 devices: even split, both shrink the same way
    assert plan_fleet(8, [("a", {"dp": 8}, None, 0),
                          ("b", {"dp": 8}, None, 0)]) == \
        {"a": {"dp": 4}, "b": {"dp": 4}}
    # three jobs, divisor rounding: everyone floored, leftovers flow
    # to the earliest-admitted
    plans = plan_fleet(8, [("a", {"dp": 4}, None, 0),
                           ("b", {"dp": 4}, None, 0),
                           ("c", {"dp": 4}, None, 0)])
    # even shares of 2 each; the rounding slack grows the EARLIEST
    # admitted job, not whoever happened to plan last
    assert plans == {"a": {"dp": 4}, "b": {"dp": 2}, "c": {"dp": 2}}


def test_plan_fleet_priority_beats_admit_order():
    # the later, higher-priority job plans first and gets the larger
    # share; the standing low-priority job shrinks but keeps its floor
    plans = plan_fleet(8, [("old", {"dp": 8}, {"dp": 2}, 0),
                           ("vip", {"dp": 8}, None, 1)])
    assert plans["vip"]["dp"] >= plans["old"]["dp"]
    assert plans["old"]["dp"] >= 2


def test_plan_fleet_two_jobs_both_reduced_shrink_dp_first():
    # neither {dp:2, tp:2} job fits at full size on a 4-device pool:
    # both shrink, and each shrink takes plan_mesh's tie-break — dp
    # first, the model-entangled tp axis stays at full size
    plans = plan_fleet(4, [("a", {"dp": 2, "tp": 2}, None, 0),
                           ("b", {"dp": 2, "tp": 2}, None, 0)])
    assert plans == {"a": {"dp": 1, "tp": 2}, "b": {"dp": 1, "tp": 2}}


def test_plan_fleet_growth_pass_uses_leftovers():
    # tier split would give the vip 7 -> dp4; the growth pass cannot
    # exceed divisors, but a {dp:6} job can pick the leftover pair up
    plans = plan_fleet(8, [("vip", {"dp": 6}, None, 1),
                           ("bg", {"dp": 2}, None, 0)])
    assert plans == {"vip": {"dp": 6}, "bg": {"dp": 2}}


def test_plan_fleet_tier_slack_never_leaks_to_lower_priority():
    """Divisor-rounding slack inside a priority tier must reach the
    growth pass (priority order) — not the next tier's budget.  Two
    prio-1 dp8 jobs each round 7//2=3 down to dp2; the 3 freed devices
    must grow job 'a' (then 'c'), never hand the background job more
    devices than each production job."""
    plans = plan_fleet(8, [("a", {"dp": 8}, None, 1),
                           ("b", {"dp": 8}, None, 1),
                           ("c", {"dp": 8}, None, 0)])
    assert plans == {"a": {"dp": 4}, "b": {"dp": 2}, "c": {"dp": 2}}
    sizes = {n: p["dp"] for n, p in plans.items()}
    assert sizes["c"] <= min(sizes["a"], sizes["b"])


def test_plan_fleet_floors_reserved_or_rejected():
    with pytest.raises(ValueError, match="floors need"):
        plan_fleet(4, [("a", {"dp": 4}, {"dp": 4}, 0),
                       ("b", {"dp": 2}, {"dp": 2}, 0)])
    with pytest.raises(ValueError, match="duplicate"):
        plan_fleet(4, [("a", {"dp": 2}, None, 0),
                       ("a", {"dp": 2}, None, 0)])
    assert plan_fleet(4, []) == {}


def test_min_plan_smallest_divisor_meeting_floor():
    assert min_plan({"dp": 8, "tp": 4}) == {"dp": 1, "tp": 1}
    assert min_plan({"dp": 8, "tp": 4}, {"tp": 2}) == {"dp": 1, "tp": 2}
    assert min_plan({"tp": 4}, {"tp": 3}) == {"tp": 4}  # 4 is ≥ the pin
    with pytest.raises(ValueError, match="floor"):
        min_plan({"tp": 4}, {"tp": 5})


# --------------------------------------------------------------------- #
# device pool ledger                                                     #
# --------------------------------------------------------------------- #
def test_device_pool_ownership_ledger():
    devs = list(range(4))       # bookkeeping never touches jax devices
    pool = DevicePool(devs)
    assert pool.size == 4 and pool.free() == devs
    pool.reassign({"a": [0, 1], "b": [2]})
    assert pool.owned_by("a") == [0, 1]
    assert pool.owner_of(2) == "b" and pool.owner_of(3) is None
    assert pool.free() == [3]
    pool.release("a")
    assert pool.free() == [0, 1, 3]
    with pytest.raises(ValueError, match="both"):
        pool.reassign({"a": [0], "b": [0]})
    with pytest.raises(ValueError, match="outside"):
        pool.reassign({"a": [99]})


def test_pool_claim_race_last_device_one_winner():
    # 8 claimants race for ONE free device: exactly one wins, every
    # loser gets PoolExhaustedError, and no device is double-owned
    pool = DevicePool([0])
    results, errors = [], []
    barrier = threading.Barrier(8)

    def claimant(i):
        barrier.wait()
        try:
            results.append((i, pool.claim(f"c{i}", 1)))
        except PoolExhaustedError as e:
            errors.append((i, e))

    threads = [threading.Thread(target=claimant, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 1 and len(errors) == 7
    winner, took = results[0]
    assert took == [0] and pool.owner_of(0) == f"c{winner}"
    assert pool.free() == []


def test_pool_claims_carved_out_of_planner_view():
    pool = DevicePool([0, 1, 2, 3])
    pool.claim("serve", 1)
    assert pool.schedulable() == [1, 2, 3]
    # the planner can reassign the schedulable share...
    pool.reassign({"job": [1, 2]})
    assert pool.owned_by("serve") == [0]        # claim preserved
    # ...but may neither name the claimant nor touch its device
    with pytest.raises(ValueError, match="incremental claimant"):
        pool.reassign({"serve": [3]})
    with pytest.raises(ValueError, match="both"):
        pool.reassign({"job": [0]})
    # a claim never partially succeeds: asking beyond free() takes
    # nothing
    with pytest.raises(PoolExhaustedError):
        pool.claim("serve2", 4)
    assert pool.free() == [3]


def test_pool_concurrent_claims_against_gang_replans():
    # an autoscaler claiming/releasing while the gang planner swaps
    # whole assignments: the ledger must never double-own a device
    pool = DevicePool(list(range(6)))
    stop = threading.Event()
    bad = []

    def autoscaler():
        while not stop.is_set():
            try:
                pool.claim("serve", 1)
            except PoolExhaustedError:
                pass
            pool.release("serve")

    def planner():
        while not stop.is_set():
            sched = pool.schedulable()
            half = len(sched) // 2
            try:
                pool.reassign({"a": sched[:half], "b": sched[half:]})
            except ValueError:
                # a claim landed between snapshot and swap — the real
                # FleetScheduler retries; here we just note it's loud
                pass
            owners = [pool.owner_of(d) for d in pool.devices]
            if len([o for o in owners if o == "serve"]) > 1:
                bad.append(owners)

    threads = [threading.Thread(target=autoscaler),
               threading.Thread(target=planner)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not bad
    pool.release("serve")
    # every device accounted for exactly once
    assert sorted(pool.free() + pool.owned_by("a")
                  + pool.owned_by("b")) == list(range(6))


def test_pool_release_idempotent_and_subset():
    pool = DevicePool([0, 1, 2])
    pool.claim("serve", 2)
    assert pool.release("serve", [0]) == [0]
    assert pool.release("serve", [0]) == []     # retry: no-op
    assert pool.release("serve") == [1]
    assert pool.release("serve") == []          # nothing held: no-op
    assert pool.release("ghost") == []          # unknown owner: no-op
    assert pool.free() == [0, 1, 2]
    # a fully-released claimant leaves the claims set, so the planner
    # sees the whole pool again
    assert pool.schedulable() == [0, 1, 2]


def test_pool_transfer_head_tail_and_floor():
    pool = DevicePool([0, 1, 2, 3])
    pool.claim("train", 3)
    assert pool.transfer("train", "serve", 1, take="tail") == [2]
    assert pool.transfer("train", "serve", 1, take="head") == [0]
    assert pool.owned_by("train") == [1]
    with pytest.raises(PoolExhaustedError, match="yield"):
        pool.transfer("train", "serve", 2)
    assert pool.owned_by("train") == [1]        # refusal took nothing
    # emptied source leaves the claims set
    pool.transfer("train", "serve", 1)
    assert pool.owned_by("train") == []
    assert sorted(pool.owned_by("serve")) == [0, 1, 2]


# --------------------------------------------------------------------- #
# admission control + fleet fault sites (no training required)           #
# --------------------------------------------------------------------- #
def _dummy_factory(mesh):
    raise AssertionError("never built in fast tests")


def _dummy_batch(s):
    raise AssertionError("never pulled in fast tests")


def _mini_fleet(rec, n=2):
    return FleetScheduler(jax.devices()[:n], recorder=rec,
                          handle_sigterm=False)


def test_admission_rejects_unfittable_floor_and_keeps_standing_jobs():
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = _mini_fleet(rec)
    j1 = fl.admit("j1", _dummy_factory, {"dp": 2}, min_axes={"dp": 2},
                  steps=1, batch_fn=_dummy_batch, ckpt_dir="/tmp/x1",
                  handle_sigterm=False)
    before = list(j1.devices)
    assert len(before) == 2
    # the arrival's floor cannot fit without breaking j1's: REJECTED,
    # and the standing job's assignment is untouched — a fleet decision
    # never kills (or squeezes under-floor) a job whose floor fits
    with pytest.raises(FleetAdmissionError, match="floors need"):
        fl.admit("j2", _dummy_factory, {"dp": 1}, steps=1,
                 batch_fn=_dummy_batch, ckpt_dir="/tmp/x2",
                 handle_sigterm=False)
    assert j1.devices == before and j1.state == "admitted"
    assert rec.counter_value("fleet/rejected") == 1
    assert rec.counter_value("fleet/admitted") == 1
    # the rejection is a first-class fleet_event (timeline-visible),
    # not a bare counter
    rej = [r for r in rec.recent_records(rec_type="fleet_event")
           if r.get("kind") == "rejected"]
    assert len(rej) == 1 and rej[0]["job"] == "j2"
    assert "floors need" in rej[0]["reason"]
    with pytest.raises(ValueError, match="already admitted"):
        fl.admit("j1", _dummy_factory, {"dp": 1}, steps=1,
                 batch_fn=_dummy_batch, ckpt_dir="/tmp/x3",
                 handle_sigterm=False)


def test_start_skips_job_whose_supervisor_is_not_built_yet():
    """admit() publishes the job in _jobs (under the lock) before its
    supervisor is constructed (outside it); a start() racing into that
    window must leave the job alone — launching it supervisor-less
    would crash _run_job and brand a freshly admitted job 'failed'.
    The admitting thread starts it itself once the supervisor exists."""
    from bigdl_tpu.fleet import FleetJob

    fl = FleetScheduler(jax.devices()[:2], handle_sigterm=False)
    job = FleetJob(fl, "x", {"dp": 2}, None, 0, 1, _dummy_batch, 0, None)
    with fl._lock:
        fl._jobs["x"] = job             # the mid-admit window
    fl.start()
    assert job.state == "admitted" and job.thread is None


def test_fleet_place_fault_is_retried():
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = _mini_fleet(rec)
    faults.reset()
    faults.arm("fleet.place:err:EIO@0")
    try:
        fl.admit("j", _dummy_factory, {"dp": 2}, steps=1,
                 batch_fn=_dummy_batch, ckpt_dir="/tmp/xp",
                 handle_sigterm=False)
        fired = faults.injected_total("fleet.place")
    finally:
        faults.reset()
    assert fired == 1
    assert rec.counter_value("fault/injected.fleet.place") == 1
    assert rec.counter_value("retry/attempts.fleet") >= 1
    assert fl.job("j").devices        # placement survived the blip


def test_fleet_preempt_fault_fires_on_shrink_delivery():
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = _mini_fleet(rec)
    low = fl.admit("low", _dummy_factory, {"dp": 2}, steps=1,
                   batch_fn=_dummy_batch, ckpt_dir="/tmp/l",
                   handle_sigterm=False)
    assert len(low.devices) == 2
    faults.reset()
    faults.arm("fleet.preempt:err:EIO@0")
    try:
        fl.admit("vip", _dummy_factory, {"dp": 1}, priority=1, steps=1,
                 batch_fn=_dummy_batch, ckpt_dir="/tmp/v",
                 handle_sigterm=False)
        fired = faults.injected_total("fleet.preempt")
    finally:
        faults.reset()
    assert fired == 1
    # the shrink went through despite the flaky delivery: low lost one
    # device to the vip and its recorder shows the per-job fleet/* count
    assert len(low.devices) == 1 and len(fl.job("vip").devices) == 1
    assert rec.counter_value("fleet/preempted") == 1
    assert low.recorder.counter_value("fleet/preempted") == 1
    assert low.recorder.counter_value("fault/injected.fleet.preempt") == 1
    events = [r for r in rec.recent_records()
              if r.get("type") == "fleet_event"]
    kinds = [e["kind"] for e in events]
    # canonical (priority) order: the vip's placement is applied first,
    # then the standing job's shrink is delivered
    assert kinds == ["admitted", "placed", "admitted", "placed",
                     "preempted"]
    assert events[4]["job"] == "low" and events[3]["job"] == "vip"


def test_fleet_place_giveup_applies_plan_anyway():
    """A fleet.place injection that keeps failing past the retry budget
    must be counted and logged, never strand the pool: the admit still
    places the job (planning is pure arithmetic, delivery is a pull),
    and a job_done replan would otherwise die in its worker thread."""
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = _mini_fleet(rec)
    faults.reset()
    faults.arm("fleet.place:err:EIO")     # every match: exhausts retry
    try:
        j = fl.admit("j", _dummy_factory, {"dp": 2}, steps=1,
                     batch_fn=_dummy_batch, ckpt_dir="/tmp/xg",
                     handle_sigterm=False)
    finally:
        faults.reset()
    assert len(j.devices) == 2 and j.state == "admitted"
    assert rec.counter_value("fleet/place_giveups") == 1
    assert rec.counter_value("retry/giveups.fleet") == 1


def test_shared_compile_cache_config(tmp_path, monkeypatch):
    """The fleet's cache goes where it is told — unless
    JAX_COMPILATION_CACHE_DIR places it from outside, which wins and
    leaves jax's own setting untouched (utils.engine's one rule)."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_shared_compile_cache(str(tmp_path / "cache"))
        assert os.path.isdir(path)
        assert jax.config.jax_compilation_cache_dir == path
        fl = FleetScheduler(jax.devices()[:1], handle_sigterm=False,
                            compile_cache_dir=str(tmp_path / "cache2"))
        assert jax.config.jax_compilation_cache_dir == \
            fl.compile_cache_dir == str(tmp_path / "cache2")

        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert enable_shared_compile_cache(
            str(tmp_path / "cache3")) == outside
        assert os.path.isdir(outside)
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / "cache2")          # not set in code
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# --------------------------------------------------------------------- #
# SIGTERM fan-out (satellite regression)                                 #
# --------------------------------------------------------------------- #
def test_one_sigterm_fans_out_to_every_handler():
    """Two handlers in one process: one SIGTERM must reach BOTH, and
    uninstalling one must NOT unhook the other (the clobber bug this
    dispatcher exists to fix)."""
    h1 = PreemptionHandler().install()
    h2 = PreemptionHandler().install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if h1.requested and h2.requested:
                break
            time.sleep(0.01)
        assert h1.requested and h2.requested
        # the regression: h1 leaving used to restore ITS displaced
        # disposition (SIG_DFL), silently unhooking h2 — the next
        # SIGTERM would have killed the process
        h1.uninstall()
        h1.reset()
        h2.reset()
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if h2.requested:
                break
            time.sleep(0.01)
        assert h2.requested and not h1.requested
    finally:
        h1.uninstall()
        h2.uninstall()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_uninstall_under_third_party_chainer_keeps_delivery():
    """A later hook (e.g. the flight recorder) that chains the
    dispatcher must survive a handler uninstall + reinstall: the
    dispatcher must NOT forget it owns a hook that a chainer still
    calls — re-hooking would save the chainer as prev and chain the
    dispatcher into itself (infinite recursion inside the signal
    handler)."""
    h1 = PreemptionHandler().install()
    hook = signal.getsignal(signal.SIGTERM)     # the dispatcher's hook
    seen = []

    def third_party(signum, frame):
        seen.append(signum)
        if callable(hook):
            hook(signum, frame)

    signal.signal(signal.SIGTERM, third_party)
    h2 = None
    try:
        h1.uninstall()      # hook not active: saved prev must survive
        h2 = PreemptionHandler().install()   # must NOT re-hook
        assert signal.getsignal(signal.SIGTERM) is third_party
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if h2.requested:
                break
            time.sleep(0.01)
        # the chainer saw it AND delivery reached the re-registered
        # handler exactly once — no self-chain recursion
        assert h2.requested and seen == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, hook)   # pop the chainer layer
        if h2 is not None:
            h2.uninstall()
        h1.uninstall()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_worker_thread_handler_hears_main_thread_hook():
    """A handler installed from a worker thread (where signal.signal is
    impossible) still receives the signal through a main-thread owner's
    hook — the fleet routing: supervisors register, the pool installs."""
    owner = PreemptionHandler().install()    # the pool's main-thread hook
    worker_h = {}

    def job():
        worker_h["h"] = PreemptionHandler().install()

    t = threading.Thread(target=job)
    t.start()
    t.join()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if worker_h["h"].requested:
                break
            time.sleep(0.01)
        assert worker_h["h"].requested and owner.requested
    finally:
        worker_h["h"].uninstall()
        owner.uninstall()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_empty_registry_hook_passes_through_to_default():
    """A hook that outlives its handlers (a worker-thread uninstall
    cannot drop the OS hook) must be a PASS-THROUGH, not a signal sink:
    with an empty registry and a SIG_DFL prev, SIGTERM must still kill
    the process — the operator's plain `kill <pid>` cannot silently
    disappear into a handler-less hook."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import os, signal, sys, threading, time
        sys.path.insert(0, %r)
        from bigdl_tpu.checkpoint.preemption import PreemptionHandler
        h = PreemptionHandler().install()   # main thread: owns the hook
        t = threading.Thread(target=h.uninstall)
        t.start(); t.join()                 # worker thread: hook survives
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(10)
        print("SURVIVED", flush=True)       # the bug: swallowed signal
    """ % (repo,))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == -signal.SIGTERM, (
        proc.returncode, proc.stdout, proc.stderr)
    assert "SURVIVED" not in proc.stdout


# --------------------------------------------------------------------- #
# per-job retry attribution (satellite)                                  #
# --------------------------------------------------------------------- #
def test_named_supervisors_split_retry_counters(tmp_path):
    """Two supervisors sharing one recorder must not collide on
    retry/attempts.elastic: a named (fleet) supervisor suffixes its job
    name onto the counter family."""
    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    sup_a = ElasticSupervisor(None, str(tmp_path), {"dp": 1},
                              recorder=rec, name="a", backoff_base=0.0,
                              handle_sigterm=False)
    sup_b = ElasticSupervisor(None, str(tmp_path), {"dp": 1},
                              recorder=rec, name="b", backoff_base=0.0,
                              handle_sigterm=False)
    anon = ElasticSupervisor(None, str(tmp_path), {"dp": 1},
                             recorder=rec, backoff_base=0.0,
                             handle_sigterm=False)
    sup_a._backoff("seg", RuntimeError("x"))
    sup_a._backoff("seg", RuntimeError("x"))
    sup_b._backoff("seg", RuntimeError("y"))
    anon._backoff("seg", RuntimeError("z"))
    assert rec.counter_value("retry/attempts.elastic.a") == 2
    assert rec.counter_value("retry/attempts.elastic.b") == 1
    assert rec.counter_value("retry/attempts.elastic") == 1  # unnamed only
    assert rec.counter_value("retry/attempts") == 4


# --------------------------------------------------------------------- #
# aggregated /metrics + /healthz                                         #
# --------------------------------------------------------------------- #
def test_render_prometheus_multi_groups_headers_once():
    ra, rb = Recorder(annotate=False), Recorder(annotate=False)
    ra.inc("fleet/preempted")
    ra.inc("elastic/resumes", 3)
    rb.inc("fleet/preempted", 2)
    base = Recorder(annotate=False)
    base.inc("fleet/admitted", 2)
    text = render_prometheus_multi(
        [(None, base), ({"job": "a"}, ra), ({"job": "b"}, rb)])
    lines = text.splitlines()
    # exposition format: ONE TYPE header per metric even with three
    # sources; per-job samples stay distinct labeled series
    assert lines.count("# TYPE bigdl_fleet_preempted_total counter") == 1
    assert 'bigdl_fleet_preempted_total{job="a"} 1.0' in lines
    assert 'bigdl_fleet_preempted_total{job="b"} 2.0' in lines
    assert "bigdl_fleet_admitted_total 2.0" in lines      # unlabeled base
    assert 'bigdl_elastic_resumes_total{job="a"} 3.0' in lines
    # single-source rendering is unchanged by the label plumbing
    assert render_prometheus(base).splitlines()[-1] == \
        "bigdl_fleet_admitted_total 2.0"


def test_labeled_histograms_and_queue_depth_merge_labels():
    r = Recorder(annotate=False)
    r.observe("lat_ms", 1.0)
    r.observe("lat_ms", 3.0)
    r.gauge("serving.queue_depth.m1", 4)
    text = render_prometheus(r, labels={"job": "svc"})
    assert 'bigdl_lat_ms{job="svc",quantile="0.5"} 2.0' in text
    assert 'bigdl_lat_ms_count{job="svc"} 2' in text
    assert 'bigdl_serving_queue_depth{job="svc",model="m1"} 4.0' in text


def test_aggregated_healthz_worst_of_verdict():
    base = Recorder(annotate=False)
    srv = IntrospectionServer(base)
    ra, rb = Recorder(annotate=False), Recorder(annotate=False)
    srv.add_job("a", ra)
    srv.add_job("b", rb, watchdog=lambda: None)   # provider form
    hz = srv.healthz()
    assert hz["ok"] and set(hz["jobs"]) == {"a", "b"}
    # ANY job stalled => aggregated 503, the job's verdict names it
    rb.gauge("health/stalled", 1)
    hz = srv.healthz()
    assert not hz["ok"] and hz["stalled"]
    assert hz["jobs"]["b"]["stalled"] and not hz["jobs"]["a"]["stalled"]
    srv.remove_job("b")
    assert srv.healthz()["ok"]
    # over real HTTP: 503 iff any job is sick
    srv.add_job("b", rb)
    srv.start()
    try:
        try:
            urllib.request.urlopen(srv.url("/healthz"))
            raise AssertionError("expected HTTP 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            doc = json.loads(e.read().decode())
            assert doc["jobs"]["b"]["stalled"]
        rb.gauge("health/stalled", 0)
        with urllib.request.urlopen(srv.url("/healthz")) as resp:
            assert resp.status == 200
        metrics = urllib.request.urlopen(
            srv.url("/metrics")).read().decode()
        assert 'job="b"' in metrics
    finally:
        srv.stop()


# --------------------------------------------------------------------- #
# trace_summary fleet renderer (golden)                                  #
# --------------------------------------------------------------------- #
def test_trace_summary_fleet_golden(tmp_path):
    ts = _load_trace_summary()
    fleet_log = tmp_path / "fleet.jsonl"
    job_log = tmp_path / "job_b.jsonl"
    with open(fleet_log, "w") as f:
        for rec in [
            {"type": "fleet_event", "time": 100.0, "kind": "admitted",
             "job": "b", "priority": 0, "template": {"dp": 4}},
            {"type": "fleet_event", "time": 100.5, "kind": "placed",
             "job": "b", "devices": 4, "axes": {"dp": 4},
             "reason": "admit"},
            {"type": "fleet_event", "time": 104.0, "kind": "displaced",
             "job": "b", "devices": 4, "axes": {"dp": 4},
             "reason": "admit"},
            {"type": "step", "time": 104.5},        # ignored
            {"type": "fleet_event", "time": 110.0, "kind": "completed",
             "job": "b", "steps": 8},
        ]:
            f.write(json.dumps(rec) + "\n")
    with open(job_log, "w") as f:
        for rec in [
            {"type": "elastic_event", "time": 104.2, "kind": "displace",
             "job": "b", "state": "resuming", "axes": {"dp": 4},
             "devices": 4},
            {"type": "elastic_event", "time": 104.6, "kind": "resume",
             "job": "b", "state": "resuming", "step": 4, "devices": 4,
             "axes": {"dp": 4}},
        ]:
            f.write(json.dumps(rec) + "\n")
    lines = []
    events = ts.load_fleet([str(tmp_path)])
    ts.summarize_fleet(events, out=lines.append)
    assert lines == [
        "== fleet timeline ==",
        "         t  job        event        detail",
        "    +0.00s  b          admitted     template dp4 prio=0",
        "    +0.50s  b          placed       dp4 devices=4 [admit]",
        "    +4.00s  b          displaced    dp4 devices=4 [admit]",
        "    +4.20s  b          displace     dp4 devices=4",
        "    +4.60s  b          resume       dp4 devices=4 step=4",
        "   +10.00s  b          completed    steps=8",
        "\n== per-job event sequence ==",
        "  b: admitted -> placed -> displaced -> displace -> resume "
        "-> completed",
    ]
    # empty input degrades politely
    lines = []
    ts.summarize_fleet([], out=lines.append)
    assert lines == ["no fleet or elastic events found"]


# --------------------------------------------------------------------- #
# contention matrix (slow: drives two SpmdTrainers through the pool)     #
# --------------------------------------------------------------------- #
_CFG = dict(n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab_size=64,
            max_len=16)


def _trainer_factory(mesh):
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    model = T.build("tiny", dropout=0.0, **_CFG)
    return SpmdTrainer(model, Adam(learning_rate=1e-3), mesh=mesh,
                       fsdp=False, seed=0)


def _batch_for(seed):
    def batch(s):
        rs = np.random.RandomState(seed + s)
        t = rs.randint(0, 64, (8, 17))
        return t[:, :-1], t[:, 1:]
    return batch


@pytest.mark.slow
def test_contention_shrinks_low_priority_never_kills(tmp_path):
    """The shrink form of preemption, end to end: B owns the whole
    8-device pool; a high-priority arrival takes half; B SHRINKS
    through its capacity seam (drain → replan → resume — never a job
    death while its floor fits), then REGROWS to the full pool when
    the vip completes.  B's loss curve stays tight-allclose to its
    solo run — the documented reassociation drift, not divergence."""
    solo = ElasticSupervisor(
        _trainer_factory, str(tmp_path / "solo"), {"dp": 8},
        batch_fn=_batch_for(1234), ckpt_every=100, replan_every=100,
        handle_sigterm=False)
    base = solo.run(steps=24)

    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = FleetScheduler(jax.devices()[:8], recorder=rec,
                        handle_sigterm=False)
    # 24 steps, vip only 5: B must still be mid-run when the vip
    # completes, so the regrow leg always happens — with a short B a
    # slow vip compile occasionally let B finish while still shrunk
    # and the regrown/regrows asserts flaked
    b = fl.admit("b", _trainer_factory, {"dp": 8}, min_axes={"dp": 2},
                 steps=24, batch_fn=_batch_for(1234),
                 ckpt_dir=str(tmp_path / "b"), ckpt_every=3,
                 handle_sigterm=False, backoff_base=0.05)
    fl.start()
    deadline = time.time() + 120
    while b.recorder.gauge_value("elastic/steps_done") < 3:
        assert time.time() < deadline, "b made no progress"
        time.sleep(0.1)
    a = fl.admit("a", _trainer_factory, {"dp": 4}, priority=1, steps=5,
                 batch_fn=_batch_for(777),
                 ckpt_dir=str(tmp_path / "a"), ckpt_every=3,
                 handle_sigterm=False, backoff_base=0.05)
    assert len(a.devices) == 4 and len(b.devices) == 4  # b shrank
    res = fl.run(timeout=480)
    assert a.state == "completed" and b.state == "completed"
    assert len(res["b"]) == 24 and np.all(np.isfinite(res["b"]))
    # the fleet never killed anyone: preemption took the shrink path
    assert rec.counter_value("fleet/failed") == 0
    assert rec.counter_value("fleet/preempted") == 1
    assert rec.counter_value("fleet/regrown") == 1
    assert b.recorder.counter_value("elastic/shrinks") == 1
    assert b.recorder.counter_value("elastic/regrows") == 1
    # dp8 -> dp4 -> dp8 reassociates reductions: same math, last-ulp
    # drift per the checkpointing taxonomy — tight allclose, and the
    # solo prefix before the shrink is identical
    np.testing.assert_allclose(res["b"], base, rtol=1e-4)


@pytest.mark.slow
def test_two_concurrent_supervisors_one_real_sigterm(tmp_path):
    """Satellite regression at fleet level: two supervisors on worker
    threads, one real SIGTERM — BOTH must hear it (fan-out through the
    scheduler's main-thread hook) and both must end with a committed
    checkpoint instead of the process dying or one job missing the
    signal."""
    from bigdl_tpu.checkpoint import scan

    rec = Recorder(sinks=[InMemorySink()], annotate=False)
    fl = FleetScheduler(jax.devices()[:8], recorder=rec,
                        handle_sigterm=True)
    jobs = {}
    for name, seed in (("j1", 100), ("j2", 4300)):
        jobs[name] = fl.admit(
            name, _trainer_factory, {"dp": 4}, steps=200,
            batch_fn=_batch_for(seed),
            ckpt_dir=str(tmp_path / name), ckpt_every=3,
            handle_sigterm=True, backoff_base=0.05)
    try:
        fl.start()
        deadline = time.time() + 120
        while any(j.recorder.gauge_value("elastic/steps_done") < 2
                  for j in jobs.values()):
            assert time.time() < deadline, "jobs made no progress"
            time.sleep(0.1)
        os.kill(os.getpid(), signal.SIGTERM)
        res = fl.wait(timeout=300)
        assert rec.counter_value("fleet/sigterm") == 1
        for name, j in jobs.items():
            # each supervisor heard the fan-out: either it drained on
            # the preemption flag (committing a preempt checkpoint) or
            # the scheduler's stop landed first (committing a final
            # sync checkpoint) — both are the PR-3 zero-lost-steps
            # contract; what must never happen is a job that neither
            # heard the signal nor stopped
            assert j.state in ("stopped", "completed")
            assert len(res[name]) < 200     # it did NOT run to the end
            tags = [mf.tag for _, mf in scan(str(tmp_path / name))]
            assert tags, f"{name} committed no checkpoint"
            heard = (j.supervisor._preemption is not None
                     and j.supervisor._preemption.requested) \
                or j.recorder.counter_value("elastic/preemptions") >= 1
            assert heard, f"{name} never heard the SIGTERM"
    finally:
        fl.shutdown()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
