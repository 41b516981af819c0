"""Goodput ledger (ISSUE 20): exclusive-bucket conservation, span and
split folding, phase nesting across threads, pool ownership roll-up,
the /goodput endpoint, and the racecheck-harness proof that concurrent
replica-kill + checkpoint-commit + autoscale-shrink attribution never
double-books a device-second."""
import json
import threading
import time
import urllib.request

import pytest

from bigdl_tpu.analysis.racecheck import RaceCheck, wrap_lock
from bigdl_tpu.observability import Recorder
from bigdl_tpu.observability.goodput import (BUCKETS, GoodputLedger,
                                             OwnershipLedger,
                                             ledger_phase, rollup)


class FakeClock:
    """Deterministic monotonic clock the ledger math is tested against."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += float(dt)
        return self.t


def _led(devices=1, t=100.0):
    clk = FakeClock(t)
    return GoodputLedger(name="t", devices=devices, clock=clk), clk


def _conserves(snap, tol=1e-9):
    assert snap["conservation_error"] <= tol, snap
    assert abs(sum(snap["buckets"].values()) - snap["owned_s"]) \
        <= tol * max(snap["owned_s"], 1.0)


# --------------------------------------------------------------------- #
# core interval engine                                                  #
# --------------------------------------------------------------------- #
def test_background_time_defaults_to_idle():
    led, clk = _led()
    clk.tick(5.0)
    snap = led.snapshot()
    assert snap["owned_s"] == pytest.approx(5.0)
    assert snap["buckets"]["idle"] == pytest.approx(5.0)
    assert snap["goodput_fraction"] == 0.0
    _conserves(snap)


def test_snapshot_keys_cover_the_closed_taxonomy():
    led, _ = _led()
    snap = led.snapshot()
    assert set(snap["buckets"]) == set(BUCKETS)
    assert BUCKETS[0] == "goodput" and BUCKETS[-1] == "idle"


def test_fold_step_span_carving_and_residual_goodput():
    led, clk = _led()
    clk.tick(10.0)
    led.fold_step(10.0, {"data_fetch": 3.0, "checkpoint.blocking": 2.0,
                         "not_a_badput_span": 4.0})
    snap = led.snapshot()
    assert snap["buckets"]["input_stall"] == pytest.approx(3.0)
    assert snap["buckets"]["checkpoint_blocking"] == pytest.approx(2.0)
    # unknown spans are productive step time, not badput
    assert snap["buckets"]["goodput"] == pytest.approx(5.0)
    _conserves(snap)


def test_fold_step_clamps_overlapping_spans():
    """Overlapping/overlong span totals can't mint device-seconds: the
    carve is clamped to the step budget and goodput floors at zero."""
    led, clk = _led()
    clk.tick(4.0)
    led.fold_step(4.0, {"data_fetch": 3.0, "h2d": 9.0})
    snap = led.snapshot()
    assert snap["buckets"]["input_stall"] == pytest.approx(4.0)
    assert snap["buckets"]["goodput"] == 0.0
    assert snap["owned_s"] == pytest.approx(4.0)
    _conserves(snap)


def test_fold_step_gap_beyond_dur_goes_to_background():
    led, clk = _led()
    led.declare("preemption_drain")
    clk.tick(7.0)
    led.fold_step(2.0, {})      # 2s step, 5s un-closed gap before it
    snap = led.snapshot()
    assert snap["buckets"]["goodput"] == pytest.approx(2.0)
    assert snap["buckets"]["preemption_drain"] == pytest.approx(5.0)
    _conserves(snap)


def test_note_step_begin_closes_the_gap_first():
    led, clk = _led()
    clk.tick(3.0)
    led.note_step_begin()
    clk.tick(2.0)
    led.fold_step(2.0, {})
    snap = led.snapshot()
    assert snap["buckets"]["idle"] == pytest.approx(3.0)
    assert snap["buckets"]["goodput"] == pytest.approx(2.0)


def test_fold_split_proportional_and_zero_weight_fallback():
    led, clk = _led()
    clk.tick(4.0)
    led.fold_split({"goodput": 2.0, "queue_wait": 1.0, "idle": 1.0})
    snap = led.snapshot()
    assert snap["buckets"]["goodput"] == pytest.approx(2.0)
    assert snap["buckets"]["queue_wait"] == pytest.approx(1.0)
    assert snap["buckets"]["idle"] == pytest.approx(1.0)
    led.declare("brownout")
    clk.tick(2.0)
    led.fold_split({"goodput": 0.0})        # zero total -> background
    snap = led.snapshot()
    assert snap["buckets"]["brownout"] == pytest.approx(2.0)
    _conserves(snap)


def test_set_devices_charges_old_count_up_to_the_edge():
    led, clk = _led(devices=2)
    clk.tick(3.0)               # 3s x 2 dev
    led.set_devices(4)
    clk.tick(1.0)               # 1s x 4 dev
    snap = led.snapshot()
    assert snap["owned_s"] == pytest.approx(10.0)
    assert snap["buckets"]["idle"] == pytest.approx(10.0)
    assert snap["devices"] == 4
    _conserves(snap)


# --------------------------------------------------------------------- #
# declared phases                                                       #
# --------------------------------------------------------------------- #
def test_nested_phases_newest_wins():
    led, clk = _led()
    with led.phase("failover"):
        clk.tick(1.0)
        with led.phase("probe_readmission"):
            clk.tick(2.0)
        clk.tick(3.0)
    snap = led.snapshot()
    assert snap["buckets"]["failover"] == pytest.approx(4.0)
    assert snap["buckets"]["probe_readmission"] == pytest.approx(2.0)
    _conserves(snap)


def test_concurrent_phases_unwind_in_any_order():
    """Two threads' phases interleave: each pop removes its OWN token
    wherever it sits, and elapsed time always flowed to whichever
    declaration was newest — nothing double-books, nothing leaks."""
    led, clk = _led()
    p1 = led.phase("preemption_drain")
    p1.__enter__()
    clk.tick(1.0)
    p2 = led.phase("autoscale_transfer")
    p2.__enter__()
    clk.tick(2.0)
    p1.__exit__(None, None, None)       # outer exits FIRST
    clk.tick(3.0)
    p2.__exit__(None, None, None)
    clk.tick(4.0)
    snap = led.snapshot()
    assert snap["buckets"]["preemption_drain"] == pytest.approx(1.0)
    assert snap["buckets"]["autoscale_transfer"] == pytest.approx(5.0)
    assert snap["buckets"]["idle"] == pytest.approx(4.0)
    _conserves(snap)


def test_declare_switches_background_and_returns_previous():
    led, clk = _led()
    assert led.declare("preemption_replan") == "idle"
    clk.tick(2.0)
    assert led.declare("idle") == "preemption_replan"
    snap = led.snapshot()
    assert snap["buckets"]["preemption_replan"] == pytest.approx(2.0)


def test_ledger_phase_is_noop_without_a_ledger():
    rec = Recorder(annotate=False)
    with ledger_phase(rec, "failover"):
        pass                            # no ledger attached: null cm
    with ledger_phase(object(), "failover"):
        pass                            # not even a recorder


# --------------------------------------------------------------------- #
# recorder wiring                                                       #
# --------------------------------------------------------------------- #
def test_recorder_end_step_folds_and_publishes():
    rec = Recorder(annotate=False)
    rec.set_ledger(GoodputLedger(name="train", devices=2))
    rec.start_step(0)
    with rec.span("data_fetch"):
        time.sleep(0.03)
    time.sleep(0.02)
    rec.end_step(0, loss=1.0)
    snap = rec.get_ledger().snapshot()
    assert snap["buckets"]["input_stall"] > 0.0
    assert snap["buckets"]["goodput"] > 0.0
    _conserves(snap, tol=1e-6)
    # the gauge mirror trace_summary's JSONL fallback rebuilds from
    assert rec.gauge_value("goodput/input_stall_s") > 0.0
    assert rec.gauge_value("goodput/owned_s") > 0.0
    assert rec.gauge_value("goodput/devices") == 2.0


def test_goodput_endpoint_serves_the_attached_ledger():
    from bigdl_tpu.observability.http import IntrospectionServer
    rec = Recorder(annotate=False)
    led = GoodputLedger(name="train", devices=4)
    rec.set_ledger(led)
    with led.phase("checkpoint_blocking"):
        time.sleep(0.02)
    srv = IntrospectionServer(rec, port=0).start()
    try:
        with urllib.request.urlopen(srv.url("/goodput"),
                                    timeout=5.0) as r:
            doc = json.loads(r.read().decode())
        assert doc["name"] == "train" and doc["devices"] == 4
        assert doc["buckets"]["checkpoint_blocking"] > 0.0
        assert doc["conservation_error"] <= 1e-6
    finally:
        srv.stop()


def test_goodput_endpoint_404_without_ledger_and_source_override():
    from bigdl_tpu.observability.http import IntrospectionServer
    srv = IntrospectionServer(Recorder(annotate=False), port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url("/goodput"), timeout=5.0)
        assert ei.value.code == 404
    finally:
        srv.stop()
    led, clk = _led()
    clk.tick(1.0)
    srv = IntrospectionServer(
        Recorder(annotate=False), port=0,
        goodput_source=lambda: rollup({"j": led.snapshot()})).start()
    try:
        with urllib.request.urlopen(srv.url("/goodput"),
                                    timeout=5.0) as r:
            doc = json.loads(r.read().decode())
        assert "jobs" in doc and doc["owned_s"] == pytest.approx(1.0)
    finally:
        srv.stop()


# --------------------------------------------------------------------- #
# pool ownership + roll-up                                              #
# --------------------------------------------------------------------- #
def test_ownership_ledger_splits_claimed_vs_pool_idle():
    clk = FakeClock()
    own = OwnershipLedger(4, clock=clk)
    clk.tick(2.0)                       # 2s x 0 claimed
    own.note(3)
    clk.tick(3.0)                       # 3s x 3 claimed
    own.note(0)
    snap = own.snapshot()
    assert snap["claimed_s"] == pytest.approx(9.0)
    assert snap["pool_idle_s"] == pytest.approx(8.0 + 3.0)
    assert snap["owned_s"] == pytest.approx(20.0)


def test_rollup_keeps_pool_idle_disjoint_from_job_badput():
    a, ca = _led(devices=2)
    ca.tick(4.0)
    a.fold_split({"goodput": 1.0})
    b, cb = _led()
    cb.tick(2.0)
    with b.phase("failover"):
        cb.tick(1.0)
    roll = rollup({"a": a.snapshot(), "b": b.snapshot()},
                  {"devices": 4, "pool_idle_s": 5.0, "claimed_s": 11.0,
                   "owned_s": 16.0})
    assert roll["buckets"]["goodput"] == pytest.approx(8.0)
    assert roll["buckets"]["failover"] == pytest.approx(1.0)
    assert roll["pool_idle_s"] == pytest.approx(5.0)
    assert roll["owned_s"] == pytest.approx(8.0 + 3.0 + 5.0)
    assert roll["conservation_error"] <= 1e-9
    assert roll["goodput_fraction"] == pytest.approx(8.0 / 16.0)
    assert "pool" in roll and roll["jobs"]["a"]["devices"] == 2


def test_device_pool_notes_occupancy_into_its_ownership_ledger():
    from bigdl_tpu.fleet import DevicePool
    pool = DevicePool(devices=["d0", "d1", "d2"])
    pool.claim("train", 2)
    time.sleep(0.02)
    snap = pool.goodput.snapshot()
    assert snap["devices"] == 3
    assert snap["claimed_s"] > 0.0
    assert snap["pool_idle_s"] > 0.0        # d2 claimed by nobody
    pool.release("train")
    snap2 = pool.goodput.snapshot()
    assert snap2["claimed"] == 0


# --------------------------------------------------------------------- #
# racecheck: concurrent attribution never double-books                  #
# --------------------------------------------------------------------- #
def test_concurrent_kill_checkpoint_shrink_never_double_books():
    """Replica-kill failover phases, checkpoint-commit folds, and an
    autoscale shrink (device-count edges + transfer phases) hammer ONE
    ledger from three threads under the racecheck harness: no lock
    inversion, no bare write, and the buckets still sum to owned —
    i.e. no interleaving can double-book a device-second."""
    rc = RaceCheck()
    led = GoodputLedger(name="race", devices=4)
    wrap_lock(led, "_lock", rc)
    stop = threading.Event()
    errors = []

    def guard(fn):
        def run():
            try:
                while not stop.is_set():
                    fn()
            except Exception as e:      # pragma: no cover
                errors.append(e)
        return run

    def kill_failover():                # the ReplicaSet._failover shape
        with led.phase("failover"):
            time.sleep(0.001)
        with led.phase("probe_readmission"):
            time.sleep(0.0005)

    def checkpoint_commit():            # the end_step fold shape
        led.note_step_begin()
        time.sleep(0.001)
        led.fold_step(0.001, {"checkpoint.blocking": 0.0005})

    def autoscale_shrink():             # the controller + mesh edge
        with led.phase("autoscale_transfer"):
            time.sleep(0.0005)
        led.set_devices(2)
        time.sleep(0.0005)
        led.set_devices(4)

    threads = [threading.Thread(target=guard(f), daemon=True)
               for f in (kill_failover, checkpoint_commit,
                         autoscale_shrink)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    assert not errors, errors
    rc.assert_clean()
    snap = led.snapshot()
    assert snap["owned_s"] > 0.0
    assert abs(sum(snap["buckets"].values()) - snap["owned_s"]) \
        <= 1e-6 * snap["owned_s"]
    assert snap["conservation_error"] <= 1e-6
    for bucket in ("failover", "probe_readmission", "goodput",
                   "checkpoint_blocking", "autoscale_transfer"):
        assert snap["buckets"][bucket] > 0.0, bucket
