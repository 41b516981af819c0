"""Goodput ledger (ISSUE 20): exclusive-bucket conservation, span and
split folding, phase nesting across threads, pool ownership roll-up,
the /goodput endpoint, the proxy-regression sentinel, BENCH-round
normalization, and the racecheck-harness proof that concurrent
replica-kill + checkpoint-commit + autoscale-shrink attribution never
double-books a device-second."""
import importlib.util
import json
import os
import threading
import time
import urllib.request

import pytest

from bigdl_tpu.analysis.racecheck import RaceCheck, wrap_lock
from bigdl_tpu.observability import Recorder, regress
from bigdl_tpu.observability.goodput import (BUCKETS, GoodputLedger,
                                             OwnershipLedger,
                                             ledger_phase, rollup)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
# regression sentinel                                                   #
# --------------------------------------------------------------------- #
def _row(source, **metrics):
    return {"source": source, "metrics": metrics}


def test_sentinel_fails_undocumented_regression_waives_justified():
    rows = [_row("bench:r09", tps=100.0)]
    findings = regress.check(rows, {"metrics": {
        "bench:r09/tps": {"min": 150.0}}})
    assert [f.severity for f in findings] == ["fail"]
    assert not regress.gate(findings)
    findings = regress.check(rows, {"metrics": {
        "bench:r09/tps": {"min": 150.0,
                          "justification": "known CPU-proxy slowdown"}}})
    assert [f.severity for f in findings] == ["waived"]
    assert regress.gate(findings)


def test_sentinel_bucket_ceiling_applies_to_every_ledger_row():
    led, clk = _led()
    with led.phase("checkpoint_blocking"):
        clk.tick(8.0)
    clk.tick(2.0)
    rows = [_row("bench:r09", tps=1.0),
            regress.ledger_row("train", led.snapshot())]
    findings = regress.check(rows, {"buckets": {
        "checkpoint_blocking": {"max_fraction": 0.5}}})
    assert len(findings) == 1
    f = findings[0]
    assert f.severity == "fail" and not regress.gate(findings)
    assert f.key == "ledger:train/buckets.checkpoint_blocking"
    assert f.value == pytest.approx(0.8)


def test_sentinel_stale_bound_and_change_point_are_advisory():
    rows = [_row("bench:r07", x=10.0), _row("bench:r08", x=10.5),
            _row("bench:r09", x=9.8), _row("bench:r10", x=95.0)]
    findings = regress.check(
        rows, {"metrics": {"bench:r10/x": {"min": 1.0}},
               "watch": ["bench:*/x"]})
    sev = sorted(f.severity for f in findings)
    assert sev == ["info", "info"]          # stale bound + change-point
    assert regress.gate(findings)
    assert any("change-point" in f.message for f in findings)


def test_sentinel_missing_source_or_metric_is_info_not_fail():
    findings = regress.check([_row("bench:r09", tps=1.0)], {"metrics": {
        "bench:r03/gone": {"min": 1.0},
        "bench:r09/absent": {"max": 2.0}}})
    assert all(f.severity == "info" for f in findings)
    assert regress.gate(findings)


def test_ledger_row_folds_buckets_to_fractions_of_owned():
    led, clk = _led(devices=2)
    clk.tick(5.0)
    led.fold_split({"goodput": 3.0, "queue_wait": 2.0})
    row = regress.ledger_row("serve", led.snapshot())
    assert row["source"] == "ledger:serve"
    assert row["metrics"]["buckets.goodput"] == pytest.approx(0.6)
    assert row["metrics"]["buckets.queue_wait"] == pytest.approx(0.4)
    assert row["metrics"]["conservation_error"] <= 1e-9
    assert row["metrics"]["owned_s"] == pytest.approx(10.0)


def test_committed_baseline_parses_and_names_real_buckets():
    base = regress.load_baseline(
        os.path.join(_REPO, "artifacts", "goodput_baseline.json"))
    assert base["metrics"], "baseline must bound at least one metric"
    for b in (base.get("buckets") or {}):
        assert b in BUCKETS, f"unknown bucket {b!r} in baseline"


# --------------------------------------------------------------------- #
# BENCH-round normalization (bench_trend)                               #
# --------------------------------------------------------------------- #
def _bench_trend():
    spec = importlib.util.spec_from_file_location(
        "bench_trend", os.path.join(_REPO, "scripts", "bench_trend.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round_docs():
    """One document per schema the driver has banked: a parsed chip line,
    a failed round, and the three smoke shapes (compose matrix, decode
    with no metric key anywhere, rec_smoke)."""
    return {
        1: {"n": 1, "cmd": "python bench.py", "rc": 0,
            "tail": "2026-07-29 18:29:49 ...",
            "parsed": {"metric": "resnet50_train_images_per_sec_per_chip",
                       "value": 2609.02, "unit": "images/sec",
                       "vs_baseline": 45.772}},
        2: {"n": 2, "cmd": "python bench.py", "rc": 2,
            "tail": "2026-07-30 15:49:33 backend unreachable",
            "parsed": None},
        8: {"bench": "compose_proxy_smoke", "proxy": True, "devices": 8,
            "configs": {
                "dp2_tp2_pp2": {"status": "trained"},
                **{f"cfg{i}": {"losses": [4.0 - i, 3.0 - i],
                               "wire": 1000.0 * i, "bitwise": True}
                   for i in range(6)}}},
        9: {"n": 9, "cmd": "python scripts/decode_smoke.py", "rc": 0,
            "proxy": True,
            "decode_throughput": {"speedup": 1.6, "recompiles": 0,
                                  "continuous_tokens_per_s": 4000.0},
            "churn": {"requests": 32, "tokens": 586},
            "weight_stream": {"published": 3, "client_errors": 0}},
        10: {"metric": "rec_smoke", "proxy": True, "rc": 0, "ok": True,
             "lookup_exchange": {"bitwise_vs_dense": True,
                                 "dedup_ratio": 0.404},
             "table_bytes": {"f32": 6400, "int8": 2000, "ratio": 3.2},
             "two_tower": {"loss_first": 0.69333, "loss_last": 0.69236}},
    }


def test_normalize_rounds_unifies_divergent_schemas(tmp_path):
    bt = _bench_trend()
    for n, doc in _round_docs().items():
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))
    rows = bt.normalize_rounds(bt.load_rounds(str(tmp_path)))
    assert [r["round"] for r in rows] == [1, 2, 8, 9, 10]
    by_round = {r["round"]: r for r in rows}
    # r08 (compose matrix), r09 (no metric key), r10 (rec_smoke):
    # three different document shapes, one row schema
    assert by_round[8]["metrics"]["configs.total"] == 7.0
    assert by_round[8]["metrics"]["configs.cfg3.wire"] == 3000.0
    assert by_round[8]["metrics"]["configs.cfg3.bitwise"] == 1.0
    assert by_round[9]["metric"] == "decode_smoke"
    assert by_round[9]["metrics"], "r09 metrics empty"
    assert by_round[10]["metrics"], "r10 metrics empty"
    assert by_round[1]["mode"] == "hardware"
    assert by_round[1]["metrics"]["value"] == 2609.02
    assert by_round[2]["mode"] == "FAILED"     # a failed round keeps
    assert by_round[2]["metrics"] == {}        # its gap in the table
    bench = regress.bench_rows(rows)
    assert all(b["source"].startswith("bench:r") for b in bench)


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
