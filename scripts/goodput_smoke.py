#!/usr/bin/env python
"""Goodput-ledger smoke (ISSUE 20 acceptance, CI ``goodput-smoke``).

**Leg 1 — train: every preemption second lands in a named bucket.**
An :class:`ElasticSupervisor` trains ``{"dp": 4}`` over a mutable
capacity seam; mid-run the harness shrinks capacity 4 → 2 and then
regrows it, forcing one full shrink (drain → checkpoint → replan →
relayout) and one regrow.  With ``ckpt_every=4`` the device→host
snapshot copies book ``checkpoint.blocking`` spans throughout.
Asserts: the trainer's ledger conserves (buckets sum to owned
device-seconds within 1%), and ``preemption_drain``,
``preemption_replan``, ``checkpoint_blocking`` and ``goodput`` are
each individually non-zero.

**Leg 2 — serve: failover, probe readmission, autoscale transfer.**
A two-replica CPU decode set takes pinned-latency traffic; a hard
``kill(0)`` mid-flight exercises the budgeted failover path, then an
:class:`AutoscaleController` driven through a synthetic occupancy
peak/trough claims a pool device for a third replica (golden-probed
into rotation — ``probe_readmission``) and drains it back out.
Asserts: the set-level control-plane ledger and every decode engine's
occupancy ledger conserve within 1%; ``failover``,
``autoscale_transfer``, ``probe_readmission``, decode ``goodput`` and
``compile_warmup`` are each non-zero and named.

**Roll-up + waterfall.**  Both legs' ledgers plus the shared
:class:`DevicePool`'s ownership ledger (one device deliberately never
claimed → ``pool_idle``, kept disjoint from job badput) roll into one
fleet document, written to disk and rendered by
``trace_summary.py goodput`` — the render is asserted, not just run.

Emits ONE machine-parseable JSON line last (the CI contract).
"""
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=8").strip()

import numpy as np                                         # noqa: E402

from bigdl_tpu import faults                               # noqa: E402
from bigdl_tpu.autoscale import (AutoscaleController,      # noqa: E402
                                 AutoscalePolicy)
from bigdl_tpu.fleet import DevicePool                     # noqa: E402
from bigdl_tpu.models import transformer as T              # noqa: E402
from bigdl_tpu.observability import (JsonlSink,            # noqa: E402
                                     Recorder, SeriesStore)
from bigdl_tpu.observability.goodput import rollup         # noqa: E402
from bigdl_tpu.serving import (DecodeEngine,               # noqa: E402
                               ModelRegistry)
from bigdl_tpu.serving.decode import \
    build_decode_replica_set                               # noqa: E402

STEP_PIN_MS = 30
OUT_TOKENS = 8
ENGINE_KW = dict(slots=4, page_size=8, max_context=64, max_prompt=8,
                 max_new_tokens=OUT_TOKENS, max_waiting=512)

T_STEPS = 80            # divisible by ckpt_every
T_CKPT_EVERY = 4
T_REPLAN_EVERY = 2

FAILURES = []


def check(ok, msg):
    print(f"# {'ok' if ok else 'FAIL'}: {msg}", flush=True)
    if not ok:
        FAILURES.append(msg)
    return ok


def wait_for(cond, timeout, msg):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return check(False, f"timed out waiting: {msg}")


# ===================================================================== #
# leg 1: elastic trainer — drain/replan/checkpoint badput, all named    #
# ===================================================================== #
def _train_factory(mesh):
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    model = T.build("tiny", dropout=0.0, n_layers=1, d_model=32,
                    n_heads=2, d_ff=64, max_len=16, vocab_size=64)
    return SpmdTrainer(model, Adam(learning_rate=1e-3), mesh=mesh,
                       fsdp=False, seed=0)


def _train_batch(s):
    rs_ = np.random.RandomState(9000 + s)
    t = rs_.randint(0, 64, (8, 17))
    # pace the loop so the mid-run capacity shrink lands between
    # planning polls instead of racing the whole run
    time.sleep(0.02)
    return t[:, :-1], t[:, 1:]


def leg_train(out_dir, pool):
    import jax
    from bigdl_tpu.elastic import ElasticSupervisor

    train_dir = os.path.join(out_dir, "train")
    os.makedirs(train_dir, exist_ok=True)
    pool.claim("train", 4)
    cap = {"devs": list(jax.devices()[:4])}
    rec = Recorder(sinks=[JsonlSink(os.path.join(train_dir,
                                                 "elastic.jsonl"))],
                   annotate=False)
    sup = ElasticSupervisor(
        _train_factory, os.path.join(out_dir, "ck_train"), {"dp": 4},
        capacity_fn=lambda: list(cap["devs"]),
        recorder=rec, ckpt_every=T_CKPT_EVERY,
        replan_every=T_REPLAN_EVERY, min_axes={"dp": 1},
        shard_arrays=True, handle_sigterm=False)

    result = {}

    def run():
        result["losses"] = sup.run(_train_batch, steps=T_STEPS)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    # mid-run capacity breathing: shrink dp 4 -> 2, then regrow.  The
    # shrink must land while the step loop is RUNNING (the supervisor
    # reads capacity only at planning polls), so gate on its state,
    # not on wall-clock guesses
    wait_for(lambda: sup.state == "running" or not th.is_alive(),
             120.0, "first segment stepping")
    time.sleep(0.3)
    cap["devs"] = list(jax.devices()[:2])
    wait_for(lambda: rec.counter_value("elastic/shrinks") >= 1
             or not th.is_alive(), 120.0, "shrink observed")
    time.sleep(0.5)
    cap["devs"] = list(jax.devices()[:4])
    th.join(timeout=300.0)
    check(not th.is_alive(), "elastic run finished")
    check(len(result.get("losses") or []) == T_STEPS,
          f"trained {T_STEPS} steps through the capacity cycle")
    check(rec.counter_value("elastic/shrinks") >= 1,
          "capacity shrink replanned the mesh")

    led = rec.get_ledger()
    snap = led.snapshot() if led is not None else None
    check(snap is not None and snap["owned_s"] > 0.0,
          "trainer recorder carries a goodput ledger with owned time")
    if snap is not None:
        check(snap["conservation_error"] <= 0.01,
              f"trainer ledger conserves: buckets sum to owned within "
              f"1% (err {100 * snap['conservation_error']:.3f}%)")
        for bucket in ("goodput", "preemption_drain",
                       "preemption_replan", "checkpoint_blocking"):
            check(snap["buckets"][bucket] > 0.0,
                  f"train {bucket} device-seconds non-zero and named "
                  f"({snap['buckets'][bucket]:.4f} dev-s)")
    rec.flush()
    pool.release("train")
    return {"snap": snap, "train_dir": train_dir}


# ===================================================================== #
# leg 2: serving — failover, probe readmission, autoscale transfer      #
# ===================================================================== #
def leg_serve(out_dir, pool):
    serve_dir = os.path.join(out_dir, "serve")
    os.makedirs(serve_dir, exist_ok=True)
    model = T.build("tiny", dropout=0.0, n_layers=2, max_len=128)

    rs = build_decode_replica_set(
        model, 2, name="lm", engine_kw=ENGINE_KW,
        recorder=Recorder(sinks=[JsonlSink(
            os.path.join(serve_dir, "serve.jsonl"))], annotate=False),
        health_interval=0.05, probe_interval=0.05)
    engines = [rep.engine for rep in rs.replicas]

    def engine_factory():
        reg = ModelRegistry()
        reg.register("lm", model)
        eng = DecodeEngine(reg, "lm", recorder=Recorder(annotate=False),
                           **ENGINE_KW)
        engines.append(eng)
        return eng

    rs.warmup()
    rs.start()

    store = SeriesStore()
    ctl = AutoscaleController(
        rs, engine_factory,
        AutoscalePolicy(min_replicas=1, max_replicas=3,
                        occupancy_high=0.85, occupancy_low=0.3,
                        idle_ticks=1, cooldown_up=0.05,
                        cooldown_down=0.1, max_step=1),
        pool=pool, claimant="serve", store=store, member_name="serve")

    # -- traffic + a hard kill mid-flight: the failover path ---------- #
    rng = np.random.RandomState(3)
    faults.arm(f"serving.decode_step:delay:{STEP_PIN_MS}")
    futs = []
    try:
        for _ in range(24):
            plen = int(rng.randint(2, 9))
            futs.append(rs.submit(
                "lm", rng.randint(0, 256, plen).astype(np.int32)))
        time.sleep(0.25)        # both replicas mid-decode
        rs.kill(0)              # chaos: in-flight work must fail over
        wait_for(lambda: rs.recorder.get_ledger().snapshot()
                 ["buckets"]["failover"] > 0.0, 20.0,
                 "failover seconds booked on the set ledger")
    finally:
        faults.disarm()
    errors = []
    for f in futs:
        try:
            f.result(timeout=60.0)
        except Exception as e:
            errors.append(f"{type(e).__name__}: {e}")
    check(not errors,
          f"every request survived the kill via failover "
          f"(first error: {errors[:1]})")

    # -- autoscale peak/trough: transfer badput + probe readmission --- #
    ups = lambda: rs.recorder.counter_value("autoscale/scale_ups")
    downs = lambda: rs.recorder.counter_value("autoscale/scale_downs")

    def tick_until(counter, target, occupancy, msg, timeout=60.0):
        deadline = time.monotonic() + timeout
        while counter() < target and time.monotonic() < deadline:
            store.observe("decode/occupancy", occupancy)
            ctl.tick()
            time.sleep(0.05)
        return check(counter() >= target, msg)

    tick_until(ups, 1, 0.97,
               "peak claimed a pool device for a third replica")
    wait_for(lambda: sum(1 for h in rs.health().values()
                         if h["state"] == "healthy") >= 2,
             30.0, "joiner golden-probed into rotation")
    tick_until(downs, 1, 0.02,
               "trough drained the third replica back out")
    ctl.stop()

    set_snap = rs.recorder.get_ledger().snapshot()
    check(set_snap["conservation_error"] <= 0.01,
          f"set ledger conserves: buckets sum to owned within 1% "
          f"(err {100 * set_snap['conservation_error']:.3f}%)")
    for bucket in ("failover", "autoscale_transfer",
                   "probe_readmission"):
        check(set_snap["buckets"][bucket] > 0.0,
              f"serve {bucket} device-seconds non-zero and named "
              f"({set_snap['buckets'][bucket]:.6f} dev-s)")
    eng_snaps = [e.recorder.get_ledger().snapshot() for e in engines
                 if e.recorder.get_ledger() is not None]
    check(bool(eng_snaps) and all(
        s["conservation_error"] <= 0.01 for s in eng_snaps),
        f"every decode-engine ledger conserves within 1% "
        f"({len(eng_snaps)} engines)")
    check(sum(s["buckets"]["goodput"] for s in eng_snaps) > 0.0,
          "decode goodput (live-slot device-seconds) non-zero")
    check(sum(s["buckets"]["compile_warmup"] for s in eng_snaps) > 0.0,
          "decode compile/warmup badput non-zero and named")

    rs.recorder.flush()
    rs.shutdown(drain=False)
    return {"set": set_snap,
            "engines": {f"decode{i}": s
                        for i, s in enumerate(eng_snaps)},
            "serve_dir": serve_dir}


# ===================================================================== #
def main():
    out_dir = tempfile.mkdtemp(prefix="goodput_smoke_")
    print(f"# workdir {out_dir}", flush=True)
    # one shared pool; x0 is deliberately never claimed, so the
    # ownership ledger must report pool-idle seconds DISJOINT from any
    # job's badput
    pool = DevicePool(devices=["t0", "t1", "t2", "t3", "s0", "x0"])

    tr = leg_train(out_dir, pool)
    sv = leg_serve(out_dir, pool)

    # -- fleet roll-up: jobs + pool ownership, conservation asserted -- #
    jobs = {"train": tr["snap"], "serve": sv["set"]}
    jobs.update(sv["engines"])
    pool_snap = pool.goodput.snapshot()
    check(pool_snap["pool_idle_s"] > 0.0,
          f"unclaimed device accrued pool-idle seconds "
          f"({pool_snap['pool_idle_s']:.3f}), not job badput")
    roll = rollup(jobs, pool_snap)
    check(roll["conservation_error"] <= 0.01,
          f"fleet roll-up conserves within 1% "
          f"(err {100 * roll['conservation_error']:.3f}%)")
    doc_path = os.path.join(out_dir, "goodput.json")
    with open(doc_path, "w") as f:
        json.dump(roll, f)

    print("# --- trace_summary goodput ---", flush=True)
    ts = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "scripts", "trace_summary.py"),
         "goodput", doc_path],
        capture_output=True, text=True, timeout=120)
    print(ts.stdout, flush=True)
    check(ts.returncode == 0 and "goodput waterfall" in ts.stdout
          and "conservation error" in ts.stdout
          and "top gap" in ts.stdout,
          "trace_summary goodput renders the waterfall")

    summary = {
        "metric": "goodput_smoke",
        "ok": not FAILURES,
        "failures": FAILURES,
        "train_goodput_fraction": round(
            (tr["snap"] or {}).get("goodput_fraction", 0.0), 4),
        "fleet_goodput_fraction": round(roll["goodput_fraction"], 4),
        "pool_idle_s": round(roll["pool_idle_s"], 3),
        "conservation_error": round(roll["conservation_error"], 5),
        "goodput_doc": doc_path,
        "workdir": out_dir,
    }
    print(json.dumps(summary), flush=True)
    return 0 if not FAILURES else 1


if __name__ == "__main__":
    sys.exit(main())
