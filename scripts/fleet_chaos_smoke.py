#!/usr/bin/env python
"""Fleet chaos smoke (ISSUE 11 acceptance, CI `fleet-chaos-smoke` job):
two jobs contend for one 8-device CPU pool and every survival claim is
asserted, not assumed.

Matrix (each case a subprocess with its own fault env):

  solo_a       high-priority job A alone on the pool (dp4, 10 steps)
  solo_b       low-priority job B alone on the pool (dp4, 60 steps,
               hang-abort armed but never fired)
  contention   B admitted first and running; A admitted mid-run with
               higher priority → the scheduler PREEMPTS B off its
               devices (same-size displacement: drain → commit →
               rebuild on the other half of the pool → resume).  After
               A completes, a ``step.dispatch:delay:300000@0`` fault
               wedges B's next step for 5 minutes; the watchdog
               hang-abort fires EXACTLY ONCE, the supervisor replans,
               and B resumes and completes.

Asserted per the acceptance bar:

  1. completion-in-time — the parent timeout (280s) is far under the
     300s injected delay, so a waited-out wedge cannot pass;
  2. the fault FIRED exactly once (``faults.injected_total``) and the
     abort happened exactly once (``elastic/hang_aborts``);
  3. B's final committed params are BIT-IDENTICAL to its unfaulted
     solo run, and A's to *its* solo run — displacement and same-mesh
     resume are the bit-exact forms of preemption (a *shrink* changes
     partition counts and drifts at the last ulp by the documented
     checkpointing taxonomy; the shrink path is covered by
     tests/test_fleet.py's contention matrix with that taxonomy);
  4. no job was killed by a fleet decision: both complete,
     ``fleet/failed`` == 0.

All three cases share the repo's one persistent compile cache
(``utils.engine.enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
where set, else ``<checkout>/.jax_cache``), so the contention case's
displacement rebuilds warm-start from the solo runs' compiles — the
fleet's re-placement cost claim, exercised on every CI run.

Usage: python scripts/fleet_chaos_smoke.py           # run the matrix
       python scripts/fleet_chaos_smoke.py --worker <case>   # internal
"""
import json
import os
import subprocess
import sys
import time

_SCRIPTS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_SCRIPTS)
for _p in (_REPO, _SCRIPTS):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# ONE definition of the bit-identity digest: both chaos matrices must
# share the same notion of "bit-identical final params"
from chaos_smoke import _digest      # noqa: E402

_A_STEPS = 10
_B_STEPS = 60
_WEDGE_MS = 300_000         # far past the parent timeout: must be aborted
_CONTENTION_TIMEOUT = 280


def _ckpt_digest(ckpt_dir) -> str:
    from bigdl_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(ckpt_dir)
    kind, trees, meta = mgr.restore_latest()
    mgr.close()
    return _digest(trees)


def _factory(mesh):
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    model = T.build("tiny", dropout=0.0, n_layers=1, d_model=32,
                    n_heads=2, d_ff=64, max_len=16, vocab_size=64)
    return SpmdTrainer(model, Adam(learning_rate=1e-3), mesh=mesh,
                       fsdp=False, seed=0)


def _batch_a(s):
    import numpy as np
    rs = np.random.RandomState(9000 + s)
    t = rs.randint(0, 64, (8, 17))
    return t[:, :-1], t[:, 1:]


def _batch_b(s):
    import numpy as np
    rs = np.random.RandomState(5000 + s)
    t = rs.randint(0, 64, (8, 17))
    return t[:, :-1], t[:, 1:]


def _admit_a(fl, work_dir, rec_a):
    return fl.admit("a", _factory, {"dp": 4}, steps=_A_STEPS,
                    batch_fn=_batch_a, priority=1, recorder=rec_a,
                    ckpt_dir=os.path.join(work_dir, "ck_a"),
                    ckpt_every=5, handle_sigterm=False,
                    backoff_base=0.05)


def _admit_b(fl, work_dir, rec_b):
    from bigdl_tpu.observability.health import StallWatchdog
    wd = StallWatchdog(rec_b, factor=3.0, min_history=4,
                       floor_seconds=0.6, poll_interval=0.05)
    return fl.admit("b", _factory, {"dp": 4}, steps=_B_STEPS,
                    batch_fn=_batch_b, priority=0, recorder=rec_b,
                    ckpt_dir=os.path.join(work_dir, "ck_b"),
                    ckpt_every=5, handle_sigterm=False,
                    backoff_base=0.05, hang_abort_grace=0.5,
                    watchdog=wd,
                    flight_dir=os.path.join(work_dir, "flight"))


def _emit(fl, rec, digests):
    import bigdl_tpu.faults as faults
    jobs = fl.jobs()
    out = {
        "digests": digests,
        "states": {name: j.state for name, j in jobs.items()},
        "fault_injected": faults.injected_total("step.dispatch"),
        "fleet": {k: rec.counter_value(k) for k in (
            "fleet/admitted", "fleet/placed", "fleet/preempted",
            "fleet/displaced", "fleet/regrown", "fleet/completed",
            "fleet/failed", "fleet/rejected")},
        "jobs": {name: {
            "hang_aborts": j.recorder.counter_value("elastic/hang_aborts"),
            "displaces": j.recorder.counter_value("elastic/displaces"),
            "resumes": j.recorder.counter_value("elastic/resumes"),
            "failures": j.recorder.counter_value("elastic/failures"),
        } for name, j in jobs.items()},
        # per-job goodput ledger snapshots (attached by the trainer's
        # set_telemetry): the parent asserts conservation and that the
        # preemption/checkpoint badput the matrix injects is named
        "goodput": {name: (j.recorder.get_ledger().snapshot()
                           if j.recorder.get_ledger() is not None
                           else None)
                    for name, j in jobs.items()},
    }
    print("FLEET_RESULT " + json.dumps(out), flush=True)


def worker(case, work_dir):
    import jax
    from bigdl_tpu.fleet import FleetScheduler
    from bigdl_tpu.observability import JsonlSink, Recorder
    from bigdl_tpu.utils.engine import enable_compile_cache

    def rec_for(name):
        return Recorder(sinks=[JsonlSink(
            os.path.join(work_dir, f"{name}.jsonl"))], annotate=False)

    rec = rec_for("fleet")
    fl = FleetScheduler(jax.devices()[:8], recorder=rec,
                        compile_cache_dir=enable_compile_cache(),
                        handle_sigterm=False)
    if case == "solo_a":
        _admit_a(fl, work_dir, rec_for("job_a"))
        fl.run(timeout=240)
        _emit(fl, rec, {"a": _ckpt_digest(os.path.join(work_dir,
                                                       "ck_a"))})
        return
    if case == "solo_b":
        _admit_b(fl, work_dir, rec_for("job_b"))
        fl.run(timeout=240)
        _emit(fl, rec, {"b": _ckpt_digest(os.path.join(work_dir,
                                                       "ck_b"))})
        return

    # -- contention -------------------------------------------------- #
    import bigdl_tpu.faults as faults
    rec_b = rec_for("job_b")
    b = _admit_b(fl, work_dir, rec_b)
    fl.start()
    deadline = time.time() + 120
    while rec_b.gauge_value("elastic/steps_done") < 4:
        if time.time() > deadline:
            raise SystemExit("b never reached step 4")
        time.sleep(0.1)
    # a higher-priority arrival: the scheduler preempts B off its
    # devices (displacement — B drains, commits, resumes on the other
    # half of the pool, bit-identically)
    a = _admit_a(fl, work_dir, rec_for("job_a"))
    # fresh budget: B's warmup above may have eaten most of the first
    # one on a cold-cache CI runner, and A still has to place, rebuild
    # B on the displaced half, compile, and run — the parent timeout
    # (280s, far under the 300s wedge) stays the completion-in-time bar
    deadline = time.time() + 120
    while fl.job("a").state != "completed":
        if time.time() > deadline:
            raise SystemExit("a never completed")
        if fl.job("a").state == "failed":
            raise SystemExit(f"a failed: {fl.job('a').error!r}")
        time.sleep(0.1)
    if not b.alive():
        raise SystemExit("b finished before the wedge could be armed; "
                         "grow _B_STEPS")
    # wedge B's next step far past the parent timeout: only the
    # watchdog hang-abort -> replan path can finish this run in time
    faults.arm(f"step.dispatch:delay:{_WEDGE_MS}@0")
    try:
        fl.wait(timeout=220)
    finally:
        faults.disarm()
    _emit(fl, rec, {"a": _ckpt_digest(os.path.join(work_dir, "ck_a")),
                    "b": _ckpt_digest(os.path.join(work_dir, "ck_b"))})


def _run_case(name, tmp, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.pop("BIGDL_FAULT", None)
    work = os.path.join(tmp, name)
    os.makedirs(work, exist_ok=True)
    print(f"[fleet-chaos] {name} ...", flush=True)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", name,
         "--dir", work],
        env=env, capture_output=True, text=True, timeout=timeout)
    wall = time.time() - t0
    if proc.returncode != 0:
        print(proc.stdout[-4000:])
        print(proc.stderr[-4000:])
        raise SystemExit(f"[fleet-chaos] {name}: worker "
                         f"rc={proc.returncode}")
    for line in proc.stdout.splitlines():
        if line.startswith("FLEET_RESULT "):
            out = json.loads(line[len("FLEET_RESULT "):])
            out["wall_s"] = round(wall, 1)
            print(f"[fleet-chaos] {name} done in {wall:.1f}s", flush=True)
            return out
    print(proc.stdout[-4000:])
    raise SystemExit(f"[fleet-chaos] {name}: no FLEET_RESULT line")


def _require(name, cond, msg):
    if not cond:
        raise SystemExit(f"[fleet-chaos] {name}: FAILED — {msg}")


def main():
    import argparse
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker",
                    choices=["solo_a", "solo_b", "contention"])
    ap.add_argument("--dir")
    args = ap.parse_args()
    if args.worker:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        jax.config.update("jax_platforms", "cpu")
        worker(args.worker, args.dir)
        return

    tmp = tempfile.mkdtemp(prefix="fleet_chaos_")
    solo_a = _run_case("solo_a", tmp, 300)
    solo_b = _run_case("solo_b", tmp, 300)
    for name, solo in (("solo_a", solo_a), ("solo_b", solo_b)):
        _require(name, solo["fault_injected"] == 0,
                 "solo baselines must run fault-free")
        _require(name, solo["fleet"]["fleet/failed"] == 0, "job failed")

    cont = _run_case("contention", tmp, _CONTENTION_TIMEOUT)
    _require("contention", cont["fault_injected"] == 1,
             "the step.dispatch wedge must fire exactly once")
    _require("contention", cont["jobs"]["b"]["hang_aborts"] == 1,
             "hang-abort must fire exactly once")
    _require("contention", cont["jobs"]["b"]["resumes"] >= 2,
             "b must resume after displacement AND after the abort")
    _require("contention", cont["fleet"]["fleet/displaced"] >= 1,
             "the arrival must preempt b off its devices")
    _require("contention",
             cont["fleet"]["fleet/completed"] == 2
             and cont["fleet"]["fleet/failed"] == 0
             and cont["states"] == {"a": "completed", "b": "completed"},
             "no job may be killed by a fleet decision")
    _require("contention",
             cont["digests"]["a"] == solo_a["digests"]["a"],
             "high-priority job's params diverged from its solo run")
    _require("contention",
             cont["digests"]["b"] == solo_b["digests"]["b"],
             "preempted job's params diverged from its solo run")

    # goodput ledgers: every job's buckets must sum to its owned
    # device-seconds within 1%, and the badput the contention case
    # injects — B's preemption drain + replan, the checkpoint copies —
    # must land in its own named bucket, not vanish into idle
    for jname in ("a", "b"):
        led = (cont.get("goodput") or {}).get(jname)
        _require("contention", led is not None and led["owned_s"] > 0,
                 f"job {jname} carries a goodput ledger with owned time")
        _require("contention", led["conservation_error"] <= 0.01,
                 f"job {jname} ledger conservation: buckets sum to "
                 f"owned within 1% (err "
                 f"{100 * led['conservation_error']:.3f}%)")
    b_led = cont["goodput"]["b"]
    for bucket in ("preemption_drain", "preemption_replan",
                   "checkpoint_blocking", "goodput"):
        _require("contention", b_led["buckets"][bucket] > 0.0,
                 f"b's {bucket} device-seconds must be non-zero "
                 f"(got {b_led['buckets'][bucket]!r})")

    # the timeline must render: the trace_summary fleet view over the
    # contention case's per-recorder JSONL streams
    render = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "scripts", "trace_summary.py"), "fleet",
         os.path.join(tmp, "contention")],
        capture_output=True, text=True, timeout=60)
    _require("render", render.returncode == 0
             and "fleet timeline" in render.stdout
             and "displaced" in render.stdout,
             f"trace_summary fleet failed: {render.stdout[-500:]}"
             f"{render.stderr[-500:]}")
    print(render.stdout)

    print("[fleet-chaos] all cases green: contention displaced the "
          "low-priority job, the wedge hang-aborted once, both jobs "
          "finished bit-identical to their solo runs", flush=True)
    print(json.dumps({"solo_a": solo_a, "solo_b": solo_b,
                      "contention": cont}, indent=2))


if __name__ == "__main__":
    main()
