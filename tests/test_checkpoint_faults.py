"""Crash-consistency fault injection: REAL subprocess kills mid-write.

Each test runs tests/_ckpt_worker.py with BIGDL_CKPT_FAULT armed so the
checkpoint writer hard-kills the process (os._exit) at a configured
byte offset — mid-shard, between shards and manifest, or mid-manifest —
then re-runs the worker to resume and asserts the final parameters are
BIT-IDENTICAL to an uninterrupted run.  That is the acceptance property
of the commit protocol: a checkpoint without a valid manifest does not
exist, and resume always lands on the newest intact one.

The preemption test sends a real SIGTERM instead and asserts a clean
exit with a final committed checkpoint.

The ELASTIC matrix (slow: each leg compiles the GSPMD trainer in a
fresh subprocess) kills a run on mesh A and resumes it on mesh B —
SIGTERM preemption and mid-write kills both — asserting the loss
curve CONTINUES across the reshard and no torn state survives.
"""
import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from bigdl_tpu.checkpoint import read_manifest, scan
from bigdl_tpu.checkpoint.faults import ENV_VAR, KILL_EXIT_CODE

_WORKER = os.path.join(os.path.dirname(__file__), "_ckpt_worker.py")

# worker config: 9 iterations, checkpoints at 2,4,6,8 (+ epoch-end at 8)
_ITERS = "iters=9"


def _worker_env(fault=None):
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop(ENV_VAR, None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    if fault is not None:
        env[ENV_VAR] = fault
    return env


def _run_worker(ckpt, out, *args, fault=None, timeout=300, check_rc=None):
    p = subprocess.run(
        [sys.executable, _WORKER, str(ckpt), str(out), _ITERS, *args],
        env=_worker_env(fault), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=timeout)
    if check_rc is not None:
        assert p.returncode == check_rc, \
            f"rc={p.returncode}, wanted {check_rc}\n{p.stdout}"
    return p


def _params(out):
    with np.load(str(out)) as z:
        return [z[k] for k in z.files]


def _assert_bit_identical(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """Uninterrupted 9-iteration run: the ground-truth final params."""
    d = tmp_path_factory.mktemp("baseline")
    out = d / "params.npz"
    _run_worker(d / "ck", out, check_rc=0)
    return _params(out)


def test_kill_mid_shard_resumes_from_last_good(tmp_path, baseline):
    """Kill 64 bytes into a shard of the SECOND checkpoint save
    (iteration 4): the torn save must be invisible, resume starts from
    the intact iteration-2 checkpoint, and the rerun's final params are
    bit-identical to the uninterrupted run."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    p = _run_worker(ck, out, fault="1:bytes:64", check_rc=KILL_EXIT_CODE)
    assert not out.exists()              # really died mid-run
    intact = [m.meta["iteration"] for _, m in scan(str(ck))]
    assert intact == [2], f"only iteration 2 should be committed: {intact}"
    # the torn directory exists but has no manifest: it does not exist
    # as a checkpoint
    torn = [d for d in os.listdir(ck) if d.startswith("ckpt_")
            and not os.path.exists(os.path.join(ck, d, "MANIFEST.json"))]
    assert torn, "expected a torn manifest-less directory from the kill"
    r = _run_worker(ck, out, check_rc=0)
    assert "RESUME iteration=2" in r.stdout, r.stdout
    _assert_bit_identical(_params(out), baseline)


def test_kill_between_shards_and_manifest(tmp_path, baseline):
    """All shards of the iteration-4 save are durable, the manifest is
    not: the checkpoint still does not exist."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    _run_worker(ck, out, fault="1:pre_manifest", check_rc=KILL_EXIT_CODE)
    intact = [m.meta["iteration"] for _, m in scan(str(ck))]
    assert intact == [2], intact
    r = _run_worker(ck, out, check_rc=0)
    assert "RESUME iteration=2" in r.stdout, r.stdout
    _assert_bit_identical(_params(out), baseline)


def test_kill_mid_manifest(tmp_path, baseline):
    """Kill 10 bytes into the manifest TMP write of the third save
    (iteration 6): os.replace never ran, so the half-written manifest
    is not visible under its committed name."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    _run_worker(ck, out, fault="2:manifest:10", check_rc=KILL_EXIT_CODE)
    intact = [m.meta["iteration"] for _, m in scan(str(ck))]
    assert intact == [2, 4], intact
    r = _run_worker(ck, out, check_rc=0)
    assert "RESUME iteration=4" in r.stdout, r.stdout
    _assert_bit_identical(_params(out), baseline)


def test_kill_first_save_resumes_from_scratch(tmp_path, baseline):
    """Torn very first checkpoint: nothing intact exists, the rerun
    starts from scratch — and still matches the uninterrupted run."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    _run_worker(ck, out, fault="0:bytes:0", check_rc=KILL_EXIT_CODE)
    assert scan(str(ck)) == []
    r = _run_worker(ck, out, check_rc=0)
    assert "RESUME" not in r.stdout
    _assert_bit_identical(_params(out), baseline)


def _run_spmd(ck, out, mesh, *args, fault=None, timeout=600,
              check_rc=None):
    return _run_worker(ck, out, "spmd", f"mesh={mesh}", "ckpt_every=2",
                       *args, fault=fault, timeout=timeout,
                       check_rc=check_rc)


def _spmd_results(out):
    """(params leaves, losses) from an spmd worker's npz."""
    with np.load(str(out)) as z:
        return ([z[k] for k in z.files if k != "losses"], z["losses"])


@pytest.mark.slow
def test_spmd_sigterm_then_resume_on_reshaped_mesh(tmp_path):
    """SIGTERM a dp4 run mid-training, then resume it on dp2×fsdp2 —
    same 4 partitions, relaid axes, fixed global batch: the reshard is
    same-math AND bit-exact on this backend, so the resumed run's loss
    curve and final params must equal an uninterrupted dp4 run's, bit
    for bit, and no torn state may survive."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    ref = tmp_path / "ref.npz"
    _run_spmd(tmp_path / "ck_ref", ref, "dp4", "iters=10", check_rc=0)
    base_params, base_losses = _spmd_results(ref)
    assert len(base_losses) == 10

    p = subprocess.Popen(
        [sys.executable, _WORKER, str(ck), str(out), _ITERS, "spmd",
         "mesh=dp4", "ckpt_every=2", "preempt", "step_sleep=50"],
        env=_worker_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 300
        for line in p.stdout:
            if line.startswith("iter 4") or time.time() > deadline:
                break
        p.send_signal(signal.SIGTERM)
        rest = p.communicate(timeout=300)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, f"preempted worker must exit cleanly:\n{rest}"
    assert "final checkpoint" in rest
    cands = scan(str(ck))
    assert cands, "no committed checkpoint after preemption"
    newest = cands[-1][1]
    assert newest.tag.startswith("preempt_step_"), newest.tag
    assert newest.mesh is not None and newest.mesh["axes"] == [["dp", 4]]
    k = newest.meta["step"]
    assert k >= 4

    r = _run_spmd(ck, out, "dp2,fsdp2", "iters=10", check_rc=0)
    assert f"RESUME step={k}" in r.stdout, r.stdout
    assert "[elastic] resharded" in r.stdout, r.stdout
    params, losses = _spmd_results(out)
    # loss-curve continuation: the resumed segment reproduces the
    # uninterrupted run's tail exactly
    np.testing.assert_array_equal(losses, base_losses[k:])
    _assert_bit_identical(params, base_params)


@pytest.mark.slow
def test_spmd_sigterm_tp_dp_elastic_resume_shrinks_cheapest_axis(tmp_path):
    """The composed-mesh elastic leg: SIGTERM a dp4×tp2 job mid-run,
    replan onto HALF the devices, and resume.  plan_mesh's per-axis
    shrink costs must choose the dp axis (cheap re-batching) over tp (a
    model-entangled re-partition) — dp2×tp2, never dp4×tp1 — and the
    resumed run must continue within the documented taxonomy: dp 4→2
    halves the partitions of every dp reduction, so the curve/params
    are same-math tight-allclose (tp unchanged keeps its layout), not
    bit-exact."""
    from bigdl_tpu.elastic import plan_mesh
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    ref = tmp_path / "ref.npz"
    _run_spmd(tmp_path / "ck_ref", ref, "dp4,tp2", "iters=10",
              "shard_arrays", check_rc=0)
    base_params, base_losses = _spmd_results(ref)
    assert len(base_losses) == 10

    p = subprocess.Popen(
        [sys.executable, _WORKER, str(ck), str(out), _ITERS, "spmd",
         "mesh=dp4,tp2", "shard_arrays", "ckpt_every=2", "preempt",
         "step_sleep=50"],
        env=_worker_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 300
        for line in p.stdout:
            if line.startswith("iter 4") or time.time() > deadline:
                break
        p.send_signal(signal.SIGTERM)
        rest = p.communicate(timeout=300)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, f"preempted worker must exit cleanly:\n{rest}"
    cands = scan(str(ck))
    assert cands, "no committed checkpoint after preemption"
    newest = cands[-1][1]
    assert newest.mesh is not None \
        and newest.mesh["axes"] == [["dp", 4], ["tp", 2]]
    k = newest.meta["step"]
    assert k >= 4

    # the supervisor's choice on 4 surviving devices: shrink the CHEAP
    # axis — tp keeps its floor'd full size, dp halves
    resume_axes = plan_mesh(4, {"dp": 4, "tp": 2}, {"tp": 2})
    assert resume_axes == {"dp": 2, "tp": 2}, resume_axes
    mesh_arg = ",".join(f"{a}{s}" for a, s in resume_axes.items())
    r = _run_spmd(ck, out, mesh_arg, "iters=10", "shard_arrays",
                  check_rc=0)
    assert f"RESUME step={k}" in r.stdout, r.stdout
    assert "[elastic] resharded" in r.stdout, r.stdout
    params, losses = _spmd_results(out)
    np.testing.assert_allclose(losses, base_losses[k:], rtol=1e-4)
    for a, b in zip(params, base_params):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


@pytest.mark.slow
def test_spmd_kill_mid_write_then_resume_on_smaller_mesh(tmp_path):
    """Hard-kill a dp4 run 64 bytes into a slice shard of its second
    save, then resume on HALF the devices (dp2).  The torn save must be
    invisible, resume starts from the intact step-2 checkpoint and
    reshards 4→2; a device-count change reassociates float reductions,
    so continuation is same-math (tight allclose), not bit-exact —
    exactly what docs/checkpointing.md promises."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    ref = tmp_path / "ref.npz"
    _run_spmd(tmp_path / "ck_ref", ref, "dp4", "iters=8", "shard_arrays",
              check_rc=0)
    base_params, base_losses = _spmd_results(ref)

    _run_spmd(ck, out, "dp4", "iters=8", "shard_arrays",
              fault="1:bytes:64", check_rc=KILL_EXIT_CODE)
    assert not out.exists()
    intact = [m.meta["step"] for _, m in scan(str(ck))]
    assert intact == [2], f"only step 2 should be committed: {intact}"
    torn = [d for d in os.listdir(ck) if d.startswith("ckpt_")
            and not os.path.exists(os.path.join(ck, d, "MANIFEST.json"))]
    assert torn, "expected a torn manifest-less directory from the kill"

    r = _run_spmd(ck, out, "dp2", "iters=8", "shard_arrays", check_rc=0)
    assert "RESUME step=2" in r.stdout, r.stdout
    assert "[elastic] resharded" in r.stdout, r.stdout
    params, losses = _spmd_results(out)
    np.testing.assert_allclose(losses, base_losses[2:], rtol=1e-4)
    for a, b in zip(params, base_params):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def _ledger_entries(path):
    """Parsed ledger lines; a SIGKILL-torn final line is skipped (it
    belongs to a batch whose step never happened)."""
    entries = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return entries


def _ledger_ids(entries, max_tag=None):
    return [i for e in entries
            if max_tag is None or e["tag"] <= max_tag
            for i in e["ids"]]


def _kill_worker_at(args, iter_line, sig=signal.SIGKILL, timeout=300,
                    manifest_dir=None):
    """Run the worker, hard-kill it once `iter <n>` appears on stdout;
    returns collected stdout.  ``manifest_dir`` additionally waits for
    at least one COMMITTED checkpoint manifest before killing — the
    async writer races the kill otherwise, and a run killed before its
    first commit has nothing to resume (a test-setup race, not the
    property under test)."""
    p = subprocess.Popen([sys.executable, _WORKER, *args],
                         env=_worker_env(), stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + timeout
        for line in p.stdout:
            if line.startswith(f"iter {iter_line}") \
                    or time.time() > deadline:
                break
        if manifest_dir is not None:
            while time.time() < deadline and p.poll() is None:
                if glob.glob(os.path.join(str(manifest_dir), "ckpt_*",
                                          "MANIFEST.json")):
                    break
                time.sleep(0.05)
        p.send_signal(sig)
        rest = p.communicate(timeout=timeout)[0]
    finally:
        if p.poll() is None:
            p.kill()
    return rest, p.returncode


def test_sigkill_data_cursor_resume_exact_sample_stream(tmp_path):
    """SIGKILL mid-epoch with the sharded streaming pipeline: the data
    cursor in the last committed checkpoint re-positions the stream, so
    ledger(run1 up to the resume iteration) + ledger(run2) must be
    BIT-IDENTICAL to the uninterrupted run's sample-ID stream — no
    sample re-seen, none skipped — and the final params match too."""
    data_dir = str(tmp_path / "shards")
    # 160 records / batch 16 = 10 batches per epoch; 14 iterations
    # cross the epoch boundary mid-epoch-2
    ref_out = tmp_path / "ref.npz"
    _run_worker(tmp_path / "ck_ref", ref_out, "data_cursor",
                f"data_dir={data_dir}", "iters=14", check_rc=0)
    ref_ids = _ledger_ids(_ledger_entries(str(ref_out) + ".ledger.jsonl"))
    assert len(ref_ids) == 14 * 16

    ck = tmp_path / "ck"
    killed = tmp_path / "killed.npz"
    _, rc = _kill_worker_at(
        [str(ck), str(killed), _ITERS, "data_cursor",
         f"data_dir={data_dir}", "iters=14", "step_sleep=25"],
        iter_line=6, manifest_dir=ck)
    assert rc == -signal.SIGKILL, rc
    assert not killed.exists()
    run1 = _ledger_entries(str(killed) + ".ledger.jsonl")
    assert run1, "killed run pulled no batches?"

    resumed = tmp_path / "resumed.npz"
    r = _run_worker(ck, resumed, "data_cursor", f"data_dir={data_dir}",
                    "iters=14", check_rc=0)
    m = [l for l in r.stdout.splitlines() if l.startswith("RESUME")]
    assert m, f"resume did not restore a checkpoint:\n{r.stdout}"
    resume_iter = int(m[0].split("iteration=")[1].split()[0])
    assert 0 < resume_iter < 14
    run2 = _ledger_entries(str(resumed) + ".ledger.jsonl")
    spliced = _ledger_ids(run1, max_tag=resume_iter) + _ledger_ids(run2)
    assert spliced == ref_ids, (
        f"sample stream diverged after SIGKILL-resume at iteration "
        f"{resume_iter}: {len(spliced)} vs {len(ref_ids)} ids")
    _assert_bit_identical(_params(resumed), _params(ref_out))


@pytest.mark.slow
def test_spmd_sigkill_data_cursor_dp4_to_dp2(tmp_path):
    """The elastic variant: SIGKILL a dp4 run fed by the streaming
    pipeline, resume on dp2.  The pipeline feeds the GLOBAL batch, so
    the cursor is mesh-independent and the spliced sample-ID stream
    must equal the uninterrupted dp4 run's bit for bit."""
    data_dir = str(tmp_path / "shards")
    ref_out = tmp_path / "ref.npz"
    _run_spmd(tmp_path / "ck_ref", ref_out, "dp4", "data",
              f"data_dir={data_dir}", "iters=10", check_rc=0)
    ref_ids = _ledger_ids(_ledger_entries(str(ref_out) + ".ledger.jsonl"))
    assert len(ref_ids) == 10 * 8

    ck = tmp_path / "ck"
    killed = tmp_path / "killed.npz"
    _, rc = _kill_worker_at(
        [str(ck), str(killed), _ITERS, "spmd", "mesh=dp4",
         "ckpt_every=2", "data", f"data_dir={data_dir}", "iters=10",
         "step_sleep=50"],
        iter_line=5, timeout=600, manifest_dir=ck)
    assert rc == -signal.SIGKILL, rc
    run1 = _ledger_entries(str(killed) + ".ledger.jsonl")
    assert run1

    resumed = tmp_path / "resumed.npz"
    r = _run_spmd(ck, resumed, "dp2", "data", f"data_dir={data_dir}",
                  "iters=10", check_rc=0)
    m = [l for l in r.stdout.splitlines() if l.startswith("RESUME")]
    assert m, f"resume did not restore a checkpoint:\n{r.stdout}"
    resume_step = int(m[0].split("step=")[1].split()[0])
    assert 0 < resume_step < 10
    assert "[elastic] resharded" in r.stdout, r.stdout
    run2 = _ledger_entries(str(resumed) + ".ledger.jsonl")
    # spmd tags are step indices: run1 consumed steps 0..k-1, run2
    # starts at k — strictly-below splice (local mode is 1-based)
    spliced = _ledger_ids(run1, max_tag=resume_step - 1) \
        + _ledger_ids(run2)
    assert spliced == ref_ids, (
        f"dp4→dp2 sample stream diverged at step {resume_step}: "
        f"{len(spliced)} vs {len(ref_ids)} ids")
    # the curve is same-math across a device-count change (reassociated
    # reductions): tight allclose, per docs/checkpointing.md
    _, losses = _spmd_results(resumed)
    _, ref_losses = _spmd_results(ref_out)
    np.testing.assert_allclose(losses, ref_losses[resume_step:],
                               rtol=1e-4)


def test_sigterm_preemption_commits_final_checkpoint(tmp_path):
    """Real SIGTERM mid-run: the worker finishes the in-flight write,
    commits a final checkpoint, exits 0 — and a resumed run continues
    to the same final state as a never-preempted run."""
    ck, out = tmp_path / "ck", tmp_path / "params.npz"
    p = subprocess.Popen(
        [sys.executable, _WORKER, str(ck), str(out), "iters=14",
         "preempt", "step_sleep=25"],
        env=_worker_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 120
        for line in p.stdout:
            if line.startswith("iter 6") or time.time() > deadline:
                break
        p.send_signal(signal.SIGTERM)
        rest = p.communicate(timeout=120)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, f"preempted worker must exit cleanly:\n{rest}"
    assert "final checkpoint" in rest
    cands = scan(str(ck))
    assert cands, "no committed checkpoint after preemption"
    newest = cands[-1][1]
    assert newest.tag.startswith("preempt_iter_"), newest.tag
    preempt_iter = newest.meta["iteration"]
    assert preempt_iter >= 6

    # resume to iteration 14, then compare against one uninterrupted run
    r = _run_worker(ck, out, "iters=14", check_rc=0)
    assert f"RESUME iteration={preempt_iter}" in r.stdout, r.stdout
    out_ref = tmp_path / "ref.npz"
    _run_worker(tmp_path / "ck_ref", out_ref, "iters=14", check_rc=0)
    _assert_bit_identical(_params(out), _params(out_ref))
