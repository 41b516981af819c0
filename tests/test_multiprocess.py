"""Two-process distributed execution.

Spawns 2 OS processes that form a jax.distributed cluster on CPU
(2 procs x 4 virtual devices = global dp=8 mesh), runs DistriOptimizer
through `parallel.mesh.init_distributed`, and asserts the trained
parameters match a single-process dp=8 run of the same fixture exactly
(same SPMD program, different process topology
— ≙ optim/DistriOptimizer.scala:118 cluster vs local parity).
"""
import os
import socket
import subprocess
import sys

import numpy as np
import jax
import pytest

from bigdl_tpu import nn
from bigdl_tpu.optim import SGD, Trigger
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib

_WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_reference(fsdp=False):
    """The worker fixture, trained in-process on the 8-device mesh."""
    rng = np.random.RandomState(0)
    x = rng.randn(256, 12).astype(np.float32)
    w = rng.randn(12, 1).astype(np.float32)
    y = (x @ w + 0.01 * rng.randn(256, 1)).astype(np.float32)
    model = nn.Sequential(nn.Linear(12, 8), nn.Tanh(), nn.Linear(8, 1))
    model.reset(3)
    mesh = mesh_lib.create_mesh({"dp": 8})
    opt = (DistriOptimizer(model, (x, y), nn.MSECriterion(), batch_size=64,
                           mesh=mesh, fsdp=fsdp)
           .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
           .set_end_when(Trigger.max_epoch(2)))
    trained = opt.optimize()
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, trained._params))]


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)           # worker sets its own 4-dev flag
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    return env


def _spawn_workers(port, out, extra=()):
    env = _worker_env()
    return [subprocess.Popen(
        [sys.executable, _WORKER, str(i), "2", str(port), out, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(2)]


def _run_two_procs(tmp_path, extra=()):
    out = str(tmp_path / "mp_params.npz")
    procs = _spawn_workers(_free_port(), out, extra)
    logs = []
    for p in procs:
        try:
            o, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("two-process run timed out")
        logs.append(o)
    for i, (p, o) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, f"proc {i} failed:\n{o[-3000:]}"
    assert os.path.exists(out), logs[0][-2000:]
    got = np.load(out)
    return [got[k] for k in got.files]


@pytest.mark.slow
def test_worker_death_resume_matches_uninterrupted(tmp_path):
    """Fault injection end-to-end (≙ DistriOptimizer.scala:878-914
    drop-and-retry): worker 1 dies UNCLEANLY (os._exit) mid-training,
    the wedged survivor is killed, the cluster restarts, both workers
    auto-resume from their newest checkpoints, and the final params
    match the uninterrupted two-process run exactly."""
    import time

    out = str(tmp_path / "resumed.npz")
    ckpt = str(tmp_path / "ckpt")

    # ---- phase 1: crash run — proc 1 os._exits at iteration 7 -------- #
    # (4 iters/epoch, 3 epochs = 12 total; checkpoints every 2)
    procs = _spawn_workers(_free_port(), out,
                           (f"ckpt={ckpt}", "crash_at=7", "epochs=3"))
    try:
        o1, _ = procs[1].communicate(timeout=420)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        pytest.fail("crashing worker did not die")
    assert procs[1].returncode == 17, f"proc1:\n{o1[-2000:]}"
    # the survivor is wedged in a collective whose peer vanished — give
    # it a moment, then kill it like a job scheduler would
    time.sleep(3)
    procs[0].kill()
    o0, _ = procs[0].communicate()
    assert not os.path.exists(out), "crashed run must not publish params"
    assert os.path.exists(os.path.join(ckpt, "p0", "latest")), o0[-2000:]
    assert os.path.exists(os.path.join(ckpt, "p1", "latest")), o1[-2000:]

    # ---- phase 2: restart the cluster; both workers resume ----------- #
    procs = _spawn_workers(_free_port(), out,
                           (f"ckpt={ckpt}", "epochs=3"))
    logs = []
    for p in procs:
        try:
            o, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("resume run timed out")
        logs.append(o)
    for i, (p, o) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, f"resume proc {i} failed:\n{o[-3000:]}"
    got = np.load(out)
    got_leaves = [got[k] for k in got.files]

    # ---- uninterrupted reference: plain 2-proc run, same epochs ------ #
    want_leaves = _run_two_procs(tmp_path, extra=("epochs=3",))
    assert len(got_leaves) == len(want_leaves)
    for a, b in zip(want_leaves, got_leaves):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.slow
@pytest.mark.parametrize("fsdp", [False, True], ids=["dp", "fsdp"])
def test_two_process_matches_single(tmp_path, fsdp):
    """dp: replicated params, psum gradients. fsdp: params/opt-state
    sharded over the GLOBAL dp axis spanning both OS processes
    (all_gather/psum_scatter riding the inter-process transport).
    Either way the trained params must match the in-process dp=8 run."""
    got_leaves = _run_two_procs(tmp_path, extra=("fsdp",) if fsdp else ())
    want_leaves = _single_process_reference(fsdp=fsdp)
    assert len(got_leaves) == len(want_leaves)
    for a, b in zip(want_leaves, got_leaves):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
