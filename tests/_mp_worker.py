"""Worker for tests/test_multiprocess.py: one of N jax.distributed
processes on CPU (4 local virtual devices each), training the shared
fixture model with DistriOptimizer over the global dp mesh
(≙ a Spark executor in optim/DistriOptimizer.scala:118's cluster run).

Usage: python _mp_worker.py <proc_id> <num_procs> <port> <out.npz>
           [fsdp] [ckpt=<dir>] [crash_at=<iter>] [epochs=<n>]

`ckpt=` enables per-process checkpoints (dir/p<pid>) every 2 iterations
and auto-resume when they already exist; `crash_at=` makes proc 1 die
UNCLEANLY (os._exit) at that iteration — the fault-injection fixture
(≙ DistriOptimizer.scala:878-914 drop-and-retry, demonstrated across OS
processes)."""
import os
import sys


def main():
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    port, out = sys.argv[3], sys.argv[4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax

    from bigdl_tpu.parallel.mesh import init_distributed, create_mesh
    init_distributed(f"127.0.0.1:{port}", num_processes=nproc,
                     process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.local_device_count() == 4, jax.local_device_count()
    assert jax.device_count() == 4 * nproc, jax.device_count()

    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

    # identical fixture on every process (deterministic seeds)
    rng = np.random.RandomState(0)
    x = rng.randn(256, 12).astype(np.float32)
    w = rng.randn(12, 1).astype(np.float32)
    y = (x @ w + 0.01 * rng.randn(256, 1)).astype(np.float32)
    model = nn.Sequential(nn.Linear(12, 8), nn.Tanh(), nn.Linear(8, 1))
    model.reset(3)

    extra = sys.argv[5:]
    fsdp = "fsdp" in extra
    ckpt = next((a.split("=", 1)[1] for a in extra
                 if a.startswith("ckpt=")), None)
    crash_at = next((int(a.split("=", 1)[1]) for a in extra
                     if a.startswith("crash_at=")), None)
    epochs = next((int(a.split("=", 1)[1]) for a in extra
                   if a.startswith("epochs=")), 2)

    mesh = create_mesh({"dp": 4 * nproc})
    end = Trigger.max_epoch(epochs)
    if crash_at is not None and pid == 1:
        # die UNCLEANLY mid-training: evaluated once per iteration, so
        # the step at `crash_at` completes and then this worker vanishes
        # without any shutdown — the peer wedges in its next collective
        base = end

        class _CrashAt(Trigger):
            def __call__(self, state):
                if state.iteration >= crash_at:
                    print(f"proc {pid}: injecting crash at iteration "
                          f"{state.iteration}", flush=True)
                    os._exit(17)
                return base(state)

        end = _CrashAt()
    opt = (DistriOptimizer(model, (x, y), nn.MSECriterion(), batch_size=64,
                           mesh=mesh, fsdp=fsdp)
           .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
           .set_end_when(end))
    if ckpt:
        opt.set_checkpoint(os.path.join(ckpt, f"p{pid}"),
                           trigger=Trigger.several_iteration(2))
    trained = opt.optimize()

    leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, trained._params))]
    if pid == 0:
        np.savez(out, *leaves)
    print(f"proc {pid}: done, {len(leaves)} param leaves", flush=True)


if __name__ == "__main__":
    main()
