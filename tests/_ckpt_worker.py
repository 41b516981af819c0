"""Worker for tests/test_checkpoint_faults.py: one deterministic
training run with async manifest checkpointing, killable mid-write.

Usage: python _ckpt_worker.py <ckpt_dir> <out.npz> [iters=<n>]
           [ckpt_every=<n>] [preempt] [step_sleep=<ms>]
           [spmd] [mesh=dp4 | mesh=dp2,fsdp2] [shard_arrays]
           [data_cursor | data] [data_dir=<dir>]

`data_cursor` trains off the sharded streaming pipeline
(data/sharded.py) instead of the in-memory dataset: shard files are
(re)built deterministically in `data_dir`, the data cursor rides in
every checkpoint, and every batch's sample IDs are appended to
`<out>.ledger.jsonl` (fsync'd per line, so a SIGKILL can tear at most
the final line).  The parent splices crashed + resumed ledgers and
asserts the concatenated sample-ID stream is bit-identical to an
uninterrupted run's — no sample re-seen, none skipped.  `data` does
the same for the spmd mode (the dp4→dp2 elastic variant: the pipeline
feeds the GLOBAL batch, so the stream must be mesh-independent).

The parent arms BIGDL_CKPT_FAULT (see bigdl_tpu.checkpoint.faults) to
hard-kill this process at a byte offset inside a shard or manifest
write — exit code 42 marks the planned kill.  With `preempt` the worker
trains "forever", prints `iter <n>` each iteration, and expects the
parent's SIGTERM: the preemption handler commits a final checkpoint and
optimize() returns, after which the final params land in <out.npz> and
the worker exits 0.

Every run auto-resumes from whatever intact checkpoint the directory
holds, so the parent chains crashed runs and compares the final params
of crash+resume against an uninterrupted run — bit for bit.

`spmd` switches to the GSPMD trainer on an 8-virtual-device CPU mesh
shaped by `mesh=` (e.g. dp4, dp2,fsdp2) with a per-step STATELESS
batch generator (fixed GLOBAL batch whatever the mesh) — the elastic
matrix: the parent kills a run on mesh A and resumes it on mesh B,
asserting the loss curve continues.  `shard_arrays` saves elastic v2
slice shards instead of whole-tree shards.  <out.npz> gains a
`losses` array (the steps THIS run executed) next to the params.
"""
import os
import sys


def build_shards(data_dir, n_files=4, per_file=40, spmd=False):
    """Deterministic tfrecord shards, (re)created idempotently.

    Local mode: id(int32) + 10 float32 features (feature 0 carries the
    id so the ledger can read it off the batch).  Spmd mode: 17 int32
    tokens whose first two encode the id (vocab 64)."""
    import struct

    import numpy as np
    from bigdl_tpu.utils.tfrecord import write_tfrecords

    os.makedirs(data_dir, exist_ok=True)
    paths, gid = [], 0
    for f in range(n_files):
        p = os.path.join(data_dir, f"shard{f}.tfr")
        recs = []
        for _ in range(per_file):
            rs = np.random.RandomState(97 + gid)
            if spmd:
                toks = rs.randint(0, 64, 17).astype(np.int32)
                toks[0], toks[1] = gid // 64, gid % 64
                recs.append(toks.tobytes())
            else:
                x = rs.randn(10).astype(np.float32)
                x[0] = gid / 100.0
                recs.append(struct.pack("<i", gid) + x.tobytes())
            gid += 1
        if not os.path.exists(p):
            write_tfrecords(p, recs)
        paths.append(p)
    return paths


class _Ledger:
    """Append-only per-batch sample-ID log that survives SIGKILL: one
    JSON line per pulled batch, flushed + fsync'd before the batch is
    handed to training (a torn final line is detectable and tolerated
    by the parent)."""

    def __init__(self, path):
        import json
        self._json = json
        self._f = open(path, "a")

    def append(self, tag, ids):
        self._f.write(self._json.dumps(
            {"tag": int(tag), "ids": [int(i) for i in ids]}) + "\n")
        self._f.flush()
        os.fsync(self._f.fileno())


class _LedgerDataSet:
    """Wrap the sharded pipeline: tee each pulled batch's sample IDs
    (feature 0 × 100) into the ledger.  Delegates the cursor protocol
    so checkpoints keep recording the REAL pipeline state."""

    self_staging = True

    def __init__(self, base, ledger):
        self.base = base
        self.ledger = ledger
        self._pulled = 0

    def size(self):
        return self.base.size()

    def batches_per_epoch(self):
        return None

    def shuffle(self):
        return self

    def state(self):
        return self.base.state()

    def restore(self, st):
        self.base.restore(st)
        return self

    def set_place_fn(self, fn):
        # ids are read on the host BEFORE placement, so keep batches
        # host-side until the tee has seen them
        self.base.set_place_fn(None)
        self._place = fn

    def data(self, train=True, epoch=None):
        import numpy as np
        place = getattr(self, "_place", None)
        for x, y in self.base.data(train, epoch=epoch):
            self._pulled += 1
            ids = np.rint(np.asarray(x)[:, 0] * 100.0).astype(int)
            self.ledger.append(self._pulled, ids)
            yield (x, y) if place is None else place((x, y))


def main():
    ckpt_dir, out = sys.argv[1], sys.argv[2]
    opts = dict(kv.split("=", 1) for kv in sys.argv[3:] if "=" in kv)
    flags = {a for a in sys.argv[3:] if "=" not in a}
    iters = int(opts.get("iters", 9))
    ckpt_every = int(opts.get("ckpt_every", 2))
    step_sleep = float(opts.get("step_sleep", 0)) / 1e3
    preempt = "preempt" in flags
    spmd = "spmd" in flags
    data_cursor = "data_cursor" in flags

    os.environ["JAX_PLATFORMS"] = "cpu"
    if spmd:
        # BEFORE the jax import: the GSPMD matrix needs virtual devices
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8")
    import jax

    if spmd:
        return main_spmd(ckpt_dir, out, opts, flags, iters, ckpt_every,
                         step_sleep, preempt)

    import time

    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.optim import Adam, LocalOptimizer, Trigger

    if data_cursor:
        from bigdl_tpu.data.sharded import ShardedRecordDataSet
        paths = build_shards(opts["data_dir"])

        def decode(b):
            x = np.frombuffer(b[4:], np.float32).copy()
            return x, x[:1] * 0.5       # deterministic target

        pipe = ShardedRecordDataSet(paths, "tfrecord", decode,
                                    batch_size=16, n_workers=2, seed=5,
                                    staging_depth=1)
        ds = _LedgerDataSet(pipe, _Ledger(str(out) + ".ledger.jsonl"))
    else:
        # deterministic fixture (same recipe as test_resume_exact: fixed
        # layer names, epoch-seeded shuffle, fixed init)
        rng = np.random.RandomState(0)
        x = rng.randn(256, 10).astype(np.float32)
        w = rng.randn(10, 1).astype(np.float32)
        y = (x @ w).astype(np.float32)
        ds = DataSet.minibatch_arrays(x, y, batch_size=32, shuffle=True,
                                      seed=4)
    model = nn.Sequential(nn.Linear(10, 16, name="fc1"), nn.Tanh(),
                          nn.Linear(16, 1, name="fc2"))
    model.reset(11)

    end = Trigger.max_iteration(10_000 if preempt else iters)

    class _Tattle(Trigger):
        """End-trigger wrapper: announce every iteration (the parent
        synchronizes its SIGTERM on these lines) and optionally slow the
        loop so mid-run signals land deterministically."""

        def __call__(self, state):
            print(f"iter {state.iteration}", flush=True)
            if step_sleep:
                time.sleep(step_sleep)
            return end(state)

    opt = (LocalOptimizer(model, ds, nn.MSECriterion(), batch_size=32)
           .set_optim_method(Adam(learning_rate=1e-2))
           .set_end_when(_Tattle())
           .set_checkpoint(ckpt_dir,
                           trigger=Trigger.several_iteration(ckpt_every),
                           handle_preemption=preempt))

    pre = opt._ckpt_manager().restore_latest()
    if pre is not None:
        print(f"RESUME iteration={pre[2]['iteration']} "
              f"epoch={pre[2]['epoch']}", flush=True)

    opt.optimize()

    leaves = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(
                  jax.tree_util.tree_map(np.asarray, model._params))]
    np.savez(out, *leaves)
    print(f"WORKER DONE iteration={opt.state.iteration}", flush=True)


def main_spmd(ckpt_dir, out, opts, flags, iters, ckpt_every, step_sleep,
              preempt):
    """GSPMD elastic matrix: train the mini transformer on the mesh
    named by ``mesh=``, auto-resuming (and RESHARDING, when the
    directory was written on a different mesh) from whatever intact
    checkpoint exists."""
    import time

    import jax
    import numpy as np
    from bigdl_tpu.models import transformer as T
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel import mesh as mesh_lib
    from bigdl_tpu.parallel.spmd import SpmdTrainer

    axes = {}
    for part in opts.get("mesh", "dp4").split(","):
        name = part.rstrip("0123456789")
        axes[name] = int(part[len(name):])
    mesh = mesh_lib.create_mesh(axes)

    # deterministic fixture: fixed init seed, stateless per-step batches
    # with a FIXED GLOBAL batch — the same math on any mesh shape
    model = T.build("tiny", dropout=0.0, n_layers=1, d_model=64,
                    n_heads=2, d_ff=128, vocab_size=64, max_len=32)
    data_mode = "data" in flags
    tr = SpmdTrainer(model, Adam(learning_rate=1e-3), mesh=mesh,
                     fsdp="fsdp" in axes, seed=0)
    tr.set_checkpoint(ckpt_dir, every_steps=ckpt_every, keep=0,
                      layout="manifest",
                      shard_arrays="shard_arrays" in flags,
                      handle_preemption=preempt)
    pipe = None
    if data_mode:
        # sharded streaming pipeline feeding the GLOBAL batch: the
        # sample stream must be identical on ANY mesh (dp4 == dp2),
        # and the cursor rides in every manifest checkpoint
        from bigdl_tpu.data.sharded import ShardedRecordDataSet
        paths = build_shards(opts["data_dir"], spmd=True)

        def decode(b):
            t = np.frombuffer(b, np.int32)
            return t[:-1].copy(), t[1:].copy()

        pipe = ShardedRecordDataSet(paths, "tfrecord", decode,
                                    batch_size=8, n_workers=2, seed=5,
                                    staging_depth=1)
        tr.set_data_pipeline(pipe)
    tr.init()
    try:
        tr.load_checkpoint(ckpt_dir)
        print(f"RESUME step={tr._step_count}", flush=True)
    except FileNotFoundError:
        pass

    def batch(s):
        rs = np.random.RandomState(1234 + s)
        t = rs.randint(0, 64, (8, 17))
        return t[:, :-1], t[:, 1:]

    end = 10_000 if preempt else iters

    def batches():
        if data_mode:
            ledger = _Ledger(str(out) + ".ledger.jsonl")
            s = tr._step_count
            for tokens, targets in pipe.stream():
                if s >= end:
                    return
                # the parent synchronizes its signals on these lines
                print(f"iter {s}", flush=True)
                if step_sleep:
                    time.sleep(step_sleep)
                ids = (np.asarray(tokens)[:, 0] * 64
                       + np.asarray(tokens)[:, 1])
                ledger.append(s, ids)
                yield tokens, targets
                s += 1
            return
        for s in range(tr._step_count, end):
            # the parent synchronizes its SIGTERM on these lines
            print(f"iter {s}", flush=True)
            if step_sleep:
                time.sleep(step_sleep)
            yield batch(s)

    losses = tr.fit(batches())
    tr.detach()
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(tr.params)]
    np.savez(out, *leaves, losses=np.asarray(losses, np.float64))
    print(f"WORKER DONE step={tr._step_count}", flush=True)


if __name__ == "__main__":
    main()
