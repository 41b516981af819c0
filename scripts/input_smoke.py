"""CI proxy for the production data plane while the hardware bench
backend is down (ROADMAP standing constraint).

Runs the 8-device CPU dryrun at the PR-8 step config (DistriOptimizer
zero1 + bucketed fp16 + fused kernels) twice over the SAME shard files:

  baseline   single decode worker, per-image float32 host augmentation
             (crop + flip + normalize in python — the loop the
             reference ran inside Spark tasks), fp32 on the wire
  parallel   4-worker decode pool, raw uint8 on the wire, crop / flip /
             normalize compiled INTO the jitted step (DeviceAugment)

and asserts the CPU-measurable claims:

  1. parallel input-stall fraction below threshold AND below the
     baseline's, measured from the consumer-side
     ``data/input_stall_seconds`` counter deltas over the step records
     (never producer-side rates — see docs/performance.md
     § Input-stall methodology);
  2. >= 3x h2d wire-byte drop for uint8 + device-augment vs the fp32
     host path, gauge-accounted from ``data/h2d_bytes`` (deterministic
     arithmetic, like perf_proxy_smoke's HLO accounting: f32 crops at
     the reference's 256->224 proportions ship (28*28*3*4)B/row vs
     (32*32*3)B/row raw uint8);
  3. the cursor-resume ledger check: consume k batches, snapshot the
     cursor, restore into a FRESH pipeline, and the concatenated
     sample-ID stream equals the uninterrupted run's bit for bit.

Emits ONE parseable JSON line (last line) for CI and the BENCH
trajectory; every number is a proxy pending hardware re-measurement.
"""
import json
import os
import struct
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np
import jax

from bigdl_tpu import nn
from bigdl_tpu.data.device_augment import DeviceAugment
from bigdl_tpu.data.sharded import ShardedRecordDataSet
from bigdl_tpu.kernels import fused_optim
from bigdl_tpu.observability import InMemorySink, Recorder
from bigdl_tpu.optim import Adam, Trigger
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import mesh as mesh_lib
from bigdl_tpu.utils.tfrecord import write_tfrecords

# CPU smoke: the fused optimizer kernels run through the Pallas
# interpreter (they lower through Mosaic unless told otherwise)
fused_optim._FORCE_INTERPRET = True

DP = 8
HW, CROP, C = 32, 28, 3            # the reference's 256->224 proportions
N_FILES, PER_FILE = 12, 256
BATCH = 64                          # global batch; 8 rows per dp shard
EPOCHS = 2
MEAN = (127.0,) * 3
STD = (64.0,) * 3


def build_shards(d):
    rng = np.random.RandomState(0)
    paths, gid = [], 0
    for f in range(N_FILES):
        recs = []
        for _ in range(PER_FILE):
            img = rng.randint(0, 255, (HW, HW, C), np.uint8)
            recs.append(struct.pack("<ii", gid, gid % 10) + img.tobytes())
            gid += 1
        p = os.path.join(d, f"shard{f:02d}.tfr")
        write_tfrecords(p, recs)
        paths.append(p)
    return paths


def decode_uint8(b):
    """Parallel path: frame only — raw uint8 ships to the device."""
    _, label = struct.unpack("<ii", b[:8])
    return (np.frombuffer(b[8:], np.uint8).reshape(HW, HW, C),
            np.int32(label))


def decode_f32_host(b, rng):
    """Baseline path: the per-image python augmentation loop the
    pipeline replaces — crop + flip + normalize on the host, fp32 on
    the wire (``decode_rng`` keeps it resume-exact)."""
    _, label = struct.unpack("<ii", b[:8])
    img = np.frombuffer(b[8:], np.uint8).reshape(HW, HW, C)
    oy, ox = rng.randint(0, HW - CROP + 1, 2)
    patch = img[oy:oy + CROP, ox:ox + CROP].astype(np.float32)
    if rng.rand() < 0.5:
        patch = patch[:, ::-1]
    patch = (patch - np.asarray(MEAN, np.float32)) \
        / np.asarray(STD, np.float32)
    return np.ascontiguousarray(patch), np.int32(label)


def make_model():
    m = nn.Sequential(nn.Reshape([CROP * CROP * C]),
                      nn.Linear(CROP * CROP * C, 32, name="fc1"),
                      nn.Tanh(), nn.Linear(32, 10, name="fc2"))
    m.reset(7)
    return m


def run_config(paths, parallel: bool):
    """Train EPOCHS at the PR-8 step config; returns (sink records,
    final loss, steps)."""
    mesh = mesh_lib.create_mesh({"dp": DP})
    if parallel:
        ds = ShardedRecordDataSet(paths, "tfrecord", decode_uint8,
                                  batch_size=BATCH, n_workers=4, seed=11)
    else:
        ds = ShardedRecordDataSet(paths, "tfrecord", decode_f32_host,
                                  batch_size=BATCH, n_workers=1, seed=11,
                                  decode_rng=True)
    sink = InMemorySink()
    rec = Recorder(sinks=[sink], annotate=False)
    opt = (DistriOptimizer(make_model(), ds,
                           nn.CrossEntropyCriterion(zero_based_label=True),
                           mesh=mesh, zero1=True, bucket_bytes=256,
                           compress="fp16", fused_optim=True)
           .set_optim_method(Adam(learning_rate=1e-3))
           .set_end_when(Trigger.max_epoch(EPOCHS))
           .set_telemetry(rec, health=False))
    if parallel:
        opt.set_device_augment(DeviceAugment(
            crop=(CROP, CROP), flip=True, mean=MEAN, std=STD,
            out_format="NHWC"))
    opt.optimize()
    return sink, float(opt.state.loss), opt.state.iteration


def window_metrics(sink):
    """(stall_fraction, h2d_bytes_per_step, decode_seconds, wall) from
    consecutive step-record counter deltas, excluding the first record
    (compile + fill warmup — same exclusion discipline as
    trace_summary.py input)."""
    steps = [r for r in sink.records if r.get("type") == "step"]
    have = [s for s in steps
            if "data/input_stall_seconds" in s.get("counters", {})]
    first, last = have[0], have[-1]

    def delta(k):
        return (last["counters"].get(k, 0.0)
                - first["counters"].get(k, 0.0))

    n = len(have) - 1
    wall = sum(s.get("dur") or 0.0 for s in have[1:])
    return (delta("data/input_stall_seconds") / max(wall, 1e-12),
            delta("data/h2d_bytes") / max(n, 1),
            delta("data/decode_seconds"), wall, n)


def cursor_ledger_check(paths):
    """Consume 10 batches, snapshot, restore into a FRESH pipeline, and
    compare the concatenated id stream to an uninterrupted run's."""
    def decode(b):
        gid, label = struct.unpack("<ii", b[:8])
        return np.int32(gid), np.int32(label)

    def mk():
        return ShardedRecordDataSet(paths, "tfrecord", decode,
                                    batch_size=BATCH, n_workers=4,
                                    seed=23, drop_last=False)
    ref = [int(v) for x, y in mk().data(train=True, epoch=0) for v in x]
    ds = mk()
    it = ds.data(train=True, epoch=0)
    head = []
    for _ in range(10):
        x, _ = next(it)
        head.extend(int(v) for v in x)
    state = ds.state()
    it.close()
    ds2 = mk()
    ds2.restore(state)
    tail = [int(v) for x, y in ds2.data(train=True, epoch=0) for v in x]
    return head + tail == ref, len(ref)


def main():
    failures = []
    summary = {"metric": "input_smoke", "proxy": True, "devices": DP,
               "step_config": "zero1+bucketed_fp16+fused (PR-8)",
               "records": N_FILES * PER_FILE, "global_batch": BATCH}
    t0 = time.time()
    with tempfile.TemporaryDirectory() as d:
        paths = build_shards(d)

        base_sink, base_loss, base_steps = run_config(paths,
                                                      parallel=False)
        par_sink, par_loss, par_steps = run_config(paths, parallel=True)

        b_stall, b_h2d, b_dec, b_wall, b_n = window_metrics(base_sink)
        p_stall, p_h2d, p_dec, p_wall, p_n = window_metrics(par_sink)
        summary.update({
            "steps_per_config": par_steps,
            "baseline_stall_fraction": round(b_stall, 4),
            "parallel_stall_fraction": round(p_stall, 4),
            "baseline_h2d_bytes_per_step": round(b_h2d),
            "parallel_h2d_bytes_per_step": round(p_h2d),
            "h2d_drop_ratio": round(b_h2d / max(p_h2d, 1), 3),
            "baseline_decode_seconds": round(b_dec, 3),
            "parallel_decode_seconds": round(p_dec, 3),
            "baseline_mean_step_ms": round(1e3 * b_wall / max(b_n, 1), 3),
            "parallel_mean_step_ms": round(1e3 * p_wall / max(p_n, 1), 3),
            "parallel_final_loss": par_loss,
        })
        # 1. the parallel loader feeds the step: stall fraction under
        # threshold and under the single-worker fp32 baseline's
        if p_stall >= 0.05:
            failures.append(f"parallel stall fraction {p_stall:.4f} "
                            ">= 0.05")
        if p_stall >= b_stall:
            failures.append(f"parallel stall {p_stall:.4f} not below "
                            f"baseline {b_stall:.4f}")
        # 2. uint8 wire drop, gauge-accounted and deterministic:
        # (28*28*3*4 + 4) / (32*32*3 + 4) = 3.06x per row
        if b_h2d / max(p_h2d, 1) < 3.0:
            failures.append(f"h2d drop {b_h2d / max(p_h2d, 1):.2f}x < 3x")
        # 3. both configs saw every record exactly the same number of
        # epochs (same step count from the same shard files)
        if base_steps != par_steps:
            failures.append(f"step-count mismatch: {base_steps} vs "
                            f"{par_steps}")
        if not np.isfinite(par_loss):
            failures.append(f"device-augment config diverged: {par_loss}")

        # 4. cursor-resume ledger
        ok, n_ids = cursor_ledger_check(paths)
        summary["cursor_ledger_ok"] = bool(ok)
        summary["cursor_ledger_ids"] = n_ids
        if not ok:
            failures.append("cursor-resume ledger mismatch")

    summary["wall_seconds"] = round(time.time() - t0, 1)
    summary["ok"] = not failures
    summary["failures"] = failures
    print(json.dumps(summary))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
