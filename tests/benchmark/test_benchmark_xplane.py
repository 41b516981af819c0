"""The trace reducer on a synthetic trace: busy union, idle share, gap
attribution, module durations."""
import pytest

from benchmarks import harness, xplane

US = 1000.0


def planes():
    ops = [("fusion.1", 0 * US, 100 * US), ("fusion.2", 50 * US, 100 * US),
           ("_fwd_kernel", 400 * US, 100 * US),      # after a 250 us gap
           ("fusion.1", 505 * US, 95 * US)]          # after a 5 us gap
    mods = [("jit_fn(1)", 0, 150 * US), ("jit_fn(1)", 400 * US, 200 * US),
            ("jit_other(2)", 0, 10 * US)]
    host = [("PjitFunction(step)", 140 * US, 200 * US),
            ("tiny", 160 * US, 10 * US)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:0 SparseCore 0", "lines": [
            {"name": "XLA Ops", "events": [("x", 0, 1000 * US)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]},
    ]


def test_union_merges_overlaps():
    assert xplane.union([(0, 10), (5, 12), (20, 30), (30, 31)]) == \
        [(0, 12), (20, 31)]


def test_reduce_planes():
    r = xplane.reduce_planes(planes(), 1)
    # busy: [0,150] + [400,500] + [505,600] us; the SparseCore plane and
    # the overlap of fusion.1 / fusion.2 are not counted twice
    assert abs(r["busy_s"] - 345e-6) < 1e-12
    # the window is the extent of the whole trace, here the SparseCore
    # plane's event is not a device plane but still part of the trace
    assert abs(r["window_s"] - 1000e-6) < 1e-12
    ops = dict(map(tuple, r["device_ops"]))
    assert abs(ops["fusion.1"] - 195e-6) < 1e-12
    assert r["device_ops"][0][0] == "fusion.1"
    assert len(r["modules"]["jit_fn(1)"]) == 2
    gaps = dict(map(tuple, r["idle_gaps"]))
    # the 250 us gap lies under the host's PjitFunction event, the 5 us
    # gap is pooled, the tail after the last op has no host event
    assert abs(gaps["PjitFunction(step)"] - 250e-6) < 1e-12
    assert abs(gaps["short_gaps"] - 5e-6) < 1e-12
    assert abs(gaps["unattributed"] - 400e-6) < 1e-12
    assert abs(sum(gaps.values()) + r["busy_s"] - r["window_s"]) < 1e-12
    kernels = {n: (c, s) for n, c, s in r["kernels"]}
    assert kernels["_fwd_kernel"][0] == 1


def test_container_ops_stay_busy_but_leave_the_ranking():
    p = planes()
    p[0]["lines"][0]["events"].append(
        ("%while.9 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b",
         0 * US, 600 * US))
    r = xplane.reduce_planes(p, 1)
    assert abs(r["busy_s"] - 600e-6) < 1e-12
    assert all("while(" not in n for n, _ in r["device_ops"])
    assert not xplane.CONTAINER.search(
        "%jvp__.94 = (bf16[64,2048,128], f32[64,2048,1]) custom-call(bf16")


def test_flash_calls_are_told_by_their_results():
    from benchmarks.metrics import _flash
    fwd = ("%jvp__.94 = (bf16[64,2048,128]{2,1,0}, f32[64,2048,1]{2,1,0}) "
           "custom-call(bf16[64,2048,128] %copy.1450")
    dkv = ("%transpose_jvp___.182 = (bf16[64,2048,128]{2,1,0}, "
           "bf16[64,2048,128]{2,1,0}) custom-call(bf16[64,2048,128] %x")
    dq = ("%transpose_jvp___.181 = bf16[64,2048,128]{2,1,0} "
          "custom-call(bf16[64,2048,128] %copy.1444")
    assert [_flash.classify(n) for n in (fwd, dkv, dq, "%fusion.1 = f32[2] "
            "fusion(f32[2] %p)")] == ["fwd", "dkv", "dq", None]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Cell:
        chips = 1
    cfg = {"num_attention_heads": 16, "hidden_size": 2048}
    facts = {"kernel_batch": 4, "seq_len": 2048, "config": cfg}
    # one forward call that took exactly twice its compute roofline
    least = 4 * 64 * 128 * (2048 * 2049 // 2) / 197e12
    ctx = {"facts": facts, "peaks": peaks, "cell": Cell,
           "trace": {"kernels": [(fwd, 1, 2 * least), (dkv, 1, 1.0)]}}
    assert abs(_flash.roofline_share(ctx, "fwd") - 50.0) < 1e-9
    # the backward's two kernels are one pass, counted by its dk/dv call
    least_b = 10 * 64 * 128 * (2048 * 2049 // 2) / 197e12
    ctx["trace"]["kernels"] = [(dkv, 3, 6 * least_b), (dq, 3, 6 * least_b),
                               ("%cc.1 = bf16[8,8]{1,0} custom-call(", 9, 1.)]
    assert abs(_flash.roofline_share(ctx, "bwd") - 25.0) < 1e-9
    # nothing to read: silent off the Pallas route, an error on it
    ctx["trace"]["kernels"] = []
    assert _flash.roofline_share(ctx, "fwd") is None
    facts["attention_route"] = "pallas"
    with pytest.raises(harness.BenchmarkError, match="no longer tells"):
        _flash.roofline_share(ctx, "bwd")


def test_no_device_plane_reads_nothing():
    assert xplane.reduce_planes(planes()[2:], 1) is None
