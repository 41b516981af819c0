"""Shared by the flash kernels' roofline readers: device time of the
kernel's calls in the trace against the least time the chip could take
for the calls' shapes.

The trace names a Mosaic call by its HLO line, `%name = <result>
custom-call(...)`, not by the kernel function, so a call is told by its
result: the forward returns (o, float32 log-sum-exp column), the dk/dv
kernel two arrays of the operands' type, the dq kernel one.  A program
that fuses or renames its kernels leaves nothing here to match: where the
runner says the Pallas route was taken that is an error, not a silent
metric, and the reader is the file to replace."""
from benchmarks import harness
from benchmarks.flops import flash


def classify(event_name):
    """'fwd' | 'dkv' | 'dq' | None for one device-op event name."""
    head, sep, _ = event_name.partition(" custom-call(")
    if not sep or "=" not in head:
        return None
    result = head.split("=", 1)[1]
    n_arrays = result.count("[")
    if n_arrays == 2 and "f32[" in result and result.rstrip().endswith(")"):
        return "fwd"
    if n_arrays == 2:
        return "dkv"
    if n_arrays == 1:
        return "dq"
    return None


def roofline_share(ctx, which):
    f, trace = ctx["facts"], ctx["trace"]
    kinds = {"fwd": ("fwd",), "bwd": ("dkv", "dq")}[which]
    if "kernel_batch" not in f:
        return None
    cfg = f["config"]
    heads = cfg["num_attention_heads"]
    bh = f["kernel_batch"] * heads // ctx["cell"].chips
    dh = cfg["hidden_size"] // heads
    # other custom calls return one array too (the first traced run read
    # 147% without this): a kernel's result has the kernel's own shape
    shape = f"[{bh},{f['seq_len']},{dh}]"
    calls = [(n, cnt, secs) for n, cnt, secs in trace["kernels"]
             if classify(n) in kinds
             and shape in n.partition(" custom-call(")[0]]
    first = kinds[0]
    passes = sum(c for n, c, _ in calls if classify(n) == first)
    if not passes:
        if f.get("attention_route") == "pallas":
            raise harness.BenchmarkError(
                f"the runner says attention took the Pallas kernels, but no "
                f"{which} call with a result of shape {shape} is in the "
                f"trace: the kernels' results have changed, and "
                f"benchmarks/metrics/_flash.py no longer tells them")
        return None
    cost = flash.fwd if which == "fwd" else flash.bwd
    flops, nbytes = cost(bh, f["seq_len"], dh, causal=True)
    least, _bound = flash.roofline_seconds(flops, nbytes, ctx["peaks"])
    # one forward call a layer pass; the backward's two kernels (dk/dv
    # and dq) together are one backward, counted by its dk/dv call, whose
    # two-array result no other call has (counting dq-like calls too read
    # 21.7% where the per-call times say 12.6%: my chip run, PR 24)
    seconds = sum(s for _, _, s in calls)
    return 100.0 * least * passes / seconds
