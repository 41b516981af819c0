"""Shared by the readers of the latent-attention, held-share-of-experts
serving cell.

The named pieces are jitted inner functions of the program: in the decode
step `_latent_attend` (every layer's gather of the slots' tables and the
absorbed attention) and `_moe_experts`; in the chunk program
`_latent_chunk_attend` (every layer's gather of the longest prompt's table
and the chunk's attention, a block of heads a step).  Each compiled
program's text says, in an op's metadata, which of them the op came from,
and the runner hands that map over as `facts["op_scopes"]`, keyed as the
device trace names an op (`_sparse_moe.op_key`); an op that both programs
name alike but place differently is left out (`op_scopes_ambiguous`
counts them).  In a traced run the runner also samples the engine's
counters ten times a second, so that the traced seconds stand against
the steps, chunks, rows and experts of the same seconds.

A program with no such functions or counters (the parent of the PR that
brought them) gives every reader here nothing to read: they return None
and the result line leaves the metric out.
"""
from benchmarks.flops import mla_moe
from benchmarks.metrics import _sparse_moe

DECODE_SCOPES = ("_latent_attend", "_moe_experts")
CHUNK_SCOPES = ("_latent_chunk_attend",)

decode_steps = _sparse_moe.decode_steps
scope_seconds = _sparse_moe.scope_seconds


def is_cell(ctx):
    """Whether the run's configuration is one of latent attention."""
    return "kv_lora_rank" in ctx["facts"].get("config", {})


def _scoped(hlo_text, scopes):
    """{op_key: scope or None} for every op of a compiled program."""
    out = {}
    for line in hlo_text.splitlines():
        key = _sparse_moe.op_key(line)
        if key:
            out[key] = next((s for s in scopes if f"jit({s})" in line), None)
    return out


def op_scopes(decode_text, chunk_text):
    """({op_key: scope} over both programs, how many keys were left out
    because the two programs place one key differently)."""
    dec = _scoped(decode_text, DECODE_SCOPES)
    chk = _scoped(chunk_text, CHUNK_SCOPES)
    out, ambiguous = {}, 0
    for key in set(dec) | set(chk):
        a, b = dec.get(key), chk.get(key)
        if key in dec and key in chk and a != b:
            ambiguous += 1
        elif a or b:
            out[key] = a or b
    return out, ambiguous


def chunk_runs(trace):
    """Device seconds of each execution of the chunk program."""
    return [s for name, runs in trace["modules"].items()
            if "prefill_chunk" in name for s in runs]


def traced_rates(ctx):
    """What the engine counted between the two samples nearest the traced
    interval's ends: ({counter: mean a decode step}, {counter: mean a
    chunk}); None where no step (no chunk) ran between them."""
    samples, traced = ctx["facts"].get("counter_samples"), ctx["probe"].traced
    if not samples or not traced:
        return None, None
    near = lambda t: min(samples, key=lambda s: abs(s[0] - t))[1]
    a, b = near(traced[0]), near(traced[1])

    def per(unit):
        n = b[unit] - a[unit]
        return {k: (b[k] - a[k]) / n for k in b} if n > 0 else None
    return per("steps"), per("prefill_chunks")


def piece_roofline(ctx, scope, per_run, runs, cost):
    """A named piece's share of its roofline: `cost(cfg, counts) ->
    (flops, bytes)` of the piece's work in ONE run of its program (all its
    layers; `per_run` the counters' means a run), times the program's
    `runs` in the trace, over the piece's device seconds there."""
    if not is_cell(ctx) or per_run is None or not runs:
        return None
    secs = scope_seconds(ctx, scope)
    if secs is None:
        return None
    flops, nbytes = cost(ctx["facts"]["config"], per_run)
    least, _bound = mla_moe.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * runs / secs
