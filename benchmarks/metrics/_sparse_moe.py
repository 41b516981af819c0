"""Shared by the readers of the sparse-attention / routed-expert cell.

The decode step's three named pieces are jitted inner functions of the
program (`_index_topk`, `_sparse_attend`, `_moe_experts`): the compiled
step's text says, in each op's metadata, which of them an op came from,
and the runner hands that map over as `facts["op_scopes"]`, keyed as the
device trace names an op (`op_key`).  In a traced run the runner also
samples the engine's counters ten times a second, so that the traced
seconds stand against the steps, experts and rows of the same seconds.

A program with no such functions or counters (the parent of the PR that
brought them) gives every reader here nothing to read: they return None
and the result line leaves the metric out.
"""
import re

from benchmarks import xplane
from benchmarks.flops import sparse_moe

SCOPES = ("_index_topk", "_sparse_attend", "_moe_experts")


def op_key(line):
    """`%name = <result shapes>` of an HLO line or of a trace event's
    name, layouts taken out: what the two have in common."""
    m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+ = .+?) [a-z][\w\-]*\(", line)
    return m and re.sub(r"\{[^{}]*\}", "", m.group(1))


def op_scopes(hlo_text):
    """{op_key: scope} for the ops of a compiled program that come from
    one of SCOPES, by the `op_name` of their metadata."""
    out = {}
    for line in hlo_text.splitlines():
        for scope in SCOPES:
            if f"jit({scope})" in line:
                key = op_key(line)
                if key:
                    out[key] = scope
    return out


def decode_steps(trace):
    """Device seconds of each execution of the decode step in the trace:
    the most frequent module with `fn` in its name, as
    `_decode_program.py` takes it."""
    mods = {k: v for k, v in trace["modules"].items() if "fn" in k}
    return mods[max(mods, key=lambda k: len(mods[k]))] if mods else []


def scope_seconds(ctx, scope):
    """Device seconds, over the whole trace, of the ops of `scope`."""
    scopes = ctx["facts"].get("op_scopes")
    if not scopes:
        return None
    total = 0.0
    for name, _count, secs in ctx["trace"]["kernels"]:
        if scopes.get(op_key(name)) == scope \
                and not xplane.CONTAINER.search(name):
            total += secs
    return total or None


def traced_counts(ctx):
    """What the engine counted between the two samples nearest the traced
    interval's ends, a decode step: {counter: mean a step}."""
    samples, traced = ctx["facts"].get("counter_samples"), ctx["probe"].traced
    if not samples or not traced:
        return None
    near = lambda t: min(samples, key=lambda s: abs(s[0] - t))[1]
    a, b = near(traced[0]), near(traced[1])
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return {k: (b[k] - a[k]) / steps for k in b}


def kernel_roofline(ctx, scope, cost):
    """A named piece's share of its roofline in the decode step:
    `cost(cfg, per_layer_counts) -> (flops, bytes)` of one layer's call,
    times the layers and the steps of the trace, over the piece's device
    seconds there."""
    per_step, secs = traced_counts(ctx), scope_seconds(ctx, scope)
    steps = len(decode_steps(ctx["trace"]))
    if per_step is None or secs is None or not steps:
        return None
    cfg = ctx["facts"]["config"]
    n_layers = cfg["num_hidden_layers"]
    flops, nbytes = cost(cfg, {k: v / n_layers for k, v in per_step.items()})
    least, _bound = sparse_moe.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * n_layers * steps / secs
