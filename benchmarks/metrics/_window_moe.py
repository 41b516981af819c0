"""Shared by the readers of the window / global attention, routed-expert
serving cell.

The decode step's two named pieces are jitted inner functions of the
program (`_window_attend`: every layer's gather and attention, by its
kind's own table; `_moe_experts`): the compiled step's text says, in each
op's metadata, which of them an op came from, and the runner hands that
map over as `facts["op_scopes"]`, keyed as the device trace names an op
(`_sparse_moe.op_key`).  In a traced run the runner also samples the
engine's counters and the two kinds' page gauges ten times a second, so
that the traced seconds stand against the steps, experts, rows and pages
of the same seconds.

A program with no such functions or counters (the parent of the PR that
brought them) gives every reader here nothing to read: they return None
and the result line leaves the metric out.
"""
from benchmarks.flops import window_moe
from benchmarks.metrics import _sparse_moe

SCOPES = ("_window_attend", "_moe_experts")

decode_steps = _sparse_moe.decode_steps
traced_counts = _sparse_moe.traced_counts
scope_seconds = _sparse_moe.scope_seconds


def is_cell(ctx):
    """Whether the run's configuration is one of two kinds of layer."""
    return "sliding_window_layout" in ctx["facts"].get("config", {})


def op_scopes(hlo_text):
    """{op_key: scope} for the ops of a compiled program that come from
    one of SCOPES, by the `op_name` of their metadata."""
    out = {}
    for line in hlo_text.splitlines():
        for scope in SCOPES:
            if f"jit({scope})" in line:
                key = _sparse_moe.op_key(line)
                if key:
                    out[key] = scope
    return out


def rows_attended(counts):
    """The rows a step's (or a window's) queries may see, both kinds."""
    return counts.get("attn_rows_attended_window", 0.0) \
        + counts.get("attn_rows_attended_global", 0.0)


def piece_roofline(ctx, scope, cost):
    """A named piece's share of its roofline in the decode step:
    `cost(cfg, per_step_counts) -> (flops, bytes)` of the piece's work in
    ONE step (all its layers), times the steps of the trace, over the
    piece's device seconds there."""
    if not is_cell(ctx):
        return None
    per_step, secs = traced_counts(ctx), scope_seconds(ctx, scope)
    steps = len(decode_steps(ctx["trace"]))
    if per_step is None or secs is None or not steps:
        return None
    flops, nbytes = cost(ctx["facts"]["config"], per_step)
    least, _bound = window_moe.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * steps / secs
