"""Median gap between consecutive tokens, at the client."""


def read(ctx):
    return ctx["facts"].get("tpot_ms_p50")
