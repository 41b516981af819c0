"""How late the load generator sent (send time - due time), p95: a
starved generator must not read as a fast server."""


def read(ctx):
    return ctx["facts"].get("gen_late_ms_p95")
