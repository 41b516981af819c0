"""Mean prefill call as the engine times it: its `decode.prefill` span's
seconds over its `decode/prefills` count, both inside the window."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("prefills"):
        return None
    return 1e3 * f["prefill_s"] / f["prefills"]
