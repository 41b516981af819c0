"""`decode/recompiles` counted inside the window; expected 0."""


def read(ctx):
    return ctx["facts"].get("recompiles")
