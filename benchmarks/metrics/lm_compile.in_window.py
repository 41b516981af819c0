"""Programs the trainer's jitted step compiled inside the window
(`_step_fn._cache_size()` after minus before); expected 0."""


def read(ctx):
    return ctx["facts"].get("compiles_in_window")
