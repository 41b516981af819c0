"""`train_step_compile` spans in the window's step records; expected 0."""


def read(ctx):
    return ctx["facts"].get("compiles_in_window")
