"""Share of the window the loop spent waiting for input: the Recorder's
`data_fetch` + `h2d` spans of the window's steps over the window."""


def read(ctx):
    f = ctx["facts"]
    if "input_wait_s" not in f:
        return None
    return 100.0 * f["input_wait_s"] / f["window_s"]
