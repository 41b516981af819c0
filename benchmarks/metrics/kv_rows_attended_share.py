"""Of the cached rows that were live in the window's decode steps, the
share their attention read: `sparse/rows_attended` over
`sparse/rows_live`."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("sparse_rows_live"):
        return None
    return 100.0 * f["sparse_rows_attended"] / f["sparse_rows_live"]
