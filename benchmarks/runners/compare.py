"""The numbers a cell's `correct` compares, each beside its limit.
Shared arithmetic of the runners; the limits are data (the traffic file's
`limits`), set from readings that PERF.md records."""
import statistics

GRAD_FLOOR = 1e-3      # leaves whose reference gradient is under this
                       # share of the median leaf's move by round-off only


def norm_gap(prog, ref, leaves=None, statistic="worst"):
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger; then the worst leaf's gap or (`statistic`
    "median", for a model whose worst leaves are ill-conditioned: PERF.md)
    the median leaf's.  -> (gap, leaf)."""
    leaves = list(leaves if leaves is not None else ref)
    med = statistics.median(ref[n] for n in leaves)
    gaps = [(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30), n)
            for n in leaves]
    nan = [g for g in gaps if g[0] != g[0]]
    if nan:
        return nan[0]
    gaps.sort()
    return gaps[-1] if statistic == "worst" else gaps[len(gaps) // 2]


def leaf_gaps(prog, ref, top=6):
    """The worst leaves, for a look by hand: [(leaf, gap, program's norm,
    reference's norm)]."""
    med = statistics.median(ref.values())
    rows = [(n, abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30), prog[n],
             ref[n]) for n in ref]
    return sorted(rows, key=lambda r: -r[1])[:top]


def training(prog, ref, limits, statistic="worst", kernels=None):
    """prog / ref: dict(losses, grad_norms, dparam_norms).  `kernels`
    (leaf names) adds both gaps over those leaves alone: the convolution
    and classifier kernels of a model whose BatchNorm leaves read alike in
    bfloat16 and fp8 (PERF.md)."""
    out = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out.append({"name": f"loss_step{i + 1}", "value": abs(a - b) / abs(b)})
    g, g_leaf = norm_gap(prog["grad_norms"], ref["grad_norms"],
                         statistic=statistic)
    out.append({"name": "grad_norm_gap", "value": g, "leaf": g_leaf})
    med = statistics.median(ref["grad_norms"].values())
    moved = [n for n, v in ref["grad_norms"].items()
             if v >= GRAD_FLOOR * med]
    d, d_leaf = norm_gap(prog["dparam_norms"], ref["dparam_norms"], moved,
                         statistic)
    out.append({"name": "dparam_norm_gap", "value": d, "leaf": d_leaf})
    if kernels:
        g, g_leaf = norm_gap(prog["grad_norms"], ref["grad_norms"], kernels,
                             statistic)
        out.append({"name": "grad_norm_gap_kernels", "value": g,
                    "leaf": g_leaf})
        d, d_leaf = norm_gap(prog["dparam_norms"], ref["dparam_norms"],
                             [n for n in kernels if n in moved], statistic)
        out.append({"name": "dparam_norm_gap_kernels", "value": d,
                    "leaf": d_leaf})
    # a number with no limit in the traffic file has no upper reading
    # (PERF.md gives its readings): it is shown, not compared
    for c in out:
        c["value"] = float(c["value"])
        c["limit"] = float(limits[c["name"]]) if c["name"] in limits else None
    return out
