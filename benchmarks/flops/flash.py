"""Operations and bytes of the flash-attention kernels, from shapes.

Shapes are one kernel call's: bh = batch x heads, seq, head_dim, causal.
Causal work is the lower triangle, counted once.  The forward needs two
matmuls (QK^T, PV); the backward needs five (recompute S, dP, dV, dK,
dQ).  The program splits its backward into a dk/dv and a dq kernel that
each recompute S and dP (seven matmuls in all): the two extra are
recomputation and are not counted.
"""


def _pairs(seq, causal):
    return seq * (seq + 1) // 2 if causal else seq * seq


def fwd(bh, seq, head_dim, causal=True, bytes_per_el=2):
    flops = 2 * 2 * bh * head_dim * _pairs(seq, causal)
    # read q, k, v; write o and the float32 log-sum-exp column
    nbytes = 4 * bh * seq * head_dim * bytes_per_el + bh * seq * 4
    return flops, nbytes


def bwd(bh, seq, head_dim, causal=True, bytes_per_el=2):
    flops = 5 * 2 * bh * head_dim * _pairs(seq, causal)
    # read q, k, v, do (o is folded into delta outside the kernels),
    # lse and delta; write dq, dk, dv
    nbytes = 7 * bh * seq * head_dim * bytes_per_el + 2 * bh * seq * 4
    return flops, nbytes


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
