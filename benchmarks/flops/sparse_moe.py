"""Operations and bytes of a decoder of grouped-query attention under a
top-k sparse-attention indexer and routed experts, from its shapes and
from what the program COUNTED (experts touched, rows live and attended),
never from how the program computes it.

Matmul FLOPs are 2 x MACs.  A query at context c (the keys it may see,
itself included) attends min(c, top-k) keys; the indexer scores all c.
`cfg` is the configuration file's dict (HF key names).
"""


def dims(cfg):
    sa = cfg["sa_config"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
                topk=sa["topk"], e=cfg["num_experts"],
                k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
                v=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"])


def layer_params(cfg):
    """Parameters of one layer by part (norm gains left out)."""
    m = dims(cfg)
    d = m["d"]
    return {"attention": 2 * d * m["h"] * m["dh"] + 2 * d * m["hkv"] * m["dh"],
            "indexer": d * (m["hi"] * m["di"] + m["di"] + m["hi"]),
            "router": d * m["e"],
            "expert": 3 * d * m["f"]}


def param_count(cfg):
    m, p = dims(cfg), layer_params(cfg)
    layer = p["attention"] + p["indexer"] + p["router"] + m["e"] * p["expert"]
    return m["n_layers"] * layer + 2 * m["v"] * m["d"]


def layer_flops_per_token(cfg):
    """Forward matmul FLOPs of one token through one layer's projections,
    indexer projections, router and its top-k experts."""
    m, p = dims(cfg), layer_params(cfg)
    return 2 * (p["attention"] + p["indexer"] + p["router"]
                + m["k"] * p["expert"])


def head_flops(cfg):
    m = dims(cfg)
    return 2 * m["d"] * m["v"]


def attention_flops(cfg, context):
    """One query at `context` in one layer: QK^T and PV over the
    min(context, top-k) rows it attends, and the indexer's scores (a dot of
    index_dim an index head, then the weighted sum) over all of them."""
    m = dims(cfg)
    attended = min(context, m["topk"])
    return 4 * m["h"] * m["dh"] * attended \
        + (2 * m["hi"] * m["di"] + 2 * m["hi"]) * context


def sequence_flops(cfg, n_prompt, n_new):
    """Forward FLOPs of serving one request: every prompt token through
    the layers, the head once for the first token, then n_new - 1 decoded
    tokens through layers and head; each query against its own context."""
    m = dims(cfg)
    n_tok = n_prompt + n_new - 1
    # sum over contexts c = 1..n_tok of min(c, topk) and of c
    full = min(n_tok, m["topk"])
    attended = full * (full + 1) // 2 + (n_tok - full) * m["topk"]
    seen = n_tok * (n_tok + 1) // 2
    att = 4 * m["h"] * m["dh"] * attended \
        + (2 * m["hi"] * m["di"] + 2 * m["hi"]) * seen
    return m["n_layers"] * (n_tok * layer_flops_per_token(cfg) + att) \
        + n_new * head_flops(cfg)


# -- one decode step, from what the program counted ---------------------- #
def index_topk_cost(cfg, rows_scored, bytes_per_el=2):
    """(flops, bytes) of scoring `rows_scored` index keys in one layer
    (summed over the slots): each key is read once."""
    m = dims(cfg)
    return ((2 * m["hi"] * m["di"] + 2 * m["hi"]) * rows_scored,
            rows_scored * m["di"] * bytes_per_el)


def sparse_attend_cost(cfg, rows_attended, bytes_per_el=2):
    """(flops, bytes) of attending `rows_attended` selected rows in one
    layer: their K and V rows are read once."""
    m = dims(cfg)
    return (4 * m["h"] * m["dh"] * rows_attended,
            2 * m["hkv"] * m["dh"] * rows_attended * bytes_per_el)


def moe_experts_cost(cfg, pairs, experts_touched, bytes_per_el=2):
    """(flops, bytes) of one layer's expert matmuls over `pairs` (token,
    expert) rows that touch `experts_touched` distinct experts: every
    touched expert's three matrices are read once, and the rows in and
    out."""
    m, p = dims(cfg), layer_params(cfg)
    return (2 * p["expert"] * pairs,
            (experts_touched * p["expert"] + 2 * pairs * m["d"])
            * bytes_per_el)


def decode_step_bytes(cfg, experts_touched, rows_scored, rows_attended,
                      bytes_per_el=2):
    """Bytes one decode step must read.  The three counts are the step's
    sums over its layers: non-expert weights and the head once, the
    experts touched, the live index keys, the selected K and V rows."""
    m, p = dims(cfg), layer_params(cfg)
    weights = m["n_layers"] * (p["attention"] + p["indexer"] + p["router"]) \
        + m["d"] * m["v"] + experts_touched * p["expert"]
    cache = rows_scored * m["di"] + rows_attended * 2 * m["hkv"] * m["dh"]
    return (weights + cache) * bytes_per_el


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
