"""Operations and bytes of a decoder whose layers are of two kinds
(sliding-window and global attention over grouped KV heads), each with
routed experts, from its shapes and from what the program COUNTED
(experts touched, rows its queries may see by layer kind), never from how
the program computes it.

Matmul FLOPs are 2 x MACs.  A query at context c (the keys up to its own,
itself included) attends min(c, window) keys in a window layer and c in a
global one.  `cfg` is the configuration file's dict (HF key names).
"""


def dims(cfg):
    n = cfg["num_hidden_layers"]
    n_window = sum(1 for on in cfg["sliding_window_layout"][:n] if on)
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                e=cfg["moe_num_primary_experts"],
                k=cfg["moe_num_active_primary_experts"],
                f=cfg["moe_ffn_hidden_size"], v=cfg["vocab_size"],
                w=cfg["sliding_window_size"], n_layers=n,
                n_window=n_window, n_global=n - n_window)


def layer_params(cfg):
    """Parameters of one layer by part (the two norm gains apart)."""
    m = dims(cfg)
    d = m["d"]
    return {"attention": 2 * d * m["h"] * m["dh"] + 2 * d * m["hkv"] * m["dh"],
            "router": d * m["e"],
            "expert": 3 * d * m["f"],
            "norms": 2 * d}


def layer_param_count(cfg):
    m, p = dims(cfg), layer_params(cfg)
    return p["attention"] + p["router"] + m["e"] * p["expert"] + p["norms"]


def param_count(cfg):
    """Every parameter held: the layers, embedding, untied head and the
    final norm's gains."""
    m = dims(cfg)
    return m["n_layers"] * layer_param_count(cfg) \
        + 2 * m["v"] * m["d"] + m["d"]


def layer_flops_per_token(cfg):
    """Forward matmul FLOPs of one token through one layer's projections,
    router and its top-k experts."""
    m, p = dims(cfg), layer_params(cfg)
    return 2 * (p["attention"] + p["router"] + m["k"] * p["expert"])


def head_flops(cfg):
    m = dims(cfg)
    return 2 * m["d"] * m["v"]


def attention_flops(cfg, context, windowed):
    """One query at `context` in one layer of the kind: QK^T and PV over
    the rows it attends."""
    m = dims(cfg)
    attended = min(context, m["w"]) if windowed else context
    return 4 * m["h"] * m["dh"] * attended


def sequence_flops(cfg, n_prompt, n_new):
    """Forward FLOPs of serving one request: every prompt token through
    the layers, the head once for the first token, then n_new - 1 decoded
    tokens through layers and head; each query against its own context,
    by layer kind."""
    m = dims(cfg)
    n_tok = n_prompt + n_new - 1
    # sums over contexts c = 1..n_tok of c and of min(c, window)
    seen = n_tok * (n_tok + 1) // 2
    full = min(n_tok, m["w"])
    in_window = full * (full + 1) // 2 + (n_tok - full) * m["w"]
    att = 4 * m["h"] * m["dh"] * (m["n_global"] * seen
                                  + m["n_window"] * in_window)
    return m["n_layers"] * n_tok * layer_flops_per_token(cfg) + att \
        + n_new * head_flops(cfg)


# -- one decode step, from what the program counted ---------------------- #
def attend_cost(cfg, rows_attended, bytes_per_el=2):
    """(flops, bytes) of attending `rows_attended` rows (summed over slots
    and layers): their K and V rows are read once."""
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


def decode_step_bytes(cfg, experts_touched, rows_attended, bytes_per_el=2):
    """Bytes one decode step must read.  The two counts are the step's
    sums over its layers: non-expert weights and the head once, the
    experts touched, the K and V rows attended (window and global layers
    together)."""
    m, p = dims(cfg), layer_params(cfg)
    weights = m["n_layers"] * (p["attention"] + p["router"] + p["norms"]) \
        + m["d"] * m["v"] + m["d"] + experts_touched * p["expert"]
    cache = rows_attended * 2 * m["hkv"] * m["dh"]
    return (weights + cache) * bytes_per_el


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """K and V of one token in one layer."""
    m = dims(cfg)
    return 2 * m["hkv"] * m["dh"] * bytes_per_el


def pages_per_slot(cfg, engine):
    """(global table's pages, a window layer's ring) a slot at the
    engine's sizes: max_context / page, and window / page + chunk / page
    + 1."""
    page = engine["page_size"]
    glob = -(-engine["max_context"] // page)
    ring = -(-dims(cfg)["w"] // page) + -(-engine["prefill_chunk"] // page) + 1
    return glob, min(glob, ring)


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
