"""Operations and bytes of a decoder of latent attention (one cached row a
token under many heads) and routed experts of which the chip holds a
share, from its shapes and from what the program COUNTED (latent rows its
queries may see, held pairs, experts touched), never from how the program
computes it.

Matmul FLOPs are 2 x MACs.  Attention is counted by the formula that does
the least work where it is counted: ABSORBED in a decode step (a query
head scores a row's `rank + rope` columns and sums its `rank`: no per-head
key or value is made), UP-PROJECTED in a prompt chunk (every row the
chunk's slot holds goes through W_kvb once a chunk, then a query head
meets a key of `nope + rope` and a value of `v`).  `cfg` is the
configuration file's dict (HF key names).
"""


def dims(cfg):
    first, count = cfg["held_experts"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                q_rank=cfg["q_lora_rank"], rank=cfg["kv_lora_rank"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                v=cfg["v_head_dim"], dense_f=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"],
                shared_f=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"],
                e=cfg["published"]["n_routed_experts"], held=count,
                k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
                n_layers=cfg["num_hidden_layers"],
                n_dense=cfg["first_k_dense_replace"],
                n_expert=cfg["num_hidden_layers"]
                - cfg["first_k_dense_replace"])


def layer_params(cfg):
    """Parameters of one layer by part."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    up = m["rank"] * h * (m["nope"] + m["v"])            # W_kvb
    return {
        "attention": d * m["q_rank"] + m["q_rank"]
        + m["q_rank"] * h * (m["nope"] + m["rope"])
        + d * (m["rank"] + m["rope"]) + m["rank"] + up + h * m["v"] * d,
        "up_projection": up,
        "norms": 2 * d,
        "dense_mlp": 3 * d * m["dense_f"],
        "expert": 3 * d * m["f"],
        "shared": 3 * d * m["shared_f"],
        "router": d * m["e"] + m["e"]}                   # and its bias


def dense_layer_param_count(cfg):
    p = layer_params(cfg)
    return p["attention"] + p["norms"] + p["dense_mlp"]


def expert_layer_param_count(cfg, held=None):
    """An expert layer with `held` of its routed experts (the
    configuration's own share by default; 0: what every chip holds)."""
    m, p = dims(cfg), layer_params(cfg)
    held = m["held"] if held is None else held
    return p["attention"] + p["norms"] + p["shared"] + p["router"] \
        + held * p["expert"]


def param_count(cfg):
    """Every parameter held: the layers, embedding, untied head and the
    final norm's gains."""
    m = dims(cfg)
    return m["n_dense"] * dense_layer_param_count(cfg) \
        + m["n_expert"] * expert_layer_param_count(cfg) \
        + 2 * m["vocab"] * m["d"] + m["d"]


def latent_row_bytes(cfg, bytes_per_el=2):
    """What one token costs the cache in one layer."""
    m = dims(cfg)
    return (m["rank"] + m["rope"]) * bytes_per_el


def pool_bytes(cfg, engine, bytes_per_el=2):
    """The latent pools of every layer, sized for every slot at
    `max_context`."""
    page = engine["page_size"]
    return engine["slots"] * -(-engine["max_context"] // page) * page \
        * latent_row_bytes(cfg, bytes_per_el) * dims(cfg)["n_layers"]


# -- one token through the layers, attention's keys apart ----------------- #
def token_flops(cfg, decoded):
    """Forward matmul FLOPs of one token through every layer's
    projections, dense MLP, router and shared expert: everything but the
    attention over keys and the routed experts.  A decoded token's include
    its absorption and its values' up-projection (W_kvb once); a prompt
    token's own row is up-projected inside its chunk's attention, which
    `chunk_attention_flops` counts."""
    m, p = dims(cfg), layer_params(cfg)
    attn = p["attention"] - m["q_rank"] - m["rank"] \
        - (0 if decoded else p["up_projection"])
    return 2 * (m["n_layers"] * attn + m["n_dense"] * p["dense_mlp"]
                + m["n_expert"] * (p["shared"] + p["router"] - m["e"]))


def head_flops(cfg):
    m = dims(cfg)
    return 2 * m["d"] * m["vocab"]


def decode_attention_flops(cfg, rows_live):
    """Absorbed: every head scores `rank + rope` columns of a row and sums
    its `rank`.  `rows_live` is summed over slots and layers."""
    m = dims(cfg)
    return 2 * m["h"] * (2 * m["rank"] + m["rope"]) * rows_live


def chunk_attention_flops(cfg, rows_live, rows_visible):
    """Up-projected: each of the slot's `rows_live` rows through W_kvb,
    then each (query, visible key) pair a key of `nope + rope` and a value
    of `v` a head.  Both counts are summed over the layers."""
    m, p = dims(cfg), layer_params(cfg)
    return 2 * p["up_projection"] * rows_live \
        + 2 * m["h"] * (m["nope"] + m["rope"] + m["v"]) * rows_visible


def window_flops(cfg, served, counts):
    """Forward FLOPs of a serving window: `served` [(prompt tokens, reply
    tokens)] through the layers and the head, attention and the routed
    experts by what the program counted over the same window (`counts`:
    mla_rows_live, mla_chunk_rows_live, mla_chunk_rows_visible, moe_pairs,
    moe_prefill_pairs)."""
    p = layer_params(cfg)
    prompt = sum(n for n, new in served if new)
    decoded = sum(new - 1 for _, new in served if new)
    replies = sum(new for _, new in served)
    return prompt * token_flops(cfg, False) \
        + decoded * token_flops(cfg, True) + replies * head_flops(cfg) \
        + decode_attention_flops(cfg, counts["mla_rows_live"]) \
        + chunk_attention_flops(cfg, counts["mla_chunk_rows_live"],
                                counts["mla_chunk_rows_visible"]) \
        + 2 * p["expert"] * (counts["moe_pairs"]
                             + counts["moe_prefill_pairs"])


# -- one decode step, from what the program counted ---------------------- #
def latent_attend_cost(cfg, rows_live, bytes_per_el=2):
    """(flops, bytes) of the decode step's attention over `rows_live` rows
    (summed over slots and layers): a row is read once."""
    return (decode_attention_flops(cfg, rows_live),
            rows_live * latent_row_bytes(cfg, bytes_per_el))


def moe_experts_cost(cfg, pairs, experts_touched, bytes_per_el=2):
    """(flops, bytes) of the routed experts' matmuls over `pairs` held
    (token, expert) rows that touch `experts_touched` experts (both
    summed over the layers): a touched expert's three matrices are read
    once, and the rows in and out."""
    m, p = dims(cfg), layer_params(cfg)
    return (2 * p["expert"] * pairs,
            (experts_touched * p["expert"] + 2 * pairs * m["d"])
            * bytes_per_el)


def decode_step_bytes(cfg, experts_touched, rows_live, bytes_per_el=2):
    """Bytes one decode step must read: what every chip holds of the
    layers (attention, norms, the dense MLP, shared experts, routers) and
    the head once, the routed experts touched, the latent rows its
    queries may see (both counts summed over the layers)."""
    m, p = dims(cfg), layer_params(cfg)
    weights = m["n_dense"] * dense_layer_param_count(cfg) \
        + m["n_expert"] * expert_layer_param_count(cfg, 0) \
        + m["d"] * m["vocab"] + m["d"] + experts_touched * p["expert"]
    return weights * bytes_per_el \
        + rows_live * latent_row_bytes(cfg, bytes_per_el)


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
