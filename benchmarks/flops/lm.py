"""Operations and bytes of the decoder-only LM, from its shapes alone.

Matmul FLOPs are 2 x MACs.  Causal attention is counted once (the lower
triangle), recomputation is never counted, and the embedding gather is
not a matmul.  `cfg` is the configuration file's dict (HF key names).
"""


def dims(cfg):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def param_count(cfg):
    """Parameters as stored: tied embeddings count once."""
    d, f, n_layers, v = dims(cfg)
    per_layer = 4 * d * d + 3 * d * f + 2 * d
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return n_layers * per_layer + v * d + d + head


def matmul_flops_per_token(cfg):
    """Forward FLOPs of one token through every projection and the head."""
    d, f, n_layers, v = dims(cfg)
    return 2 * (n_layers * (4 * d * d + 3 * d * f) + d * v)


def attention_flops(cfg, n_keys):
    """Forward QK^T + PV FLOPs of ONE query attending to n_keys keys,
    all layers (2 matmuls x 2 x hidden_size x keys)."""
    d, _, n_layers, _ = dims(cfg)
    return 4 * d * n_keys * n_layers


def forward_flops_sequence(cfg, seq_len):
    """One sequence, causal: query i sees i + 1 keys."""
    keys = seq_len * (seq_len + 1) // 2
    return seq_len * matmul_flops_per_token(cfg) + attention_flops(cfg, keys)


def train_flops_per_token(cfg, seq_len):
    """Forward + backward (backward = 2 x forward), no recomputation."""
    return 3 * forward_flops_sequence(cfg, seq_len) / seq_len


def decode_flops(cfg, context_len):
    """One decoded token whose query sees context_len keys."""
    return matmul_flops_per_token(cfg) + attention_flops(cfg, context_len)


def kv_bytes_per_token(cfg, bytes_per_el=2):
    d, _, n_layers, _ = dims(cfg)
    return 2 * d * n_layers * bytes_per_el


def decode_step_bytes(cfg, live_context_tokens, weight_bytes_per_el=2,
                      kv_bytes_per_el=2):
    """Bytes one decode step must read: every weight once plus the cached
    K and V of every live token (what the step writes is small beside)."""
    return (param_count(cfg) * weight_bytes_per_el
            + live_context_tokens * kv_bytes_per_token(cfg, kv_bytes_per_el))
