"""How the benchmark builds the program's TransformerLM for a
configuration with grouped KV heads, a sparse-attention indexer and
routed experts, and hands it the benchmark's own weights a layer at a
time.  The only place that knows the names of the program's parameter
tree for such a model."""
import jax
import jax.numpy as jnp

from benchmarks.reference import sparse_moe_ref as ref

ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wqi", "wki", "wwi",
        "ki_norm", "ki_bias")
MOE = ("router", "w1", "w3", "w2")


def build_model(cfg):
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    sa = cfg["sa_config"]
    assert cfg["norm_topk_prob"] and not cfg["tie_word_embeddings"]
    return TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], qk_norm=True,
        d_ff=cfg["moe_intermediate_size"], moe_experts=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_capacity_factor=None,
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_top_k=sa["topk"], max_len=cfg["max_position_embeddings"],
        dropout=0.0, rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["activation_dtype"], tie_embeddings=False))


def program_tree(cfg, key, model, dtype=None):
    """The seed's weights as the program's parameter tree, made on the
    device a layer at a time and handed over as they are: the reference
    lays each leaf out as the program stores it, so nothing is copied and
    the set-up's peak is the weights once."""
    dtype = jnp.dtype(dtype or cfg["param_dtype"])
    root = model.name
    head = jax.jit(lambda k: ref.make_head(cfg, k, dtype))(key)
    tree = {f"{root}.embed": {"weight": jax.jit(
                lambda k: ref.make_embed(cfg, k, dtype))(key)},
            f"{root}.head": {"weight": head["head"]},
            f"{root}.final_norm": {"weight": head["final_norm"]}}
    layer = jax.jit(lambda k, i: ref.make_layer(cfg, k, i, dtype))
    for i in range(model.cfg.n_layers):
        lw = layer(key, jnp.int32(i))
        b = f"{root}.block{i}"
        tree[f"{b}.attn"] = {k: lw[k] for k in ATTN}
        tree[f"{b}.moe"] = {k: lw[k] for k in MOE}
        tree[f"{b}.norm1"] = {"weight": lw["norm1"]}
        tree[f"{b}.norm2"] = {"weight": lw["norm2"]}
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    exp = jax.tree_util.tree_map(
        lambda a: a.shape, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if got != exp:
        raise ValueError("the program's parameter tree is not the one the "
                         f"adapter builds:\n{got}\nvs\n{exp}")
    return tree
