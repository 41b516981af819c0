"""How the benchmark builds the program's TransformerLM for a
configuration whose layers are of two kinds (sliding-window with rope,
global without) over grouped KV heads, with routed ReGLU experts whose
router reads the block's input, and hands it the benchmark's own weights
a layer at a time.  The only place that knows the names of the program's
parameter tree for such a model."""
import jax
import jax.numpy as jnp

from benchmarks.reference import window_moe_ref as ref

ATTN = ("wq", "wk", "wv", "wo")
MOE = ("router", "w1", "w3", "w2")


def build_model(cfg):
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    assert cfg["norm_topk_prob"] and cfg["moe_primary_router_apply_softmax"]
    assert not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None
    n = cfg["num_hidden_layers"]
    return TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=n, d_ff=cfg["moe_ffn_hidden_size"],
        moe_experts=cfg["moe_num_primary_experts"],
        moe_top_k=cfg["moe_num_active_primary_experts"],
        moe_capacity_factor=None, moe_activation="relu",
        moe_router_pre_attention=True,
        windows=[cfg["sliding_window_size"] if on else 0
                 for on in cfg["sliding_window_layout"][:n]],
        rope_layers=[bool(on) for on in cfg["rope_layout"][:n]],
        max_len=cfg["max_position_embeddings"], dropout=0.0,
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["activation_dtype"],
        tie_embeddings=False))


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
