"""How the benchmark builds the program's TransformerLM for a
configuration of latent attention (one cached row a token under many
heads, YaRN rope) and routed experts of which this chip holds a share
(sigmoid scores, group-limited top-k, a correction bias, a shared expert;
leading dense layers), and hands it the benchmark's own weights a layer
at a time.  The only place that knows the names of the program's parameter
tree for such a model."""
import jax
import jax.numpy as jnp

from benchmarks.reference import mla_moe_ref as ref

ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
MLP = ("w1", "w3", "w2")
MOE = ("router", "router_bias", "w1", "w3", "w2", "shared_w1", "shared_w3",
       "shared_w2")


def build_model(cfg):
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    assert cfg["norm_topk_prob"] and cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc" and cfg["moe_layer_freq"] == 1
    assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
    assert cfg["rope_scaling"]["type"] == "yarn" and cfg["hidden_act"] == "silu"
    return TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=cfg["rope_scaling"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        d_ff=cfg["moe_intermediate_size"],
        moe_experts=cfg["published"]["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_capacity_factor=None,
        moe_scoring="sigmoid", moe_groups=cfg["n_group"],
        moe_top_groups=cfg["topk_group"],
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_router_bias=True, moe_held=tuple(cfg["held_experts"]),
        moe_shared_d_ff=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        max_len=cfg["max_position_embeddings"], dropout=0.0,
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
    layer = jax.jit(lambda k, i, dense: ref.make_layer(cfg, k, i, dtype,
                                                       dense),
                    static_argnums=2)
    for i in range(model.cfg.n_layers):
        dense = ref.is_dense(cfg, i)
        lw = layer(key, jnp.int32(i), dense)
        b = f"{root}.block{i}"
        tree[f"{b}.attn"] = {k: lw[k] for k in ATTN}
        if dense:
            tree[f"{b}.mlp"] = {k: lw[k] for k in MLP}
        else:
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
