"""How the benchmark builds the program's TransformerLM from a
configuration file and hands it the benchmark's own weights.  Shared by
the runners that drive that model; the only place that knows the names of
the program's parameter tree."""
import jax
import jax.numpy as jnp


def build_model(cfg, remat=False):
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    return TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"], dropout=0.0,
        rope_theta=cfg["rope_theta"], dtype=cfg["activation_dtype"],
        remat=bool(remat), tie_embeddings=cfg["tie_word_embeddings"]))


def leaf_names(model):
    """{plain reference name: (program module name, key)}."""
    root = model.name
    names = {"embed": (f"{root}.embed", "weight"),
             "final_norm": (f"{root}.final_norm", "weight")}
    for i in range(model.cfg.n_layers):
        b = f"{root}.block{i}"
        for k in ("wq", "wk", "wv", "wo"):
            names[f"layers.{i}.{k}"] = (f"{b}.attn", k)
        for k in ("w1", "w2", "w3"):
            names[f"layers.{i}.{k}"] = (f"{b}.mlp", k)
        names[f"layers.{i}.norm1"] = (f"{b}.norm1", "weight")
        names[f"layers.{i}.norm2"] = (f"{b}.norm2", "weight")
    return names


def to_program_tree(plain, model):
    """The plain reference weights as the program's parameter tree, checked
    leaf by leaf against the shapes the program's own init would give."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tree = {}
    for name, (mod, key) in leaf_names(model).items():
        node = plain
        for part in name.split("."):
            node = node[int(part)] if isinstance(node, list) else node[part]
        tree.setdefault(mod, {})[key] = node
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    exp = jax.tree_util.tree_map(lambda a: a.shape, want)
    if got != exp:
        raise ValueError("the program's parameter tree is not the one the "
                         f"adapter builds:\n{got}\nvs\n{exp}")
    return tree


def assert_pallas_route(cfg, batch, seq, platform):
    """On the chip the flash kernels must be the route taken."""
    from bigdl_tpu.ops import attention_path
    heads = cfg["num_attention_heads"]
    shape = (batch, heads, seq, cfg["hidden_size"] // heads)
    path, why = attention_path(shape, shape,
                               jnp.dtype(cfg["activation_dtype"]))
    if platform == "tpu" and path != "pallas":
        raise RuntimeError(f"attention takes {path}: {why}")
    return path
