"""How the benchmark builds the program's ResNet from a configuration
file and hands it the benchmark's own weights; the only place that knows
the program's parameter tree for this model."""
import jax
import jax.numpy as jnp


def build_model(cfg):
    from bigdl_tpu.models import resnet
    return resnet.build(class_num=cfg["class_num"], depth=cfg["depth"],
                        shortcut_type=cfg["shortcut_type"],
                        dataset="imagenet", format=cfg["format"])


def leaf_names(model, plain):
    """{plain reference name: (program module name, key)}: the program's
    convolutions, batch norms and classifier in module order are the
    reference's in definition order (main path, then the projection)."""
    order = [("conv1", "conv"), ("bn1", "bn")]
    for i, b in enumerate(plain["blocks"]):
        order += [(f"blocks.{i}.c1", "conv"), (f"blocks.{i}.b1", "bn"),
                  (f"blocks.{i}.c2", "conv"), (f"blocks.{i}.b2", "bn"),
                  (f"blocks.{i}.c3", "conv"), (f"blocks.{i}.b3", "bn")]
        if "sc" in b:
            order += [(f"blocks.{i}.sc", "conv"), (f"blocks.{i}.sb", "bn")]
    order.append(("fc", "fc"))
    kinds = {"SpatialConvolution": "conv", "SpaceToDepthConvolution": "conv",
             "SpatialBatchNormalization": "bn", "Linear": "fc"}
    mods = [m for m in model.modules() if type(m).__name__ in kinds]
    if len(mods) != len(order):
        raise ValueError(f"the program has {len(mods)} parameterised "
                         f"modules, the reference {len(order)}")
    names = {}
    for (ref, kind), m in zip(order, mods):
        if kinds[type(m).__name__] != kind:
            raise ValueError(f"{ref} is a {kind}, the program's {m.name} "
                             f"a {type(m).__name__}")
        if kind == "conv":
            names[ref] = (m.name, "weight")
        elif kind == "bn":
            names[ref + ".scale"] = (m.name, "weight")
            names[ref + ".shift"] = (m.name, "bias")
        else:
            names[ref + ".weight"] = (m.name, "weight")
            names[ref + ".bias"] = (m.name, "bias")
    return names


def plain_leaf(plain, name):
    node = plain
    for part in name.split("."):
        node = node[int(part)] if isinstance(node, list) else node[part]
    return node


def to_program_tree(plain, model, names):
    want, state = jax.eval_shape(lambda: model.init_params(0))
    tree = {}
    for ref, (mod, key) in names.items():
        tree.setdefault(mod, {})[key] = plain_leaf(plain, ref)
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    exp = jax.tree_util.tree_map(lambda a: a.shape, want)
    if got != exp:
        raise ValueError("the program's parameter tree is not the one the "
                         f"adapter builds:\n{got}\nvs\n{exp}")
    return tree, jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), state)
