"""How uneven the routing of the window's decode steps was: the fullest
expert's tokens over the mean expert's (`moe/expert_load_max` over
`moe/pairs` / experts, both summed over steps and layers)."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("moe_pairs"):
        return None
    return f["moe_expert_load_max"] * f["config"]["num_experts"] \
        / f["moe_pairs"]
