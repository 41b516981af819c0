"""Of a layer's experts, the share a decode step's tokens touched (it
sets the bytes the step streams): `moe/experts_touched` over steps x
layers x experts."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("moe_pairs") or not f.get("steps"):
        return None
    cfg = f["config"]
    return 100.0 * f["moe_experts_touched"] / (
        f["steps"] * cfg["num_hidden_layers"] * cfg["num_experts"])
