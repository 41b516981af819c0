"""The (token, expert) pairs this chip's experts computed over the pairs
its router chose, decode steps and chunks together: `moe/pairs` over
`moe/pairs_routed`.  A chip that holds 16 of 256 experts expects 6.25%; a
layer that computes experts it does not hold, or drops its own, shows
here."""


def read(ctx):
    f = ctx["facts"]
    routed = f.get("moe_pairs_routed", 0.0) \
        + f.get("moe_prefill_pairs_routed", 0.0)
    if not routed:
        return None
    return 100.0 * (f["moe_pairs"] + f["moe_prefill_pairs"]) / routed
