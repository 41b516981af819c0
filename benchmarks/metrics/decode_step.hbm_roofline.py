"""Decode step's share of its memory roofline: the bytes one step must
read (every weight once, plus the cached K and V of the live tokens of
the live slots, from shapes) at the chip's HBM rate, over the median
device duration of the decode-step program.  Memory-bound by far (one
token a slot), so the compute bound is not taken."""
from benchmarks.flops import lm
from benchmarks.metrics import _decode_program


def read(ctx):
    f = ctx["facts"]
    s = _decode_program.device_seconds(ctx["trace"])
    if s is None or not f.get("served"):
        return None
    cfg = f["config"]
    # mean context of a live slot: a request's cache grows from its
    # prompt to prompt + output, each length held for one step
    tok_steps = sum(n_new for _, n_new in f["served"])
    ctx_sum = sum(n_prompt * n_new + n_new * (n_new + 1) // 2
                  for n_prompt, n_new in f["served"])
    live_tokens = f["mean_live_slots"] * ctx_sum / max(tok_steps, 1)
    itemsize = 2 if cfg["param_dtype"] == "bfloat16" else 4
    nbytes = lm.decode_step_bytes(cfg, live_tokens, itemsize, 2)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / s
