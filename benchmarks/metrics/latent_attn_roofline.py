"""The decode step's attention (`_latent_attend`: every layer's gather of
the slots' tables and the absorbed attention over them): its roofline
seconds (the larger of the latent rows its queries may see, read once at
the HBM rate, and its absorbed FLOPs at the peak) over its ops' device
time in the trace.  The count does not depend on the route: a kernel that
reads only the live rows moves this towards 100."""
from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe


def read(ctx):
    per_step, _ = _mla_moe.traced_rates(ctx)
    return _mla_moe.piece_roofline(
        ctx, "_latent_attend", per_step,
        len(_mla_moe.decode_steps(ctx["trace"])),
        lambda cfg, c: mla_moe.latent_attend_cost(cfg, c["mla_rows_live"]))
