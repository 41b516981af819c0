"""A prompt chunk's attention (`_latent_chunk_attend`: every layer's
gather of the longest prompt's table and the chunk's attention): the
up-projected FLOPs over the keys its queries may SEE (the slot's live
rows through W_kvb, then each query against its visible keys: the
program's counters) at the chip's peak, over its ops' device time in the
trace.  The table is padded to the longest prompt, and every key of it is
scored whatever the chunk's offset: that shows as a low share."""
from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe


def read(ctx):
    _, per_chunk = _mla_moe.traced_rates(ctx)
    return _mla_moe.piece_roofline(
        ctx, "_latent_chunk_attend", per_chunk,
        len(_mla_moe.chunk_runs(ctx["trace"])),
        lambda cfg, c: (mla_moe.chunk_attention_flops(
            cfg, c["mla_chunk_rows_live"], c["mla_chunk_rows_visible"]), 0))
