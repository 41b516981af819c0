"""Median device duration of the prefill-chunk program
(`jit_prefill_chunk`) in the trace."""
import statistics


def read(ctx):
    secs = [s for name, runs in ctx["trace"]["modules"].items()
            if "prefill_chunk" in name for s in runs]
    return 1e3 * statistics.median(secs) if secs else None
