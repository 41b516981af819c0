"""Which program of the trace is the decode step: the engine names both
its prefill and decode functions `fn`, so the step is taken as the
module that ran most often in the traced window."""
import statistics


def device_seconds(trace):
    mods = {k: v for k, v in trace["modules"].items() if "fn" in k}
    if not mods:
        return None
    name = max(mods, key=lambda k: len(mods[k]))
    return statistics.median(mods[name])
