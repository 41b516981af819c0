"""Shared example plumbing: package path + argument helper.

Run any example with `python examples/<name>.py [--epochs N] [--batch N]`.
On a machine with a TPU attached the examples use it; set
JAX_PLATFORMS=cpu in the environment to run on the CPU.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args(**defaults):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=defaults.get("epochs", 2))
    p.add_argument("--batch", type=int, default=defaults.get("batch", 64))
    p.add_argument("--data-dir", default=defaults.get("data_dir", "/tmp/data"))
    p.add_argument("--lr", type=float, default=defaults.get("lr", 1e-3))
    p.add_argument("--telemetry", default=None, metavar="JSONL",
                   help="write per-step telemetry records here; render "
                        "with `python scripts/trace_summary.py steps "
                        "<file>`")
    return p.parse_args()


def make_recorder(args):
    """A Recorder with a JsonlSink at --telemetry, or None if the flag
    is unset.  Pass to optimizer.set_telemetry()."""
    if not getattr(args, "telemetry", None):
        return None
    from bigdl_tpu.observability import JsonlSink, Recorder
    return Recorder(sinks=[JsonlSink(args.telemetry)])
