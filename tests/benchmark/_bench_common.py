"""Sizes, paths and helpers shared by the benchmark's tests, which tier-1 collects
(`tests/benchmark/` is one of the benchmark's `paths`) and which run on
the CPU at toy sizes:

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark -q

They never look for a chip and never describe a TPU topology.  A module
of its own name, not a conftest: `tests/conftest.py` is the suite's."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Sizes a CPU can hold.  ResNet keeps its depth and 224 x 224 (the program
# builds no smaller bottleneck net); only the batch and the classes shrink.
TINY_LM = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=2, max_position_embeddings=128)
SCALE = {
    "olmo1b-l8-train": {
        "config": TINY_LM,
        "traffic": dict(batch=4, seq_len=64, loss_chunk=32,
                        # toy-size readings over three seeds: program
                        # 5e-4 / 6e-4, fp8 control 5.5e-3 / 1.4e-3 at least
                        limits={"loss_step1": 1e-3, "loss_step2": 1e-3,
                                "loss_step3": 1e-3, "grad_norm_gap": 2e-3,
                                "dparam_norm_gap": 2e-3})},
    "resnet50-train": {
        "config": dict(class_num=10),
        # batch 4 at the cell's learning rate overshoots (each sample is a
        # quarter of the mean): the rate is scaled with the batch.  At four
        # images BatchNorm's statistics amplify rounding so far that bf16
        # and fp8 read alike (2% of the loss), so the toy size states
        # float32 (mixed precision off) and its control is bfloat16
        "traffic": dict(batch=4, iterations_per_epoch=50,
                        mixed_precision=False, input_dtype="float32",
                        leaf_statistic="worst",
                        optimizer={"name": "sgd", "learning_rate": 0.002,
                                   "momentum": 0.9, "dampening": 0.9},
                        # toy-size readings over three seeds: program
                        # 0.012 / 0.065 at most, bf16 control 0.23 / 0.21
                        # at least (norms read through a 0.0002 step)
                        limits={"loss_step1": 5e-3, "loss_step2": 5e-3,
                                "loss_step3": 5e-3, "grad_norm_gap": 0.05,
                                "dparam_norm_gap": 0.12})},
    "olmo1b-serve-chat": {
        "config": dict(TINY_LM, vocab_size=8192),
        "traffic": dict(
            engine={"slots": 4, "page_size": 16, "max_context": 128,
                    "max_prompt": 64, "max_new_tokens": 32},
            prompt_len={"median": 24, "sigma": 0.6, "min": 4, "max": 64},
            output_len={"median": 12, "sigma": 0.6, "min": 2, "max": 32},
            drain_s=30.0, checked_requests=16,
            limits={"logit_gap_max": 0.01})},
}


def run_toy(name, **kw):
    """One run of a cell through the harness at its toy size."""
    from benchmarks import harness
    return harness.run_cell(harness.Cell(name), 321, 1.0, 0,
                            require_chip=False, scale=SCALE[name], **kw)


def over(line):
    """The compared numbers of a result line that passed their limits."""
    return sorted(k for k, c in line["compared"].items()
                  if not c["value"] <= c["limit"])


def training_control_is_not_correct(name, quant):
    """The program agrees with the reference; the reference computed one
    precision down, put in the program's place, does not."""
    import jax
    from benchmarks import harness
    from benchmarks.runners import compare
    cell = harness.Cell(name)
    probe = harness.Probe(0.0, False, None)
    r = cell.runner().Runner(cell, 99, 0.5, jax.devices()[:1], probe,
                             SCALE[name])
    r.run()
    r.results()
    limits = SCALE[name]["traffic"]["limits"]
    stat = r.tr.get("leaf_statistic", "worst")
    ref = r.reference()
    assert harness.compared_ok(compare.training(r.first, ref, limits, stat))
    control = compare.training(r.reference(quant=quant), ref, limits, stat)
    assert not harness.compared_ok(control), control
    r.release()
