"""What decides `correct` in the transformer's cells: each reference
agrees with the program at a toy size; the control (the reference computed
in fp8, the precision below bfloat16, in the program's place) comes out as
not correct; and a run whose timed path is broken underneath comes out as
not correct, once for each fault a cell can have.  ResNet-50's are in
test_benchmark_correct_conv.py, so that tier-1 gives them a worker of
their own."""
import numpy as np

from benchmarks import harness
from benchmarks.reference import lm_ref
from _bench_common import SCALE, over, run_toy as run, \
    training_control_is_not_correct


# -- the control ------------------------------------------------------- #
def test_lm_control_in_fp8_is_not_correct():
    training_control_is_not_correct("olmo1b-l8-train", lm_ref.fp8)


def test_serving_control_in_fp8_is_not_correct():
    """At this toy size (two layers, some hundred served tokens a run) the
    fp8 forward puts another token first only on some seeds (toy readings:
    program 0.0 on every seed, control 0.0 / 0.044 / 0.0), so several are
    tried: the program is correct on each, the control is not on at least
    one.  At the cell's own size it is not on every seed (PERF.md)."""
    name = "olmo1b-serve-chat"
    cell = harness.Cell(name)
    import jax
    control_failed = 0
    for seed in range(1, 7):
        probe = harness.Probe(0.0, False, None)
        r = cell.runner().Runner(cell, seed, 1.5, jax.devices()[:1], probe,
                                 SCALE[name])
        r.run()
        r.results()
        r.release()
        assert harness.compared_ok(r.check())
        control_failed += not harness.compared_ok(r.check(quant=lm_ref.fp8))
    assert control_failed >= 1


# -- faults planted under the timed path -------------------------------- #
def test_lm_state_left_unchanged(monkeypatch):
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    real = SpmdTrainer.step

    def step(self, tokens, targets):
        import jax
        import jax.numpy as jnp
        keep = jax.tree_util.tree_map(jnp.copy, (self.params, self.opt_state))
        loss = real(self, tokens, targets)
        if self._step_count > 1:          # the first step's state stays
            self.params, self.opt_state = keep
        return loss
    monkeypatch.setattr(SpmdTrainer, "step", step)
    line = run("olmo1b-l8-train")
    assert line["correct"] is False and "dparam_norm_gap" in over(line)


def test_lm_half_of_the_batch_left_out(monkeypatch):
    from bigdl_tpu.parallel.spmd import SpmdTrainer
    real = SpmdTrainer.step

    def step(self, tokens, targets):
        h = tokens.shape[0] // 2          # the mean is taken over the rest
        return real(self, np.concatenate([tokens[:h]] * 2),
                    np.concatenate([targets[:h]] * 2))
    monkeypatch.setattr(SpmdTrainer, "step", step)
    line = run("olmo1b-l8-train")
    assert line["correct"] is False and over(line)


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from bigdl_tpu.serving import decode
    real = decode._select_tokens

    def select(logits, temps, step, base_key):
        return (real(logits, temps, step, base_key) + 1) % logits.shape[-1]
    monkeypatch.setattr(decode, "_select_tokens", select)
    line = run("olmo1b-serve-chat")
    assert line["correct"] is False and over(line) == ["logit_gap_max"]


def test_serve_request_that_never_finishes(monkeypatch):
    from benchmarks import loadgen
    real = loadgen._read

    def read(req, stream):
        if len(req.prompt) % 2:
            return real(req, stream)
        req.error = "never finished"
    monkeypatch.setattr(loadgen, "_read", read)
    line = run("olmo1b-serve-chat")
    assert line["correct"] is False and "never_finished" in over(line)
    assert line["failed"] > 0
