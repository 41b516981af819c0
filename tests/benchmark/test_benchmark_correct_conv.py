"""What decides `correct` in the ResNet-50 cell: the reference agrees
with the program at a toy size, the control one precision down does not,
and a run whose timed path is broken underneath comes out as not correct
(a state left unchanged; half of the batch left out)."""
from benchmarks.reference import lm_ref
from _bench_common import over, run_toy as run, \
    training_control_is_not_correct


def test_conv_control_one_precision_down_is_not_correct():
    # the toy size states float32 (_bench_common.SCALE): bfloat16 below it
    training_control_is_not_correct("resnet50-train", lm_ref.bf16)


def _patched_train_step(alter_out=None, alter_in=None):
    from bigdl_tpu.optim import optimizer as opt_mod
    real = opt_mod.make_train_step

    def make(*a, **kw):
        inner = real(*a, **kw)

        def step(params, opt_state, model_state, x, y, rng):
            if alter_in is not None:
                x, y = alter_in(x, y)
            out = inner(params, opt_state, model_state, x, y, rng)
            if alter_out is not None:
                out = alter_out(params, opt_state, out)
            return out
        return step
    return make


def test_conv_state_left_unchanged(monkeypatch):
    from bigdl_tpu.optim import optimizer as opt_mod
    unchanged = lambda params, opt_state, out: (params, opt_state) + out[2:]
    monkeypatch.setattr(opt_mod, "make_train_step",
                        _patched_train_step(alter_out=unchanged))
    line = run("resnet50-train")
    assert line["correct"] is False
    assert {"grad_norm_gap", "dparam_norm_gap"} <= set(over(line))


def test_conv_half_of_the_batch_left_out(monkeypatch):
    import jax.numpy as jnp
    from bigdl_tpu.optim import optimizer as opt_mod

    def half(x, y):
        h = x.shape[0] // 2
        return (jnp.concatenate([x[:h]] * 2), jnp.concatenate([y[:h]] * 2))
    monkeypatch.setattr(opt_mod, "make_train_step",
                        _patched_train_step(alter_in=half))
    line = run("resnet50-train")
    assert line["correct"] is False and over(line)
