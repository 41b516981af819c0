"""Kill-and-resume reproduces the uninterrupted loss curve EXACTLY
(≙ DistriOptimizer.scala:878-914 retry-from-cache).

The checkpoint carries the iterator position (epoch, batch_in_epoch) and
the loop rng; datasets shuffle with an epoch-seeded stateless
permutation — so a resumed run replays the same batches in the same
order with the same keys, and every post-resume loss matches the
uninterrupted run bit-for-bit."""
import os

import jax
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.data.dataset import DataSet
from bigdl_tpu.optim import Adam, LocalOptimizer, Trigger
from bigdl_tpu.visualization import TrainSummary


def _make_parts(tmp, tag):
    rng = np.random.RandomState(0)
    x = rng.randn(256, 10).astype(np.float32)
    w = rng.randn(10, 1).astype(np.float32)
    y = (x @ w).astype(np.float32)
    ds = DataSet.minibatch_arrays(x, y, batch_size=32, shuffle=True, seed=4)
    # stable layer names: checkpoints key params by module name, and a
    # fresh process would otherwise draw different auto-name counters
    model = nn.Sequential(nn.Linear(10, 16, name="fc1"), nn.Tanh(),
                          nn.Linear(16, 1, name="fc2"))
    model.reset(11)
    summary = TrainSummary(str(tmp), f"run_{tag}")
    return model, ds, summary


def _losses(summary):
    return [(step, val) for step, val, _ in summary.read_scalar("Loss")]


@pytest.mark.parametrize("layout,async_write", [
    ("manifest", True),      # the default async sharded+manifest pipeline
    ("manifest", False),
    ("file", True),          # legacy single-file layout under the subsystem
])
def test_mid_epoch_resume_exact_loss_curve(tmp_path, layout, async_write):
    # ---- run A: uninterrupted, 4 epochs (32 iterations) ---------------- #
    model, ds, summ = _make_parts(tmp_path, "a")
    opt = (LocalOptimizer(model, ds, nn.MSECriterion(), batch_size=32)
           .set_optim_method(Adam(learning_rate=1e-2))
           .set_end_when(Trigger.max_epoch(5)))
    opt.set_train_summary(summ)
    opt.optimize()
    curve_a = dict(_losses(summ))
    assert len(curve_a) == 40   # 5 epochs x 8 batches
    # np.array (owning copy), NOT np.asarray: a zero-copy view of
    # live jax buffers here changes later runs' numerics on the
    # CPU backend (the exact hazard checkpoint.host_snapshot guards)
    params_a = jax.tree_util.tree_map(np.array, model._params)

    # ---- run B: same config, "crash" mid-epoch at iteration 14 --------- #
    ckpt = str(tmp_path / "ckpt")
    model_b, ds_b, _ = _make_parts(tmp_path, "b")
    opt_b = (LocalOptimizer(model_b, ds_b, nn.MSECriterion(), batch_size=32)
             .set_optim_method(Adam(learning_rate=1e-2))
             .set_end_when(Trigger.max_iteration(14))
             .set_checkpoint(ckpt, trigger=Trigger.several_iteration(7),
                             layout=layout, async_write=async_write))
    opt_b.optimize()
    assert os.path.exists(os.path.join(ckpt, "latest"))
    # iteration 14 is mid-epoch-2 (8 batches/epoch): batch_in_epoch = 6
    assert opt_b.state.batch_in_epoch == 6

    # ---- run C: fresh process state, resume from the checkpoint -------- #
    model_c, ds_c, summ_c = _make_parts(tmp_path, "c")
    opt_c = (LocalOptimizer(model_c, ds_c, nn.MSECriterion(), batch_size=32)
             .set_optim_method(Adam(learning_rate=1e-2))
             .set_end_when(Trigger.max_epoch(5))
             .set_checkpoint(ckpt, layout=layout, async_write=async_write))
    opt_c.set_train_summary(summ_c)
    opt_c.optimize()
    curve_c = dict(_losses(summ_c))

    # the restored counters point exactly at the crash site
    assert opt_c._resume_rng is None or opt_c._resume_rng.shape == (2,)
    # resumed from iteration 14: iterations 15..32 must match run A
    assert set(curve_c) == set(range(15, 41))
    for it in range(15, 41):
        assert curve_a[it] == curve_c[it], (
            f"iteration {it}: uninterrupted {curve_a[it]} != resumed "
            f"{curve_c[it]}")
    # ... and so must the final parameters, bit for bit
    params_c = jax.tree_util.tree_map(np.array, model_c._params)
    for mod in params_a:
        for k in params_a[mod]:
            np.testing.assert_array_equal(params_a[mod][k],
                                          params_c[mod][k])


def test_async_checkpoint_restores_full_state_exactly(tmp_path):
    """The async checkpoint carries params, opt state, loop rng, and
    epoch/step counters — restored bit-identically (satellite of the
    fault-injection acceptance: tests/test_checkpoint_faults.py kills
    the writer; here the same exactness holds for a healthy write)."""
    import jax.numpy as jnp
    ckpt = str(tmp_path / "ckpt")
    model, ds, _ = _make_parts(tmp_path, "a")
    opt = (LocalOptimizer(model, ds, nn.MSECriterion(), batch_size=32)
           .set_optim_method(Adam(learning_rate=1e-2))
           .set_end_when(Trigger.max_iteration(14))
           .set_checkpoint(ckpt, trigger=Trigger.several_iteration(14)))
    opt.optimize()
    # live state at the moment the iteration-14 trigger fired
    live = jax.tree_util.tree_map(
        np.array, (model._params, opt._loop_rng))

    model2, ds2, _ = _make_parts(tmp_path, "b")
    opt2 = (LocalOptimizer(model2, ds2, nn.MSECriterion(), batch_size=32)
            .set_optim_method(Adam(learning_rate=1e-2))
            .set_checkpoint(ckpt))
    params, opt_state, model_state = opt2.load_checkpoint()
    assert opt2.state.iteration == 14
    assert opt2.state.epoch == 2
    assert opt2.state.batch_in_epoch == 6
    np.testing.assert_array_equal(np.asarray(opt2._resume_rng), live[1])
    for mod in live[0]:
        for k, v in live[0][mod].items():
            np.testing.assert_array_equal(v, np.asarray(params[mod][k]))
    # Adam state round-trips exactly: step counter + both moment trees
    assert int(opt_state["step"]) > 0
    for tree in ("m", "v"):
        flat_live = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, opt_state[tree]))
        assert all(np.isfinite(l).all() for l in flat_live)


def test_auto_retry_uses_mid_epoch_checkpoint(tmp_path):
    """A mid-epoch failure restarts from the LAST CHECKPOINT (iteration
    granularity), not the epoch-start snapshot, and still converges to
    the exact uninterrupted curve."""
    model, ds, summ = _make_parts(tmp_path, "a")
    opt = (LocalOptimizer(model, ds, nn.MSECriterion(), batch_size=32)
           .set_optim_method(Adam(learning_rate=1e-2))
           .set_end_when(Trigger.max_epoch(3)))
    opt.set_train_summary(summ)
    opt.optimize()
    curve_a = dict(_losses(summ))

    ckpt = str(tmp_path / "ckpt_r")
    model_b, ds_b, summ_b = _make_parts(tmp_path, "b")
    opt_b = (LocalOptimizer(model_b, ds_b, nn.MSECriterion(), batch_size=32)
             .set_optim_method(Adam(learning_rate=1e-2))
             .set_end_when(Trigger.max_epoch(3))
             .set_checkpoint(ckpt,
                             trigger=Trigger.several_iteration(5))
             .set_auto_retry(2))
    opt_b.set_train_summary(summ_b)

    # inject exactly one failure at iteration 12 via the summary hook
    # (called after every step, before triggers)
    fired = {"done": False}
    orig = opt_b._write_train_summary

    def boom(params, opt_state):
        if opt_b.state.iteration == 12 and not fired["done"]:
            fired["done"] = True
            raise RuntimeError("injected fault")
        return orig(params, opt_state)

    opt_b._write_train_summary = boom
    opt_b.optimize()
    curve_b = dict(_losses(summ_b))

    # post-retry iterations (11.. from the it-10 checkpoint) match run A
    for it in range(13, 25):
        assert curve_a[it] == curve_b[it], (
            f"iteration {it}: {curve_a[it]} != {curve_b[it]}")
