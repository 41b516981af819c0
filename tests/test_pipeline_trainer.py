"""GPipe PipelineLMTrainer: loss/trajectory parity with a single-process
reference on the virtual CPU mesh (pp=2, and dp×pp)."""
import pytest
import numpy as np
import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import (TransformerLM, TransformerConfig,
                                          lm_cross_entropy)
from bigdl_tpu.optim import SGD
from bigdl_tpu.parallel import mesh as mesh_lib
from bigdl_tpu.parallel.pipeline import PipelineLMTrainer


def _model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=4,
                            n_heads=4, d_ff=64, max_len=16, dropout=0.0)
    return TransformerLM(cfg)


def _data(seed, batch=4):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, 64, (batch, 16)).astype(np.int32)
    return tokens, np.roll(tokens, -1, axis=1).astype(np.int32)


def _reference_losses(model, params, tokens, targets, lr, steps):
    """Plain single-process GD on the same init."""
    def loss_fn(p):
        logits, _ = model.run(p, jnp.asarray(tokens), training=True)
        return lm_cross_entropy(logits, jnp.asarray(targets))

    losses = []
    p = params
    for _ in range(steps):
        loss, g = jax.value_and_grad(loss_fn)(p)
        losses.append(float(loss))
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
    return losses


@pytest.mark.slow
def test_pipeline_pp2_matches_single_process():
    tokens, targets = _data(0)
    mesh = mesh_lib.create_mesh({"pp": 2})
    model = _model()
    tr = PipelineLMTrainer(model, SGD(learning_rate=0.1), mesh,
                           n_microbatches=2, seed=3).init()
    # same initialization as the trainer uses
    ref_params = model.init(jax.random.PRNGKey(3))
    want = _reference_losses(model, ref_params, tokens, targets, 0.1, 3)
    got = [float(tr.step(tokens, targets)) for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_pipeline_dp2_pp2():
    tokens, targets = _data(1, batch=4)
    mesh = mesh_lib.create_mesh({"dp": 2, "pp": 2})
    model = _model()
    tr = PipelineLMTrainer(model, SGD(learning_rate=0.1), mesh,
                           n_microbatches=2, seed=5).init()
    ref_params = model.init(jax.random.PRNGKey(5))
    want = _reference_losses(model, ref_params, tokens, targets, 0.1, 2)
    got = [float(tr.step(tokens, targets)) for _ in range(2)]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_merge_returns_model_params():
    tokens, targets = _data(2)
    mesh = mesh_lib.create_mesh({"pp": 2})
    model = _model()
    tr = PipelineLMTrainer(model, SGD(learning_rate=0.1), mesh,
                           n_microbatches=2, seed=7).init()
    tr.step(tokens, targets)
    merged = tr.merge()
    logits, _ = model.run(
        jax.tree_util.tree_map(jnp.asarray, merged), jnp.asarray(tokens),
        training=False)
    assert logits.shape == (4, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow
def test_pipeline_composes_with_sequence_parallel():
    """pp x sp: sequence dim sharded over the AUTO sp axis inside each
    pipeline stage must match the pp-only run exactly (the one
    previously untested axis pairing)."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32, dropout=0.0)
    rng = np.random.RandomState(3)
    tok = rng.randint(0, 64, (4, 32)).astype(np.int32)
    tgt = rng.randint(0, 64, (4, 32)).astype(np.int32)

    results = []
    for axes in ({"pp": 2}, {"pp": 2, "sp": 2},
                 {"dp": 2, "pp": 2, "sp": 2}):
        mesh = mesh_lib.create_mesh(axes)
        tr = PipelineLMTrainer(TransformerLM(cfg), SGD(learning_rate=0.1),
                               mesh, n_microbatches=2, seed=0,
                               loss_chunk=8)
        tr.init()
        for _ in range(3):
            loss = tr.step(jnp.asarray(tok), jnp.asarray(tgt))
        results.append((float(loss), tr.merge()))
    for loss_i, params_i in results[1:]:
        assert abs(results[0][0] - loss_i) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(results[0][1]),
                        jax.tree_util.tree_leaves(params_i)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_pipeline_composes_with_tensor_parallel():
    """dp x pp x tp: shard_map manual over pp/dp with tp as an AUTO axis
    (XLA partitions each stage's matmuls via the template pspecs) must
    match the pp-only run exactly (multi-axis composition)."""
    from bigdl_tpu.parallel.mesh import create_mesh
    from bigdl_tpu.parallel.pipeline import PipelineLMTrainer
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.models.transformer import TransformerLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32, dropout=0.0)
    rng = np.random.RandomState(2)
    tok = rng.randint(0, 64, (4, 16)).astype(np.int32)
    tgt = rng.randint(0, 64, (4, 16)).astype(np.int32)

    results = []
    for axes in ({"pp": 2}, {"dp": 2, "pp": 2, "tp": 2}):
        mesh = create_mesh(axes)
        tr = PipelineLMTrainer(TransformerLM(cfg), SGD(learning_rate=0.1),
                               mesh, n_microbatches=2, seed=0)
        tr.init()
        for _ in range(3):
            loss = tr.step(jnp.asarray(tok), jnp.asarray(tgt))
        results.append((float(loss), tr.merge()))
    assert abs(results[0][0] - results[1][0]) < 1e-5
    # EVERY param leaf — especially the tp-auto-partitioned block
    # weights, not just the replicated embedding
    for a, b in zip(jax.tree_util.tree_leaves(results[0][1]),
                    jax.tree_util.tree_leaves(results[1][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
