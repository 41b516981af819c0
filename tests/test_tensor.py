"""bigdl_tpu.tensor unit tests (≙ tensor/DenseTensorSpec.scala,
SparseTensorSpec.scala, QuantizedTensorSpec.scala): torch-style 1-based
index helpers vs torch ground truth, sparse COO ops, int8 quantization."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import tensor as bt


def test_narrow_select_index_select():
    x = jnp.asarray(np.arange(24, dtype=np.float32).reshape(4, 6))
    np.testing.assert_allclose(np.asarray(bt.narrow(x, 1, 2, 2)),
                               np.asarray(x)[1:3])
    np.testing.assert_allclose(np.asarray(bt.select(x, 2, 3)),
                               np.asarray(x)[:, 2])
    np.testing.assert_allclose(np.asarray(bt.index_select(x, 1, [3, 1])),
                               np.asarray(x)[[2, 0]])


def test_index_add_copy_fill_match_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(0)
    x = rng.randn(5, 4).astype(np.float32)
    src = rng.randn(3, 4).astype(np.float32)
    idx = np.array([1, 4, 1], np.int64)   # duplicate index accumulates

    got = np.asarray(bt.index_add(jnp.asarray(x), 1, idx + 1,
                                  jnp.asarray(src)))
    want = torch.from_numpy(x.copy()).index_add(
        0, torch.from_numpy(idx), torch.from_numpy(src)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)

    got = np.asarray(bt.index_copy(jnp.asarray(x), 1, np.array([2, 5]),
                                   jnp.asarray(src[:2])))
    want = torch.from_numpy(x.copy()).index_copy(
        0, torch.tensor([1, 4]), torch.from_numpy(src[:2])).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)

    got = np.asarray(bt.index_fill(jnp.asarray(x), 2, np.array([1, 3]), 7.0))
    want = torch.from_numpy(x.copy()).index_fill(
        1, torch.tensor([0, 2]), 7.0).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_gather_scatter_match_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(1)
    x = rng.randn(4, 5).astype(np.float32)
    index0 = rng.randint(0, 4, (3, 5))
    got = np.asarray(bt.gather(jnp.asarray(x), 1, index0 + 1))
    want = torch.gather(torch.from_numpy(x), 0,
                        torch.from_numpy(index0)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)

    index1 = rng.randint(0, 5, (4, 3))
    got = np.asarray(bt.gather(jnp.asarray(x), 2, index1 + 1))
    want = torch.gather(torch.from_numpy(x), 1,
                        torch.from_numpy(index1)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)

    src = rng.randn(4, 3).astype(np.float32)
    got = np.asarray(bt.scatter(jnp.asarray(x), 2, index1 + 1,
                                jnp.asarray(src)))
    want = torch.from_numpy(x.copy()).scatter(
        1, torch.from_numpy(index1), torch.from_numpy(src)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)

    got = np.asarray(bt.scatter_add(jnp.asarray(x), 2, index1 + 1,
                                    jnp.asarray(src)))
    want = torch.from_numpy(x.copy()).scatter_add(
        1, torch.from_numpy(index1), torch.from_numpy(src)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_masked_fill_select():
    x = jnp.asarray(np.arange(6, dtype=np.float32))
    mask = np.array([0, 1, 0, 1, 0, 0])
    np.testing.assert_allclose(
        np.asarray(bt.masked_fill(x, mask, -1.0)),
        [0, -1, 2, -1, 4, 5])
    np.testing.assert_allclose(np.asarray(bt.masked_select(x, mask)), [1, 3])


def test_sparse_roundtrip_and_matmul():
    rng = np.random.RandomState(2)
    dense = rng.randn(5, 7).astype(np.float32)
    dense[rng.rand(5, 7) < 0.6] = 0.0
    sp = bt.SparseTensor.from_dense(dense)
    np.testing.assert_allclose(np.asarray(sp.to_dense()), dense)

    w = rng.randn(7, 3).astype(np.float32)
    got = np.asarray(bt.sparse_dense_matmul(sp, jnp.asarray(w)))
    np.testing.assert_allclose(got, dense @ w, rtol=1e-5, atol=1e-6)


def test_embedding_bag_combiners():
    rng = np.random.RandomState(3)
    W = rng.randn(10, 4).astype(np.float32)
    # 2 bags: bag0 = ids [2, 5], bag1 = ids [7]
    ids = bt.SparseTensor(np.array([[0, 0, 1], [0, 1, 0]], np.int32),
                          np.array([2, 5, 7], np.float32), (2, 2))
    s = np.asarray(bt.embedding_bag(jnp.asarray(W), ids, combiner="sum"))
    np.testing.assert_allclose(s[0], W[1] + W[4], rtol=1e-6)
    np.testing.assert_allclose(s[1], W[6], rtol=1e-6)
    m = np.asarray(bt.embedding_bag(jnp.asarray(W), ids, combiner="mean"))
    np.testing.assert_allclose(m[0], (W[1] + W[4]) / 2, rtol=1e-6)
    q = np.asarray(bt.embedding_bag(jnp.asarray(W), ids, combiner="sqrtn"))
    np.testing.assert_allclose(q[0], (W[1] + W[4]) / np.sqrt(2), rtol=1e-6)


def test_embedding_bag_empty_bag_is_zero():
    # bag 1 has no ids at all: sum combines to exactly 0, mean/sqrtn
    # must not divide by zero
    W = np.random.RandomState(0).randn(5, 4).astype(np.float32)
    ids = bt.SparseTensor(np.array([[0, 0], [0, 1]], np.int32),
                          np.array([1, 3], np.float32), (3, 2))
    for combiner in ("sum", "mean", "sqrtn"):
        y = np.asarray(bt.embedding_bag(jnp.asarray(W), ids,
                                        combiner=combiner))
        assert np.isfinite(y).all()
        np.testing.assert_array_equal(y[1:], 0.0)


def test_embedding_bag_duplicate_ids_in_one_bag():
    # the same id twice in one bag counts twice (and mean divides by 2)
    W = np.random.RandomState(1).randn(5, 4).astype(np.float32)
    ids = bt.SparseTensor(np.array([[0, 0], [0, 1]], np.int32),
                          np.array([3, 3], np.float32), (1, 2))
    s = np.asarray(bt.embedding_bag(jnp.asarray(W), ids, combiner="sum"))
    np.testing.assert_allclose(s[0], 2 * W[2], rtol=1e-6)
    m = np.asarray(bt.embedding_bag(jnp.asarray(W), ids, combiner="mean"))
    np.testing.assert_allclose(m[0], W[2], rtol=1e-6)


def test_embedding_bag_out_of_range_raises():
    # hardening: ids past the table (or < 1) raise loudly for concrete
    # inputs instead of silently clipping to an existing row
    W = jnp.zeros((5, 4), jnp.float32)
    for bad in (0.0, 6.0, -1.0):
        ids = bt.SparseTensor(np.array([[0, 0], [0, 1]], np.int32),
                              np.array([1.0, bad], np.float32), (1, 2))
        with pytest.raises(IndexError, match="out of range"):
            bt.embedding_bag(W, ids)


def test_embedding_bag_out_of_range_poisons_under_trace():
    # inside jit, python raising can't fire — the offending output rows
    # become NaN so the bug surfaces instead of reading a wrong row
    W = jnp.asarray(np.random.RandomState(2).randn(5, 4), jnp.float32)

    @jax.jit
    def f(vals):
        sp = bt.SparseTensor(np.array([[0, 1], [0, 0]]), vals, (2, 2))
        return bt.embedding_bag(W, sp)

    bad = np.asarray(f(jnp.array([1.0, 9.0])))
    assert np.isnan(bad[1]).all() and np.isfinite(bad[0]).all()
    ok = np.asarray(f(jnp.array([1.0, 2.0])))
    assert np.isfinite(ok).all()


def test_embedding_bag_gradients():
    # AD gradients of the bag (valid ids) against finite differences,
    # duplicate ids included — through the LookupTableSparse module so
    # the shared gradient_checker drives it
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from gradient_checker import check_gradients
    from bigdl_tpu import nn
    ids = bt.SparseTensor(np.array([[0, 0, 0, 1], [0, 1, 2, 0]], np.int32),
                          np.array([2, 4, 2, 1], np.float32), (2, 3))
    for combiner in ("sum", "mean", "sqrtn"):
        check_gradients(nn.LookupTableSparse(5, 4, combiner=combiner), ids)


def test_sparse_concat():
    a = bt.SparseTensor.from_dense(np.array([[1., 0.], [0., 2.]]))
    b = bt.SparseTensor.from_dense(np.array([[0., 3.], [4., 0.]]))
    cat = bt.sparse_concat([a, b], dim=2)
    np.testing.assert_allclose(
        np.asarray(cat.to_dense()),
        [[1, 0, 0, 3], [0, 2, 4, 0]])


def test_quantized_tensor_pytree_and_accuracy():
    import jax
    rng = np.random.RandomState(4)
    x = rng.randn(6, 8).astype(np.float32)
    qt = bt.QuantizedTensor.quantize(jnp.asarray(x), axis=0)
    err = np.abs(np.asarray(qt.dequantize()) - x).max()
    assert err < np.abs(x).max() / 100, err
    # pytree: survives jit boundaries
    out = jax.jit(lambda t: t.dequantize())(qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(qt.dequantize()))


def test_jit_sparse_flows():
    import jax
    dense = np.diag(np.arange(1.0, 5.0)).astype(np.float32)
    sp = bt.SparseTensor.from_dense(dense)
    w = jnp.asarray(np.eye(4, dtype=np.float32))
    out = jax.jit(bt.sparse_dense_matmul)(sp, w)
    np.testing.assert_allclose(np.asarray(out), dense)


def test_sparse_tensor_surface():
    """Widened SparseTensor ops (the reference's
    implemented subset: narrow/select/concat/transpose/numNonZeroByRow/
    apply1 — tensor/SparseTensor.scala)."""
    import numpy as np
    import jax.numpy as jnp
    from bigdl_tpu.tensor import (SparseTensor, sparse_concat,
                                  sparse_dense_add)

    d = np.zeros((4, 5), np.float32)
    d[0, 1] = 1.0
    d[1, 3] = 2.0
    d[2, 0] = -3.0
    d[3, 4] = 4.0
    sp = SparseTensor.from_dense(d)

    # elementwise / scalar ops keep the pattern
    np.testing.assert_allclose(np.asarray((sp * 2).to_dense()), d * 2)
    np.testing.assert_allclose(np.asarray((-sp).to_dense()), -d)
    np.testing.assert_allclose(np.asarray(sp.abs().to_dense()), np.abs(d))
    np.testing.assert_allclose(
        np.asarray(sp.apply1(jnp.square).to_dense()), d * d)
    assert float(sp.sum()) == float(d.sum())

    # narrow/select on rows (1-based)
    np.testing.assert_allclose(np.asarray(sp.narrow(1, 2, 2).to_dense()),
                               d[1:3])
    np.testing.assert_allclose(np.asarray(sp.select(1, 3).to_dense()),
                               d[2])

    # transpose
    np.testing.assert_allclose(np.asarray(sp.t().to_dense()), d.T)

    # concat rows + cols
    cat1 = sparse_concat([sp, sp], dim=1)
    np.testing.assert_allclose(np.asarray(cat1.to_dense()),
                               np.concatenate([d, d], 0))
    cat2 = sparse_concat([sp, sp], dim=2)
    np.testing.assert_allclose(np.asarray(cat2.to_dense()),
                               np.concatenate([d, d], 1))

    # nnz by row, dense add
    np.testing.assert_array_equal(np.asarray(sp.num_nonzero_by_row()),
                                  [1, 1, 1, 1])
    base = np.ones((4, 5), np.float32)
    np.testing.assert_allclose(np.asarray(sparse_dense_add(sp, base)),
                               base + d)

    # dtype change (bf16: x64 is disabled under jit defaults)
    assert sp.astype(jnp.bfloat16).dtype == jnp.bfloat16
