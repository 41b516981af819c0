"""Quantization tests (≙ nn/quantized *Spec.scala: quantized output close
to float output; Quantizer graph rewrite)."""
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.quantized import (QuantizedLinear, QuantizedSpatialConvolution,
                                 quantize, quantize_weights_symmetric)


def test_weight_quantization_roundtrip():
    rs = np.random.RandomState(0)
    w = rs.randn(8, 16).astype(np.float32)
    q, scale = quantize_weights_symmetric(w, axis=0)
    assert q.dtype == np.int8 and np.abs(q).max() <= 127
    err = np.abs(q.astype(np.float32) * scale - w).max()
    assert err <= np.abs(w).max() / 127.0 + 1e-6  # within one step


def test_quantized_linear_close_to_float():
    rs = np.random.RandomState(0)
    lin = nn.Linear(32, 16)
    lin.reset(0)
    x = rs.randn(8, 32).astype(np.float32)
    want = np.asarray(lin.forward(x))
    qlin = QuantizedLinear.from_float(lin)
    got = np.asarray(qlin.forward(x))
    # int8 symmetric: ~1% relative error on random gaussians
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05, rel


def test_quantized_conv_close_to_float():
    rs = np.random.RandomState(0)
    conv = nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1)
    conv.reset(0)
    x = rs.randn(2, 3, 12, 12).astype(np.float32)
    want = np.asarray(conv.forward(x))
    qconv = QuantizedSpatialConvolution.from_float(conv)
    got = np.asarray(qconv.forward(x))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05, rel


def test_quantize_model_rewrite_and_predict():
    rs = np.random.RandomState(0)
    model = nn.Sequential(
        nn.SpatialConvolution(1, 4, 3, 3, 1, 1, 1, 1), nn.ReLU(),
        nn.Reshape((4 * 8 * 8,)), nn.Linear(256, 10), nn.LogSoftMax())
    model.reset(0)
    x = rs.randn(4, 1, 8, 8).astype(np.float32)
    want = np.asarray(model.forward(x))
    qmodel = quantize(model)
    kinds = [type(c).__name__ for c in qmodel.children()]
    assert kinds[0] == "QuantizedSpatialConvolution"
    assert kinds[3] == "QuantizedLinear"
    got = np.asarray(qmodel.forward(x))
    # logits land on the same ordering for most rows
    agree = (got.argmax(1) == want.argmax(1)).mean()
    assert agree >= 0.75
    # original model untouched
    assert type(model.children()[0]).__name__ == "SpatialConvolution"


def test_quantized_backward_refuses():
    lin = nn.Linear(4, 2)
    lin.reset(0)
    q = QuantizedLinear.from_float(lin)
    x = np.ones((1, 4), np.float32)
    q.forward(x)
    with pytest.raises(RuntimeError):
        q.backward(x, np.ones((1, 2), np.float32))


def test_quantize_preserves_trained_bn_and_state():
    """Regression: quantize() must carry trained params/state of
    NON-quantized children through (BN gamma/beta + running stats were
    silently re-initialized before)."""
    rs = np.random.RandomState(1)
    model = nn.Sequential(
        nn.SpatialConvolution(1, 4, 3, 3, 1, 1, 1, 1),
        nn.SpatialBatchNormalization(4), nn.ReLU(),
        nn.Reshape((4 * 8 * 8,)), nn.Linear(256, 10))
    model.reset(0)
    bn = model.children()[1]
    # fake a "trained" BN: non-default affine params and running stats
    params = dict(model.ensure_initialized())
    params[bn.name] = {
        "weight": rs.rand(4).astype(np.float32) + 0.5,
        "bias": rs.randn(4).astype(np.float32)}
    state = dict(model._state)
    state[bn.name] = {
        "running_mean": rs.randn(4).astype(np.float32),
        "running_var": rs.rand(4).astype(np.float32) + 0.5}
    model.set_params(params, state)
    model.evaluate()
    x = rs.randn(4, 1, 8, 8).astype(np.float32)
    want = np.asarray(model.forward(x))
    qmodel = quantize(model).evaluate()
    # BN entries survived into the quantized model's carried tree
    np.testing.assert_array_equal(
        np.asarray(qmodel._params[bn.name]["weight"]),
        np.asarray(params[bn.name]["weight"]))
    np.testing.assert_array_equal(
        np.asarray(qmodel._state[bn.name]["running_mean"]),
        np.asarray(state[bn.name]["running_mean"]))
    got = np.asarray(qmodel.forward(x))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.1, rel


def test_quantized_conv_nhwc_matches_float():
    """Regression: NHWC float convs must quantize with NHWC dimension
    numbers (was hardwired NCHW)."""
    rs = np.random.RandomState(0)
    conv = nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1, format="NHWC")
    conv.reset(0)
    x = rs.randn(2, 12, 12, 3).astype(np.float32)
    want = np.asarray(conv.forward(x))
    qconv = QuantizedSpatialConvolution.from_float(conv)
    got = np.asarray(qconv.forward(x))
    assert got.shape == want.shape
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05, rel


def test_quantized_conv_mixed_same_explicit_padding():
    """Regression: pad_h=-1 (SAME) combined with explicit pad_w must pad
    per-axis like the float layer, not force SAME on both axes."""
    rs = np.random.RandomState(1)
    conv = nn.SpatialConvolution(3, 8, 3, 3, 2, 2, -1, 0)
    conv.reset(0)
    x = rs.randn(2, 3, 11, 11).astype(np.float32)
    want = np.asarray(conv.forward(x))
    qconv = QuantizedSpatialConvolution.from_float(conv)
    got = np.asarray(qconv.forward(x))
    assert got.shape == want.shape
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05, rel


def test_quantize_graph_dag_model():
    """Graph models (e.g. Caffe-loaded DAG nets) must quantize too, not
    silently pass through unchanged."""
    from bigdl_tpu.nn.graph import Graph, Input
    from bigdl_tpu.quantized import quantize

    rs = np.random.RandomState(0)
    inp = Input()
    c1 = nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1).inputs(inp)
    r1 = nn.ReLU().inputs(c1)
    br_a = nn.SpatialConvolution(8, 4, 1, 1).inputs(r1)
    br_b = nn.SpatialConvolution(8, 4, 1, 1).inputs(r1)
    cat = nn.JoinTable(2).inputs([br_a, br_b])
    g = Graph(inp, cat)
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    want = np.asarray(g.forward(x))

    q = quantize(g)
    q_types = [type(m).__name__ for m in q.modules()]
    assert "QuantizedSpatialConvolution" in q_types, q_types
    assert not any(isinstance(m, nn.SpatialConvolution)
                   and type(m) is nn.SpatialConvolution
                   for m in q.modules() if m is not q)
    got = np.asarray(q.forward(x))
    assert got.shape == want.shape
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.1, rel


def test_quantized_dilated_conv_close_to_float_and_serde():
    """QuantizedSpatialDilatedConvolution
    (≙ nn/quantized/SpatialDilatedConvolution.scala:30) + v2-serde
    round-trip for quantized models (≙ QuantSerializer.scala)."""
    import os
    import tempfile
    from bigdl_tpu.quantized import QuantizedSpatialDilatedConvolution
    from bigdl_tpu.utils.serializer import save_module, load_module

    m = nn.Sequential(
        nn.SpatialDilatedConvolution(3, 8, 3, 3, 1, 1, 2, 2, 2, 2),
        nn.ReLU(),
        nn.SpatialConvolution(8, 4, 1, 1),
        nn.Reshape((4 * 8 * 8,)),
        nn.Linear(4 * 8 * 8, 10))
    m.reset(0)
    x = np.random.RandomState(1).rand(2, 3, 8, 8).astype(np.float32)
    y_float = np.asarray(m.forward(x))

    q = quantize(m)
    kinds = [type(c).__name__ for c in q.modules()]
    assert "QuantizedSpatialDilatedConvolution" in kinds
    assert "QuantizedLinear" in kinds
    y_q = np.asarray(q.forward(x))
    assert y_q.shape == y_float.shape
    # int8 output stays close to float (per-channel symmetric weights)
    rel = np.abs(y_q - y_float).max() / max(np.abs(y_float).max(), 1e-6)
    assert rel < 0.08, rel

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "q.bigdl_tpu")
        save_module(q, p)
        q2 = load_module(p)
    y_q2 = np.asarray(q2.forward(x))
    np.testing.assert_allclose(y_q2, y_q, rtol=1e-6, atol=1e-6)


def test_quantized_dilated_backward_refuses():
    from bigdl_tpu.quantized import QuantizedSpatialDilatedConvolution
    lay = nn.SpatialDilatedConvolution(2, 2, 3, 3, 1, 1, 1, 1, 2, 2)
    lay.reset(0)
    qc = QuantizedSpatialDilatedConvolution.from_float(lay)
    x = np.zeros((1, 2, 6, 6), np.float32)
    qc.forward(x)
    with pytest.raises(RuntimeError, match="inference-only"):
        qc.backward(x, np.zeros_like(np.asarray(qc.output)))


def test_quantize_resnet_nhwc_close_to_float():
    """bench.py's int8 config path: NHWC ResNet quantizes whole and stays
    close to the float net."""
    from bigdl_tpu.models import resnet
    from bigdl_tpu.quantized import QuantizedSpatialConvolution

    m = resnet.build(class_num=10, depth=20, dataset="cifar10",
                     format="NHWC")
    m.reset(0)
    x = np.random.RandomState(0).rand(2, 32, 32, 3).astype(np.float32)
    y0 = np.asarray(m.forward(x))
    q = quantize(m)
    assert any(isinstance(c, QuantizedSpatialConvolution)
               for c in q.modules())
    y1 = np.asarray(q.forward(x))
    rel = np.abs(y1 - y0).max() / max(np.abs(y0).max(), 1e-6)
    assert rel < 0.05, rel


def test_calibrated_activation_scales():
    """quantize(model, calibration_data=...) bakes static activation
    scales (the TPU-side lever that removes the per-batch |x| reduction
    before every int8 GEMM; see quantized/__init__.py docstrings)."""
    from bigdl_tpu.quantized import (quantize, calibrate_activation_absmax,
                                     QuantizedSpatialConvolution,
                                     QuantizedLinear)

    m = nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1),
        nn.ReLU(),
        nn.SpatialConvolution(8, 4, 1, 1),
        nn.Reshape((4 * 8 * 8,)),
        nn.Linear(4 * 8 * 8, 10))
    m.reset(0)
    rng = np.random.RandomState(3)
    calib = [rng.rand(2, 3, 8, 8).astype(np.float32) for _ in range(3)]
    x = rng.rand(2, 3, 8, 8).astype(np.float32)
    y_float = np.asarray(m.forward(x))

    absmax = calibrate_activation_absmax(m, calib)
    assert len(absmax) == 3 and all(v > 0 for v in absmax.values())
    # the float model is restored (no recorder shadows left behind)
    assert all("apply" not in mod.__dict__ for mod in m.modules())

    q = quantize(m, calibration_data=calib)
    qlayers = [c for c in q.modules()
               if isinstance(c, (QuantizedSpatialConvolution,
                                 QuantizedLinear))]
    assert qlayers and all(l.act_absmax is not None for l in qlayers)

    y_q = np.asarray(q.forward(x))
    rel = np.abs(y_q - y_float).max() / max(np.abs(y_float).max(), 1e-6)
    assert rel < 0.08, rel

    # static scales: doubling the input magnitude must NOT double the
    # quantization range (runtime quantization would adapt; calibrated
    # scales clip instead)
    q_rt = quantize(m)
    big = (4.0 * x).astype(np.float32)
    y_static = np.asarray(q.forward(big))
    y_runtime = np.asarray(q_rt.forward(big))
    assert np.abs(y_static - y_runtime).max() > 1e-3


def test_calibrated_quantized_serde_roundtrip():
    import os
    import tempfile
    from bigdl_tpu.quantized import quantize
    from bigdl_tpu.utils.serializer import save_module, load_module

    m = nn.Sequential(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1),
                      nn.ReLU(),
                      nn.Reshape((4 * 8 * 8,)),
                      nn.Linear(4 * 8 * 8, 5))
    m.reset(0)
    rng = np.random.RandomState(4)
    calib = [rng.rand(2, 3, 8, 8).astype(np.float32)]
    q = quantize(m, calibration_data=calib)
    x = rng.rand(2, 3, 8, 8).astype(np.float32)
    y_q = np.asarray(q.forward(x))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "qc.bigdl_tpu")
        save_module(q, p)
        q2 = load_module(p)
    y_q2 = np.asarray(q2.forward(x))
    np.testing.assert_allclose(y_q2, y_q, rtol=1e-6, atol=1e-6)
    from bigdl_tpu.quantized import QuantizedLinear
    l2 = [c for c in q2.modules() if isinstance(c, QuantizedLinear)]
    assert l2 and l2[0].act_absmax is not None


def test_weight_only_int8_transformer_serving():
    """quantize_weights_only on the TransformerLM flagship: ~2x smaller
    weights, loss within tolerance, and greedy generation matches the
    fp model token-for-token on a short prompt."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import (TransformerLM,
                                              TransformerConfig)
    from bigdl_tpu.quantized import (dequantize_weights,
                                     quantize_weights_only,
                                     quantized_bytes)

    cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_len=64, dropout=0.0)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, (2, 16)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, 128, (2, 16)), jnp.int32)

    qparams = quantize_weights_only(params, min_size=1024)
    assert quantized_bytes(qparams) < 0.5 * quantized_bytes(params)

    loss_fp = float(model.loss(params, tokens, targets))
    deq = dequantize_weights(qparams, dtype=jnp.float32)
    loss_q = float(model.loss(deq, tokens, targets))
    assert abs(loss_fp - loss_q) / loss_fp < 0.05, (loss_fp, loss_q)

    prompt = tokens[:, :8]
    out_fp = np.asarray(model.generate(params, prompt, max_new_tokens=8,
                                       temperature=0.0))
    out_q = np.asarray(model.generate(deq, prompt, max_new_tokens=8,
                                      temperature=0.0))
    agree = (out_fp == out_q).mean()
    assert agree >= 0.8, agree


def test_weight_only_int8_roundtrip_identity_for_small_leaves():
    from bigdl_tpu.quantized import (dequantize_weights,
                                     quantize_weights_only)
    import jax.numpy as jnp

    params = {"m": {"w": np.random.RandomState(0)
                    .randn(64, 64).astype(np.float32),
                    "b": np.arange(4, dtype=np.float32)}}
    q = quantize_weights_only(params, min_size=1024)
    assert isinstance(q["m"]["w"], dict) and "q8" in q["m"]["w"]
    np.testing.assert_array_equal(np.asarray(q["m"]["b"]), params["m"]["b"])
    d = dequantize_weights(q, dtype=jnp.float32)
    err = np.abs(np.asarray(d["m"]["w"]) - params["m"]["w"]).max()
    scale = np.abs(params["m"]["w"]).max(0) / 127.0
    assert err <= scale.max() * 0.51 + 1e-6


def test_calibrated_int8_has_no_runtime_activation_scaling():
    """The r3 on-device finding was int8 inference SLOWER than bf16
    forward; diagnosis: per-batch activation |x|-max reductions before
    every int8 op.  Calibration bakes static scales — the compiled
    program must contain NO abs ops at all, while the dynamic-scale
    path keeps them (structural guard for the fix, checkable on CPU)."""
    import jax
    rng = np.random.RandomState(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m.ensure_initialized()
    calib = [rng.rand(4, 8).astype(np.float32) for _ in range(3)]

    def compiled_abs_count(model):
        x = np.zeros((4, 8), np.float32)
        p, s = model._params, model._state
        f = jax.jit(lambda pp, xx: model.run(pp, xx, state=s,
                                             training=False)[0])
        return f.lower(p, x).compile().as_text().count("abs(")

    assert compiled_abs_count(quantize(m, calibration_data=calib)) == 0
    assert compiled_abs_count(quantize(m)) > 0
