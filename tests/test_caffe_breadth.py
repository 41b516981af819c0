"""Caffe converter breadth: Deconvolution, dilation,
ELU, PReLU, Power, Exp, Log, AbsVal, Reshape, Slice, Threshold, Tile,
RNN, Eltwise coefficients — mirroring utils/caffe/Converter.scala:632 and
LayerConverter.scala:39 layer coverage."""
import os
import tempfile

import numpy as np
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.utils import proto
from bigdl_tpu.utils.caffe import (CaffeLoader, load_caffe, parse_prototxt,
                                   _blob_bytes)


def _load(prototxt, caffemodel_bytes=None):
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "net.prototxt")
        with open(p, "w") as f:
            f.write(prototxt)
        mp = None
        if caffemodel_bytes is not None:
            mp = os.path.join(d, "net.caffemodel")
            with open(mp, "wb") as f:
                f.write(caffemodel_bytes)
        return load_caffe(p, mp)


def _layer_bytes(name, ltype, blobs=()):
    lp = proto.enc_string(1, name) + proto.enc_string(2, ltype)
    for b in blobs:
        lp += proto.enc_bytes(7, _blob_bytes(np.asarray(b, np.float32)))
    return proto.enc_bytes(100, lp)


HEAD = 'name: "t"\ninput: "data"\ninput_shape { dim: 2 dim: 3 dim: 8 dim: 8 }\n'


def test_unary_activation_chain():
    net = HEAD + """
layer { name: "c1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1 } }
layer { name: "e" type: "ELU" bottom: "c1" top: "e"
  elu_param { alpha: 0.5 } }
layer { name: "p" type: "Power" bottom: "e" top: "p"
  power_param { power: 2.0 scale: 0.5 shift: 1.0 } }
layer { name: "x" type: "Exp" bottom: "p" top: "x" }
layer { name: "l" type: "Log" bottom: "x" top: "l" }
layer { name: "a" type: "AbsVal" bottom: "l" top: "a" }
layer { name: "t" type: "Threshold" bottom: "a" top: "t"
  threshold_param { threshold: 0.25 } }
"""
    m = _load(net)
    kinds = [type(c).__name__ for c in m.modules() if not c.children()]
    for want in ("ELU", "Power", "Exp", "Log", "Abs", "BinaryThreshold"):
        assert want in kinds, kinds
    out = m.forward(np.random.RandomState(0).rand(2, 3, 8, 8)
                    .astype(np.float32))
    assert out.shape == (2, 4, 8, 8)
    assert set(np.unique(np.asarray(out))) <= {0.0, 1.0}   # threshold output


def test_deconvolution_with_weights():
    net = HEAD + """
layer { name: "d" type: "Deconvolution" bottom: "data" top: "d"
  convolution_param { num_output: 5 kernel_size: 2 stride: 2 } }
"""
    rng = np.random.RandomState(1)
    w = rng.randn(3, 5, 2, 2).astype(np.float32)   # (in, out, kh, kw)
    b = rng.randn(5).astype(np.float32)
    body = proto.enc_string(1, "t") + _layer_bytes("d", "Deconvolution",
                                                   [w, b])
    m = _load(net, body)
    deconv = [c for c in m.modules()
              if isinstance(c, nn.SpatialFullConvolution)]
    assert len(deconv) == 1
    out = m.forward(rng.rand(2, 3, 8, 8).astype(np.float32))
    assert out.shape == (2, 5, 16, 16)   # stride-2 upsample
    got_w = np.asarray(m.ensure_initialized()[deconv[0].name]["weight"])
    np.testing.assert_allclose(got_w.reshape(w.shape), w)


def test_dilated_convolution():
    net = HEAD + """
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
  convolution_param { num_output: 4 kernel_size: 3 pad: 2 dilation: 2 } }
"""
    m = _load(net)
    mods = [c for c in m.modules()
            if isinstance(c, nn.SpatialDilatedConvolution)]
    assert len(mods) == 1 and mods[0].dilation == (2, 2)
    out = m.forward(np.zeros((2, 3, 8, 8), np.float32))
    assert out.shape == (2, 4, 8, 8)


def test_prelu_weights_from_blob():
    net = HEAD + """
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
  convolution_param { num_output: 4 kernel_size: 1 } }
layer { name: "pr" type: "PReLU" bottom: "c" top: "pr" }
"""
    slopes = np.asarray([0.1, 0.2, 0.3, 0.4], np.float32)
    body = proto.enc_string(1, "t") + _layer_bytes("pr", "PReLU", [slopes])
    m = _load(net, body)
    pr = [c for c in m.modules() if isinstance(c, nn.PReLU)][0]
    assert pr.n_output_plane == 4
    np.testing.assert_allclose(
        np.asarray(m.ensure_initialized()[pr.name]["weight"]), slopes)
    assert m.forward(np.zeros((1, 3, 8, 8), np.float32)).shape \
        == (1, 4, 8, 8)
    # slope semantics: negative inputs scale per-channel
    x = -np.ones((1, 4, 2, 2), np.float32)
    pm = nn.PReLU(4)
    pm.ensure_initialized()
    pm.set_params({pm.name: {"weight": jnp.asarray(slopes)}})
    got = np.asarray(pm.forward(x))
    np.testing.assert_allclose(got[0, :, 0, 0], -slopes)


def test_reshape_and_tile():
    net = HEAD + """
layer { name: "r" type: "Reshape" bottom: "data" top: "r"
  reshape_param { shape { dim: 0 dim: -1 } } }
layer { name: "ti" type: "Tile" bottom: "r" top: "ti"
  tile_param { axis: 1 tiles: 3 } }
"""
    m = _load(net)
    out = m.forward(np.zeros((2, 3, 8, 8), np.float32))
    assert out.shape == (2, 3 * 8 * 8 * 3)


def test_slice_narrow_semantics():
    net = HEAD + """
layer { name: "s" type: "Slice" bottom: "data" top: "s1" top: "s2"
  slice_param { axis: 1 slice_point: 1 } }
layer { name: "m1" type: "Pooling" bottom: "s1" top: "m1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "m2" type: "Pooling" bottom: "s2" top: "m2"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "cat" type: "Concat" bottom: "m1" bottom: "m2" top: "cat" }
"""
    m = _load(net)
    x = np.random.RandomState(0).rand(2, 3, 8, 8).astype(np.float32)
    out = np.asarray(m.forward(x))
    assert out.shape == (2, 3, 4, 4)
    # slice_point 1 on axis 1: s1 = x[:, :1], s2 = x[:, 1:]
    want = np.concatenate([
        x[:, :1].reshape(2, 1, 4, 2, 4, 2).max((3, 5)),
        x[:, 1:].reshape(2, 2, 4, 2, 4, 2).max((3, 5))], axis=1)
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_slice_equal_split_no_points():
    net = 'name: "t"\ninput: "data"\n' \
          'input_shape { dim: 2 dim: 4 dim: 4 dim: 4 }\n' + """
layer { name: "s" type: "Slice" bottom: "data" top: "a" top: "b" }
layer { name: "add" type: "Eltwise" bottom: "a" bottom: "b" top: "add" }
"""
    m = _load(net)
    x = np.random.RandomState(0).rand(2, 4, 4, 4).astype(np.float32)
    out = np.asarray(m.forward(x))
    np.testing.assert_allclose(out, x[:, :2] + x[:, 2:], rtol=1e-6)


def test_eltwise_coefficients():
    head = 'name: "t"\ninput: "data"\n' \
           'input_shape { dim: 2 dim: 4 dim: 4 dim: 4 }\n'
    sub = head + """
layer { name: "s" type: "Slice" bottom: "data" top: "a" top: "b" }
layer { name: "e" type: "Eltwise" bottom: "a" bottom: "b" top: "e"
  eltwise_param { operation: SUM coeff: 1 coeff: -1 } }
"""
    m = _load(sub)
    x = np.random.RandomState(1).rand(2, 4, 4, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(m.forward(x)),
                               x[:, :2] - x[:, 2:], rtol=1e-6)

    weighted = head + """
layer { name: "s" type: "Slice" bottom: "data" top: "a" top: "b" }
layer { name: "e" type: "Eltwise" bottom: "a" bottom: "b" top: "e"
  eltwise_param { operation: SUM coeff: 2 coeff: 3 } }
"""
    m2 = _load(weighted)
    np.testing.assert_allclose(np.asarray(m2.forward(x)),
                               2 * x[:, :2] + 3 * x[:, 2:], rtol=1e-6)


def test_rnn_layer_imports_as_recurrent():
    net = 'name: "t"\ninput: "data"\n' \
          'input_shape { dim: 2 dim: 5 dim: 6 }\n' + """
layer { name: "r" type: "RNN" bottom: "data" top: "r"
  recurrent_param { num_output: 7 } }
"""
    m = _load(net)
    rec = [c for c in m.modules() if isinstance(c, nn.Recurrent)]
    assert len(rec) == 1
    out = m.forward(np.zeros((2, 5, 6), np.float32))
    assert out.shape == (2, 5, 7)


def test_deconv_segmentation_net_end_to_end():
    """Multi-type FCN-style net: conv/pool downsample, 1x1 score, deconv
    upsample, PReLU, eltwise skip fusion — loads and runs."""
    net = HEAD + """
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1 } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1 } }
layer { name: "pr2" type: "PReLU" bottom: "conv2" top: "conv2" }
layer { name: "score" type: "Convolution" bottom: "conv2" top: "score"
  convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "up" type: "Deconvolution" bottom: "score" top: "up"
  convolution_param { num_output: 2 kernel_size: 2 stride: 2 } }
layer { name: "skip" type: "Convolution" bottom: "data" top: "skip"
  convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "fuse" type: "Eltwise" bottom: "up" bottom: "skip" top: "fuse"
  eltwise_param { operation: SUM } }
layer { name: "prob" type: "Softmax" bottom: "fuse" top: "prob" }
"""
    m = _load(net)
    x = np.random.RandomState(0).rand(2, 3, 8, 8).astype(np.float32)
    out = np.asarray(m.forward(x))
    assert out.shape == (2, 2, 8, 8)
    np.testing.assert_allclose(out.sum(1), np.ones((2, 8, 8)), rtol=1e-5)


def test_slice_point_feeds_convolution():
    """Open-ended last Slice chunk must report in_ch - slice_point so a
    downstream Convolution is built with the right input planes."""
    net = 'name: "t"\ninput: "data"\n' \
          'input_shape { dim: 2 dim: 6 dim: 8 dim: 8 }\n' + """
layer { name: "s" type: "Slice" bottom: "data" top: "a" top: "b"
  slice_param { axis: 1 slice_point: 2 } }
layer { name: "ca" type: "Convolution" bottom: "a" top: "ca"
  convolution_param { num_output: 3 kernel_size: 1 } }
layer { name: "cb" type: "Convolution" bottom: "b" top: "cb"
  convolution_param { num_output: 3 kernel_size: 1 } }
layer { name: "cat" type: "Concat" bottom: "ca" bottom: "cb" top: "cat" }
"""
    m = _load(net)
    convs = [c for c in m.modules() if isinstance(c, nn.SpatialConvolution)]
    assert sorted(c.n_input_plane for c in convs) == [2, 4]
    out = m.forward(np.zeros((2, 6, 8, 8), np.float32))
    assert out.shape == (2, 6, 8, 8)


def test_grouped_dilated_conv_rejected():
    net = HEAD + """
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
  convolution_param { num_output: 6 kernel_size: 3 dilation: 2 group: 3 } }
"""
    with pytest.raises(ValueError, match="grouped dilated"):
        _load(net)


def test_rnn_weights_load_from_caffemodel():
    """Caffe RNNLayer blobs (W_xh, B_h, W_hh) must land in the RnnCell
    params (transposed to our x @ W convention), not be silently
    dropped."""
    net = 'name: "t"\ninput: "data"\n' \
          'input_shape { dim: 2 dim: 5 dim: 3 }\n' + """
layer { name: "r" type: "RNN" bottom: "data" top: "r"
  recurrent_param { num_output: 4 } }
"""
    rng = np.random.RandomState(0)
    w_xh = rng.randn(4, 3).astype(np.float32)
    b_h = rng.randn(4).astype(np.float32)
    w_hh = rng.randn(4, 4).astype(np.float32)
    body = proto.enc_string(1, "t") + _layer_bytes("r", "RNN",
                                                   [w_xh, b_h, w_hh])
    m = _load(net, body)
    rec = [c for c in m.modules() if isinstance(c, nn.Recurrent)][0]
    params = m.ensure_initialized()
    p = params[rec.cell.name]
    np.testing.assert_allclose(np.asarray(p["weight_i"]), w_xh.T)
    np.testing.assert_allclose(np.asarray(p["weight_h"]), w_hh.T)
    np.testing.assert_allclose(np.asarray(p["bias"]), b_h)
    # forward equals a hand-rolled tanh RNN
    x = rng.randn(2, 5, 3).astype(np.float32)
    h = np.zeros((2, 4), np.float32)
    outs = []
    for t in range(5):
        h = np.tanh(x[:, t] @ w_xh.T + h @ w_hh.T + b_h)
        outs.append(h)
    want = np.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(m.forward(x)), want, rtol=1e-5)


def test_slice_spatial_axis_tracks_shape_into_inner_product():
    """Slice on the height axis must shrink the tracked spatial shape so
    the implicit flatten before InnerProduct sizes the Linear right."""
    net = HEAD + """
layer { name: "s" type: "Slice" bottom: "data" top: "a" top: "b"
  slice_param { axis: 2 slice_point: 2 } }
layer { name: "fc" type: "InnerProduct" bottom: "b" top: "fc"
  inner_product_param { num_output: 7 } }
"""
    m = _load(net)
    lin = [c for c in m.modules() if isinstance(c, nn.Linear)][0]
    assert lin.input_size == 3 * 6 * 8          # sliced height = 8 - 2
    x = np.random.RandomState(0).rand(2, 3, 8, 8).astype(np.float32)
    from bigdl_tpu.utils.table import as_list
    outs = as_list(m.forward(x))                # [unconsumed 'a', 'fc']
    assert outs[-1].shape == (2, 7)


def test_per_axis_dilation():
    net = HEAD + """
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
  convolution_param { num_output: 4 kernel_size: 3 pad_h: 2 pad_w: 3
                      dilation: 2 dilation: 3 } }
"""
    m = _load(net)
    mod = [c for c in m.modules()
           if isinstance(c, nn.SpatialDilatedConvolution)][0]
    assert mod.dilation == (2, 3)               # (dh, dw)
    out = m.forward(np.zeros((2, 3, 8, 8), np.float32))
    assert out.shape == (2, 4, 8, 8)
