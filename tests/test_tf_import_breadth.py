"""TF GraphDef importer breadth: a generated
slim-style graph with 50+ nodes exercising Split/Pack/Unpack/Fill/
Conv2DBackpropInput/StridedSlice/Cast/Shape/GatherV2/Select and
constant-folded Switch/Merge control flow whose untaken branch contains
an unsupported op (≙ utils/tf/loaders/ coverage + TensorflowLoader's
control-flow pruning)."""
import numpy as np
import pytest

from bigdl_tpu.utils import proto
from bigdl_tpu.utils.tf_import import (load_tf_graph, _node, _enc_tensor,
                                       _enc_shape)
from bigdl_tpu.utils.proto import enc_bytes, enc_string


def _tensor_attr(arr):
    return {"dtype": proto.enc_int64(6, 1 if arr.dtype == np.float32 else 3),
            "value": enc_bytes(8, _enc_tensor(arr))}


def _const(name, arr):
    arr = np.asarray(arr)
    if arr.dtype in (np.int64, int):
        arr = arr.astype(np.int32)
    return _node(name, "Const", (), _tensor_attr(arr))


def _ints_attr(vals):
    # AttrValue.ListValue.i = field 3, packed (attr_value.proto)
    payload = b"".join(proto._varint(v) for v in vals)
    return enc_bytes(1, enc_bytes(3, payload))


def _build_graph():
    """Returns (graphdef_bytes, expected_fn) with expected_fn mirroring the
    graph in NumPy."""
    rng = np.random.RandomState(0)
    w1 = rng.randn(3, 3, 3, 8).astype(np.float32) * 0.3
    scale = rng.rand(8).astype(np.float32) + 0.5
    offset = rng.randn(8).astype(np.float32) * 0.1
    mean = rng.randn(8).astype(np.float32) * 0.1
    var = rng.rand(8).astype(np.float32) + 0.5
    upw = rng.randn(2, 2, 8, 8).astype(np.float32) * 0.2
    wfc = rng.randn(8, 5).astype(np.float32)
    bias = rng.randn(5).astype(np.float32)

    g = b""
    g += _node("input", "Placeholder",
               attrs={"dtype": proto.enc_int64(6, 1),
                      "shape": enc_bytes(7, _enc_shape((2, 6, 6, 3)))})
    g += _const("padv", np.asarray([[0, 0], [1, 1], [1, 1], [0, 0]]))
    g += _node("pad", "Pad", ["input", "padv"])
    g += _const("w1", w1)
    g += _node("conv1", "Conv2D", ["pad", "w1"],
               {"strides": _ints_attr([1, 1, 1, 1]),
                "padding": enc_string(2, "VALID")})
    for nm, arr in (("scale", scale), ("offset", offset),
                    ("mean", mean), ("var", var)):
        g += _const(nm, arr)
    g += _node("bn", "FusedBatchNormV3",
               ["conv1", "scale", "offset", "mean", "var"],
               {"epsilon": proto.enc_float(4, 1e-3)})
    g += _node("relu", "Relu", ["bn"])
    # constant-folded cond: untaken branch holds an unsupported op
    g += _const("is_training", np.asarray(False, np.bool_))
    g += _node("sw", "Switch", ["relu", "is_training"])
    g += _node("train_op", "ApplyGradientDescent", ["sw:1"])
    g += _node("merged", "Merge", ["train_op", "sw"])
    # channel split -> per-branch math -> concat
    g += _const("split_axis", np.asarray(3))
    g += _node("spl", "Split", ["split_axis", "merged"],
               {"num_split": proto.enc_int64(3, 2)})
    g += _node("b0", "Neg", ["spl"])
    g += _const("two", np.asarray(2.0, np.float32))
    g += _node("b1a", "AddV2", ["spl:1", "two"])
    g += _node("b1", "Rsqrt", ["b1a"])
    g += _const("cat_axis", np.asarray(3))
    g += _node("cat", "ConcatV2", ["b0", "b1", "cat_axis"])
    # deconv upsample 6->12
    g += _const("up_sizes", np.asarray([2, 12, 12, 8]))
    g += _const("upw", upw)
    g += _node("up", "Conv2DBackpropInput", ["up_sizes", "upw", "cat"],
               {"strides": _ints_attr([1, 2, 2, 1]),
                "padding": enc_string(2, "SAME")})
    g += _const("gap_axes", np.asarray([1, 2]))
    g += _node("gap", "Mean", ["up", "gap_axes"])            # (2, 8)
    # pack/unpack/strided-slice shuffle (identity overall)
    g += _const("exp_axis", np.asarray(1))
    g += _node("exp", "ExpandDims", ["gap", "exp_axis"])     # (2, 1, 8)
    g += _const("tilev", np.asarray([1, 2, 1]))
    g += _node("til", "Tile", ["exp", "tilev"])              # (2, 2, 8)
    g += _node("unp", "Unpack", ["til"],
               {"axis": proto.enc_int64(3, 1),
                "num": proto.enc_int64(3, 2)})
    g += _node("pk", "Pack", ["unp", "gap"],
               {"axis": proto.enc_int64(3, 0)})              # (2, 2, 8)
    g += _const("ss_b", np.asarray([0]))
    g += _const("ss_e", np.asarray([1]))
    g += _const("ss_s", np.asarray([1]))
    g += _node("ss", "StridedSlice", ["pk", "ss_b", "ss_e", "ss_s"],
               {"shrink_axis_mask": proto.enc_int64(3, 1)})  # (2, 8)
    g += _const("half", np.asarray(0.5, np.float32))
    g += _node("sqd", "SquaredDifference", ["ss", "half"])
    g += _const("p15", np.asarray(1.5, np.float32))
    g += _node("pw", "Pow", ["sqd", "p15"])
    g += _const("fill_dims", np.asarray([2, 8]))
    g += _const("fill_val", np.asarray(0.1, np.float32))
    g += _node("fil", "Fill", ["fill_dims", "fill_val"])
    g += _node("plus", "AddV2", ["pw", "fil"])
    g += _const("thr", np.asarray(0.15, np.float32))
    g += _node("gt", "Greater", ["plus", "thr"])
    g += _node("zeros", "ZerosLike", ["plus"])
    g += _node("sel", "Select", ["gt", "plus", "zeros"])
    g += _const("wfc", wfc)
    g += _node("mm", "MatMul", ["sel", "wfc"])
    g += _const("bias", bias)
    g += _node("ba", "BiasAdd", ["mm", "bias"])
    g += _node("prob", "Softmax", ["ba"])
    # aux head: Shape/Gather/Cast
    g += _node("shape", "Shape", ["ba"])
    g += _const("one", np.asarray(1))
    g += _const("gax", np.asarray(0))
    g += _node("gath", "GatherV2", ["shape", "one", "gax"])
    g += _node("aux", "Cast", ["gath"],
               {"DstT": proto.enc_int64(6, 1)})

    def expected(x):
        pad = np.pad(x, [(0, 0), (1, 1), (1, 1), (0, 0)])
        # conv VALID stride 1 (NHWC x HWIO)
        N, H, W, _ = pad.shape
        kh, kw, ci, co = w1.shape
        oh, ow = H - kh + 1, W - kw + 1
        conv = np.zeros((N, oh, ow, co), np.float32)
        for i in range(oh):
            for j in range(ow):
                patch = pad[:, i:i + kh, j:j + kw, :]
                conv[:, i, j, :] = np.tensordot(patch, w1, 3)
        bn = (conv - mean) / np.sqrt(var + 1e-3) * scale + offset
        relu = np.maximum(bn, 0)
        merged = relu                      # is_training=False branch
        b0 = -merged[..., :4]
        b1 = 1.0 / np.sqrt(merged[..., 4:] + 2.0)
        cat = np.concatenate([b0, b1], -1)
        # Conv2DBackpropInput = grad of stride-2 k2 conv w.r.t. its input:
        # each grad pixel scatters f[h,w,c,o] contracted over o (the
        # filter's OUTPUT slot), landing on input channel c
        up = np.zeros((2, 12, 12, 8), np.float32)
        for i in range(6):
            for j in range(6):
                up[:, 2 * i:2 * i + 2, 2 * j:2 * j + 2, :] += np.einsum(
                    "no,hwco->nhwc", cat[:, i, j, :], upw)
        gap = up.mean((1, 2))
        plus = ((gap - 0.5) ** 2) ** 1.5 + 0.1
        sel = np.where(plus > 0.15, plus, 0.0)
        ba = sel @ wfc + bias
        e = np.exp(ba - ba.max(-1, keepdims=True))
        prob = e / e.sum(-1, keepdims=True)
        return prob, np.float32(5.0)

    return g, expected


def test_slim_style_graph_imports_and_matches_numpy():
    g, expected = _build_graph()
    m = load_tf_graph(g, inputs=["input"], outputs=["prob", "aux"])
    assert len(m.nodes) >= 45
    x = np.random.RandomState(7).rand(2, 6, 6, 3).astype(np.float32)
    prob, aux = m.forward(x)
    want_prob, want_aux = expected(x)
    np.testing.assert_allclose(np.asarray(prob), want_prob,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(aux), want_aux)


def test_dynamic_switch_raises():
    g = b""
    g += _node("input", "Placeholder",
               attrs={"dtype": proto.enc_int64(6, 1)})
    g += _node("pred", "Greater", ["input", "input"])
    g += _node("sw", "Switch", ["input", "pred"])
    g += _node("out", "Identity", ["sw"])
    m = load_tf_graph(g, inputs=["input"], outputs=["out"])
    with pytest.raises(Exception, match="[Dd]ynamic Switch|Tracer"):
        m.forward(np.ones((2,), np.float32))


def test_splitv_and_slice():
    g = b""
    g += _node("input", "Placeholder",
               attrs={"dtype": proto.enc_int64(6, 1)})
    g += _const("sizes", np.asarray([1, 3]))
    g += _const("axis", np.asarray(1))
    g += _node("sv", "SplitV", ["input", "sizes", "axis"],
               {"num_split": proto.enc_int64(3, 2)})
    g += _const("sb", np.asarray([0, 0]))
    g += _const("ssz", np.asarray([-1, 2]))
    g += _node("sl", "Slice", ["sv:1", "sb", "ssz"])
    m = load_tf_graph(g, inputs=["input"], outputs=["sv", "sl"])
    x = np.random.RandomState(0).rand(2, 4).astype(np.float32)
    a, b = m.forward(x)
    np.testing.assert_allclose(np.asarray(a), x[:, :1])
    np.testing.assert_allclose(np.asarray(b), x[:, 1:3])


def test_import_graphdef_exported_by_real_tensorflow():
    """The strongest importer check: TensorFlow itself builds and
    serializes a slim-style conv graph (constants folded in), we import
    the bytes with load_tf_graph and match TF's own session output."""
    tf = pytest.importorskip("tensorflow")
    rng = np.random.RandomState(0)
    w1 = rng.randn(3, 3, 3, 8).astype(np.float32) * 0.3
    scale = (rng.rand(8) + 0.5).astype(np.float32)
    offset = rng.randn(8).astype(np.float32) * 0.1
    mean = rng.randn(8).astype(np.float32) * 0.1
    var = (rng.rand(8) + 0.5).astype(np.float32)
    wfc = rng.randn(8, 5).astype(np.float32)
    x = rng.rand(2, 8, 8, 3).astype(np.float32)

    g = tf.Graph()
    with g.as_default():
        inp = tf.compat.v1.placeholder(tf.float32, (2, 8, 8, 3),
                                       name="input")
        h = tf.nn.conv2d(inp, tf.constant(w1), strides=[1, 1, 1, 1],
                         padding="SAME")
        h = tf.compat.v1.nn.fused_batch_norm(
            h, tf.constant(scale), tf.constant(offset),
            tf.constant(mean), tf.constant(var), is_training=False)[0]
        h = tf.nn.relu(h)
        h = tf.nn.max_pool2d(h, 2, 2, "VALID")
        h = tf.pad(h, [[0, 0], [1, 1], [1, 1], [0, 0]])
        h = tf.reduce_mean(h, axis=[1, 2])
        h = tf.matmul(h, tf.constant(wfc))
        out = tf.nn.softmax(h, name="probs")
    with tf.compat.v1.Session(graph=g) as sess:
        want = sess.run("probs:0", feed_dict={"input:0": x})
    data = g.as_graph_def().SerializeToString()

    m = load_tf_graph(data, inputs=["input"], outputs=["probs"])
    got = np.asarray(m.forward(x))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_import_real_tf_cond_switch_merge():
    """tf.compat.v1 control flow (tf.cond on a constant predicate)
    serializes to real Switch/Merge nodes; the importer must fold them
    and prune the untaken branch."""
    tf = pytest.importorskip("tensorflow")
    was_v2 = tf.compat.v1.control_flow_v2_enabled()
    tf.compat.v1.disable_control_flow_v2()   # emit v1 Switch/Merge nodes
    try:
        g = tf.Graph()
        with g.as_default():
            inp = tf.compat.v1.placeholder(tf.float32, (2, 3), name="input")
            pred = tf.constant(False)
            out = tf.cond(pred, lambda: inp * 100.0, lambda: inp + 1.0)
            out = tf.identity(out, name="out")
    finally:
        if was_v2:
            tf.compat.v1.enable_control_flow_v2()
    with tf.compat.v1.Session(graph=g) as sess:
        x = np.random.RandomState(0).rand(2, 3).astype(np.float32)
        want = sess.run("out:0", feed_dict={"input:0": x})
    data = g.as_graph_def().SerializeToString()
    ops = {n.op for n in tf.compat.v1.GraphDef.FromString(data).node}
    assert "Switch" in ops and "Merge" in ops   # real v1 control flow

    m = load_tf_graph(data, inputs=["input"], outputs=["out"])
    got = np.asarray(m.forward(x))
    np.testing.assert_allclose(got, want, rtol=1e-6)
