"""Reference-format .bigdl reader/writer
(≙ utils/serializer/ModuleSerializer.scala, serialization/bigdl.proto).

The fixture in test_hand_encoded_linear is built with raw bigdl.proto
field numbers, independent of the writer, so reader and writer cannot
share a mistaken view of the schema."""
import os
import tempfile

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.utils import proto
from bigdl_tpu.utils.proto import enc_bytes, enc_string, enc_int64
from bigdl_tpu.utils.bigdl_format import load_bigdl, save_bigdl


def _roundtrip(model, x):
    y0 = np.asarray(model.forward(x))
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "m.bigdl")
        save_bigdl(model, p)
        m2 = load_bigdl(p)
    y1 = np.asarray(m2.forward(x))
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-6)
    return m2


def test_lenet_roundtrip_forward_parity():
    m = nn.Sequential(
        nn.Reshape((1, 28, 28)),
        nn.SpatialConvolution(1, 6, 5, 5), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.SpatialConvolution(6, 12, 5, 5), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.Reshape((12 * 4 * 4,)),
        nn.Linear(12 * 4 * 4, 100), nn.Tanh(),
        nn.Linear(100, 10), nn.LogSoftMax())
    m.reset(3)
    x = np.random.RandomState(0).rand(2, 784).astype(np.float32)
    m2 = _roundtrip(m, x)
    kinds = [type(c).__name__ for c in m2.modules()]
    assert "SpatialConvolution" in kinds and "LogSoftMax" in kinds


def test_resnet_block_roundtrip():
    block = nn.Sequential(
        nn.ConcatTable(
            nn.Sequential(
                nn.SpatialConvolution(4, 4, 3, 3, 1, 1, 1, 1),
                nn.SpatialBatchNormalization(4), nn.ReLU(),
                nn.SpatialConvolution(4, 4, 3, 3, 1, 1, 1, 1),
                nn.SpatialBatchNormalization(4)),
            nn.Identity()),
        nn.CAddTable(), nn.ReLU())
    block.reset(1)
    x = np.random.RandomState(1).rand(2, 4, 8, 8).astype(np.float32)
    _roundtrip(block, x)


def test_hand_encoded_linear():
    """Fixture encoded with raw bigdl.proto field numbers: BigDLModule
    {name=1, moduleType=7, attr=8 (map key=1/value=2), hasParameters=15,
    parameters=16}; AttrValue {dataType=1, int32Value=3, boolValue=8};
    BigDLTensor {datatype=1, size=2, offset=4, storage=8, id=9};
    TensorStorage {datatype=1, float_data=2 (packed), id=9};
    global_storage as NameAttrList (dataType NAME_ATTR_LIST=14)."""
    rng = np.random.RandomState(0)
    w = rng.randn(3, 5).astype(np.float32)   # (out, in) reference layout
    b = rng.randn(3).astype(np.float32)

    def tensor(arr, tid, sid, inline):
        body = enc_int64(1, 2)                        # datatype FLOAT
        for d in arr.shape:
            body += enc_int64(2, d)                   # size
        body += enc_int64(4, 1)                       # offset (1-based)
        st = enc_int64(1, 2)
        if inline:
            st += enc_bytes(2, arr.astype("<f4").tobytes())  # float_data
        st += enc_int64(9, sid)                       # storage id
        body += enc_bytes(8, st)
        body += enc_int64(9, tid)                     # tensor id
        return body

    def attr_entry(key, val):
        return enc_bytes(8, enc_string(1, key) + enc_bytes(2, val))

    attr_int = lambda v: enc_int64(1, 0) + enc_int64(3, v)
    attr_bool = lambda v: enc_int64(1, 5) + enc_int64(8, int(v))

    mod = enc_string(1, "fc1")
    mod += enc_string(7, "com.intel.analytics.bigdl.nn.Linear")
    mod += attr_entry("inputSize", attr_int(5))
    mod += attr_entry("outputSize", attr_int(3))
    mod += attr_entry("withBias", attr_bool(True))
    mod += enc_int64(15, 1)                           # hasParameters
    mod += enc_bytes(16, tensor(w, 1, 2, inline=False))
    mod += enc_bytes(16, tensor(b, 3, 4, inline=False))
    # global_storage holds the actual data
    nal = enc_string(1, "global_storage")
    for tid, sid, arr in ((1, 2, w), (3, 4, b)):
        av = enc_int64(1, 10) + enc_bytes(10, tensor(arr, tid, sid,
                                                     inline=True))
        nal += enc_bytes(2, enc_string(1, str(tid)) + enc_bytes(2, av))
    mod += attr_entry("global_storage", enc_int64(1, 14) + enc_bytes(14, nal))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "linear.bigdl")
        with open(p, "wb") as f:
            f.write(mod)
        m = load_bigdl(p)
    assert type(m).__name__ == "Linear" and m.name == "fc1"
    x = np.random.RandomState(2).rand(4, 5).astype(np.float32)
    np.testing.assert_allclose(np.asarray(m.forward(x)), x @ w.T + b,
                               rtol=1e-5)


def test_legacy_weight_bias_fields():
    """Pre-0.5.0 files carry weight/bias in the deprecated fields 3/4
    (ModuleSerializable.scala:336 copyWeightAndBias)."""
    rng = np.random.RandomState(1)
    w = rng.randn(2, 4).astype(np.float32)
    b = rng.randn(2).astype(np.float32)

    def tensor(arr):
        body = enc_int64(1, 2)
        for d in arr.shape:
            body += enc_int64(2, d)
        st = enc_int64(1, 2) + enc_bytes(2, arr.astype("<f4").tobytes())
        body += enc_bytes(8, st)
        return body

    def attr_entry(key, val):
        return enc_bytes(8, enc_string(1, key) + enc_bytes(2, val))

    attr_int = lambda v: enc_int64(1, 0) + enc_int64(3, v)
    mod = enc_string(1, "old")
    mod += enc_string(7, "com.intel.analytics.bigdl.nn.Linear")
    mod += attr_entry("inputSize", attr_int(4))
    mod += attr_entry("outputSize", attr_int(2))
    mod += enc_bytes(3, tensor(w))    # deprecated weight
    mod += enc_bytes(4, tensor(b))    # deprecated bias

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "legacy.bigdl")
        with open(p, "wb") as f:
            f.write(mod)
        m = load_bigdl(p)
    x = np.random.RandomState(3).rand(3, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(m.forward(x)), x @ w.T + b,
                               rtol=1e-5)


def test_unsupported_type_raises():
    mod = enc_string(7, "com.intel.analytics.bigdl.nn.VolumetricWeird")
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bad.bigdl")
        with open(p, "wb") as f:
            f.write(mod)
        with pytest.raises(ValueError, match="not mapped"):
            load_bigdl(p)


def test_save_unsupported_layer_raises():
    m = nn.Sequential(nn.Linear(2, 2), nn.RMSNorm(2))
    m.reset(0)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="unsupported layer"):
            save_bigdl(m, os.path.join(d, "x.bigdl"))


def test_full_convolution_roundtrip():
    """Deconv round-trip: reference weight (nGroup, in/g, out/g, kH, kW)
    flattens to exactly our (in, out/g, kh, kw) order, incl. groups."""
    m = nn.Sequential(
        nn.SpatialFullConvolution(4, 6, 3, 3, 2, 2, 1, 1, 1, 1,
                                  n_group=2))
    m.reset(6)
    x = np.random.RandomState(8).rand(2, 4, 5, 5).astype(np.float32)
    m2 = _roundtrip(m, x)
    fc = [c for c in m2.modules()
          if type(c).__name__ == "SpatialFullConvolution"][0]
    assert fc.n_group == 2 and fc.adj == (1, 1)


def test_prelu_and_elu_roundtrip():
    m = nn.Sequential(nn.Linear(4, 3), nn.PReLU(3), nn.ELU(0.7))
    m.reset(2)
    x = np.random.RandomState(4).randn(5, 4).astype(np.float32)
    _roundtrip(m, x)


def test_graph_dag_roundtrip():
    """StaticGraph wire form: a skip-connection DAG round-trips with
    forward parity (subModules + preModules wiring + inputNames/
    outputNames attrs, ≙ nn/Graph.scala GraphSerializable)."""
    from bigdl_tpu.nn.graph import Graph, Input

    inp = Input()
    fc1 = nn.Linear(6, 6).inputs(inp)
    act = nn.ReLU().inputs(fc1)
    add = nn.CAddTable().inputs([act, inp])       # skip connection
    out = nn.Linear(6, 3).inputs(add)
    m = Graph(inp, out)
    m.reset(4)
    x = np.random.RandomState(5).randn(3, 6).astype(np.float32)
    m2 = _roundtrip(m, x)
    kinds = [type(c).__name__ for c in m2.modules()]
    assert "CAddTable" in kinds


def test_graph_multi_input_roundtrip():
    from bigdl_tpu.nn.graph import Graph, Input
    from bigdl_tpu.utils.table import T

    a, b = Input(), Input()
    fa = nn.Linear(4, 5).inputs(a)
    fb = nn.Linear(4, 5).inputs(b)
    merged = nn.CMulTable().inputs([fa, fb])
    m = Graph([a, b], merged)
    m.reset(7)
    xa = np.random.RandomState(1).randn(2, 4).astype(np.float32)
    xb = np.random.RandomState(2).randn(2, 4).astype(np.float32)
    _roundtrip(m, T(xa, xb))


def test_graph_shared_module_rejected():
    from bigdl_tpu.nn.graph import Graph, Input

    inp = Input()
    shared = nn.Linear(4, 4)
    m = Graph(inp, shared.inputs(shared.inputs(inp)))
    m.reset(0)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(NotImplementedError, match="multiple graph"):
            save_bigdl(m, os.path.join(d, "s.bigdl"))


def test_hand_encoded_graph():
    """Graph fixture from raw field numbers (independent of the writer):
    BigDLModule subModules=2, preModules=5; inputNames/outputNames as
    ArrayValue str (ArrayValue.str field 7, datatype STRING=4)."""
    rng = np.random.RandomState(0)
    w = rng.randn(3, 5).astype(np.float32)
    b = rng.randn(3).astype(np.float32)

    def tensor(arr, inline=True):
        body = enc_int64(1, 2)
        for d in arr.shape:
            body += enc_int64(2, d)
        st = enc_int64(1, 2) + enc_bytes(2, arr.astype("<f4").tobytes())
        body += enc_bytes(8, st)
        return body

    def attr_entry(key, val):
        return enc_bytes(8, enc_string(1, key) + enc_bytes(2, val))

    attr_int = lambda v: enc_int64(1, 0) + enc_int64(3, v)

    def str_array(vals):
        arr = enc_int64(1, len(vals)) + enc_int64(2, 4)   # STRING
        for v in vals:
            arr += enc_string(7, v)
        return enc_int64(1, 15) + enc_bytes(15, arr)      # ARRAY_VALUE

    node_in = enc_string(1, "in0") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Input")
    node_fc = enc_string(1, "fc")
    node_fc += enc_string(7, "com.intel.analytics.bigdl.nn.Linear")
    node_fc += attr_entry("inputSize", attr_int(5))
    node_fc += attr_entry("outputSize", attr_int(3))
    node_fc += enc_int64(15, 1)
    node_fc += enc_bytes(16, tensor(w))
    node_fc += enc_bytes(16, tensor(b))
    node_fc += enc_string(5, "in0")                       # preModules
    node_out = enc_string(1, "act")
    node_out += enc_string(7, "com.intel.analytics.bigdl.nn.Tanh")
    node_out += enc_string(5, "fc")

    g = enc_string(1, "net")
    g += enc_string(7, "com.intel.analytics.bigdl.nn.StaticGraph")
    g += enc_bytes(2, node_in) + enc_bytes(2, node_fc) \
        + enc_bytes(2, node_out)
    g += attr_entry("inputNames", str_array(["in0"]))
    g += attr_entry("outputNames", str_array(["act"]))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "g.bigdl")
        with open(p, "wb") as f:
            f.write(g)
        m = load_bigdl(p)
    x = np.random.RandomState(6).rand(4, 5).astype(np.float32)
    np.testing.assert_allclose(np.asarray(m.forward(x)),
                               np.tanh(x @ w.T + b), rtol=1e-5)


def test_elementwise_breadth_roundtrip():
    """The widened factory (activations/constants/shape ops) round-trips
    with non-default hyperparameters preserved."""
    m = nn.Sequential(
        nn.Linear(6, 6),
        nn.HardTanh(-2.0, 2.0),
        nn.MulConstant(3.0),
        nn.AddConstant(0.25),
        nn.SoftPlus(2.0),
        nn.LeakyReLU(0.2),
        nn.Normalize(1.0),
        nn.Mean(2, squeeze=True))
    m.reset(9)
    x = np.random.RandomState(9).randn(3, 6).astype(np.float32)
    m2 = _roundtrip(m, x)
    got = {type(c).__name__: c for c in m2.modules()}
    assert got["HardTanh"].min_value == -2.0
    assert got["MulConstant"].scalar == 3.0
    assert got["AddConstant"].constant == 0.25
    assert got["SoftPlus"].beta == 2.0
    assert got["LeakyReLU"].negval == 0.2
    assert got["Normalize"].p == 1.0


def test_shape_and_table_ops_roundtrip():
    m = nn.Sequential(
        nn.Unsqueeze(1),            # (B, 1, 6)
        nn.Narrow(3, 2, 4),         # (B, 1, 4)
        nn.Squeeze(),               # (B, 4)  (drop all size-1 dims)
        nn.Select(2, 1))            # (B,)
    m.reset(0)
    x = np.random.RandomState(3).randn(5, 6).astype(np.float32)
    _roundtrip(m, x)


def test_bn_running_stats_roundtrip():
    """Running mean/var ride the BN module's attr map
    (nn/BatchNormalization.scala:346 doSerializeModule) and must survive
    save->load so eval-mode inference matches."""
    m = nn.Sequential(nn.SpatialConvolution(2, 3, 3, 3, 1, 1, 1, 1),
                      nn.SpatialBatchNormalization(3), nn.ReLU())
    m.reset(5)
    rng = np.random.RandomState(7)
    m.training()
    for _ in range(3):   # accumulate non-trivial running stats
        m.forward(rng.rand(4, 2, 6, 6).astype(np.float32) * 3 + 1)
    m.evaluate()
    x = rng.rand(2, 2, 6, 6).astype(np.float32)
    y0 = np.asarray(m.forward(x))

    bn_name = [c.name for c in m.modules()
               if type(c).__name__ == "SpatialBatchNormalization"][0]
    rm0 = np.asarray(m._state[bn_name]["running_mean"])
    assert np.abs(rm0).max() > 0.1   # stats actually moved off init

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bn.bigdl")
        save_bigdl(m, p)
        m2 = load_bigdl(p)
    m2.evaluate()
    np.testing.assert_allclose(np.asarray(m2._state[bn_name]["running_mean"]),
                               rm0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m2.forward(x)), y0,
                               rtol=1e-5, atol=1e-6)


def test_hand_encoded_bn_running_stats():
    """Fixture with raw field numbers: runningMean/runningVar as TENSOR
    attrs (dataType=10, tensorValue field 10) on the BN module, data
    inline — independent of the writer."""
    n = 4
    gamma = np.ones(n, np.float32)
    beta = np.zeros(n, np.float32)
    rmean = np.array([0.5, -1.0, 2.0, 0.0], np.float32)
    rvar = np.array([1.5, 0.25, 4.0, 1.0], np.float32)

    def tensor(arr):
        body = enc_int64(1, 2)
        for d in arr.shape:
            body += enc_int64(2, d)
        st = enc_int64(1, 2) + enc_bytes(2, arr.astype("<f4").tobytes())
        body += enc_bytes(8, st)
        return body

    def attr_entry(key, val):
        return enc_bytes(8, enc_string(1, key) + enc_bytes(2, val))

    attr_int = lambda v: enc_int64(1, 0) + enc_int64(3, v)
    attr_tensor = lambda a: enc_int64(1, 10) + enc_bytes(10, tensor(a))

    mod = enc_string(1, "bn")
    mod += enc_string(7,
                      "com.intel.analytics.bigdl.nn.SpatialBatchNormalization")
    mod += attr_entry("nOutput", attr_int(n))
    mod += enc_int64(15, 1)
    mod += enc_bytes(16, tensor(gamma))
    mod += enc_bytes(16, tensor(beta))
    mod += attr_entry("runningMean", attr_tensor(rmean))
    mod += attr_entry("runningVar", attr_tensor(rvar))
    mod += attr_entry("saveMean", attr_tensor(np.zeros(n, np.float32)))
    mod += attr_entry("saveStd", attr_tensor(np.zeros(n, np.float32)))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bn.bigdl")
        with open(p, "wb") as f:
            f.write(mod)
        m = load_bigdl(p)
    m.evaluate()
    x = np.random.RandomState(8).rand(2, n, 3, 3).astype(np.float32)
    want = (x - rmean[None, :, None, None]) / np.sqrt(
        rvar[None, :, None, None] + m.eps)
    np.testing.assert_allclose(np.asarray(m.forward(x)), want,
                               rtol=1e-4, atol=1e-5)


def _mod_tensor(arr):
    body = enc_int64(1, 2)
    for d in arr.shape:
        body += enc_int64(2, d)
    st = enc_int64(1, 2) + enc_bytes(2, arr.astype("<f4").tobytes())
    body += enc_bytes(8, st)
    return body


def _mod_attr_entry(key, val):
    return enc_bytes(8, enc_string(1, key) + enc_bytes(2, val))


def _attr_i(v):
    return enc_int64(1, 0) + enc_int64(3, v)


def _attr_d(v):
    return enc_int64(1, 3) + proto.enc_double(6, v)


def _attr_mod(mod_bytes):
    # DataType MODULE = 13 (bigdl.proto:112) so fixtures match real
    # reference files; our reader keys off field 13 regardless.
    return enc_int64(1, 13) + enc_bytes(13, mod_bytes)


def _linear_module(name, w, b=None):
    m = enc_string(1, name)
    m += enc_string(7, "com.intel.analytics.bigdl.nn.Linear")
    m += _mod_attr_entry("inputSize", _attr_i(w.shape[1]))
    m += _mod_attr_entry("outputSize", _attr_i(w.shape[0]))
    m += enc_int64(15, 1)
    m += enc_bytes(16, _mod_tensor(w))
    if b is not None:
        m += enc_bytes(16, _mod_tensor(b))
    return m


def test_recurrent_lstm_read():
    """Recurrent(LSTM) fixture in reference wire layout: topology as a
    module attr (nn/Recurrent.scala:776 doSerializeModule), the LSTM's
    input Linear under its preTopology attr (Cell.scala CellSerializer),
    h2g in the cell's flat params.  Reference gate order [i, g, f, o]
    (LSTM.scala:134-147) must be re-ordered onto our fused [i, f, g, o]."""
    rng = np.random.RandomState(11)
    nin, h = 3, 4
    w_pre = rng.randn(4 * h, nin).astype(np.float32)
    b_pre = rng.randn(4 * h).astype(np.float32)
    w_h2g = rng.randn(4 * h, h).astype(np.float32)

    lstm = enc_string(1, "lstm1")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(nin))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("p", _attr_d(0.0))
    lstm += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    lstm += enc_int64(15, 1)
    lstm += enc_bytes(16, _mod_tensor(w_h2g))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("bnorm", enc_int64(1, 5) + enc_int64(8, 0))
    rec += _mod_attr_entry("topology", _attr_mod(lstm))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 2, 5
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    # independent numpy reference in the REFERENCE's [i, g, f, o] order
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    cs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        z = x[:, t] @ w_pre.T + b_pre + hs @ w_h2g.T
        i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        i, f, o, g = sig(i), sig(f), sig(o), np.tanh(g)
        cs = i * g + f * cs
        hs = o * np.tanh(cs)
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_gru_read():
    """Recurrent(GRU): pre-Linear chunks [r, z, n] (GRU.scala:107,137),
    hidden Linears h2g (2h, no bias) and the new-gate Linear (h, no
    bias) ride the cell's flat params."""
    rng = np.random.RandomState(12)
    nin, h = 4, 3
    w_pre = rng.randn(3 * h, nin).astype(np.float32)
    b_pre = rng.randn(3 * h).astype(np.float32)
    w_h2g = rng.randn(2 * h, h).astype(np.float32)
    w_new = rng.randn(h, h).astype(np.float32)

    gru = enc_string(1, "gru1")
    gru += enc_string(7, "com.intel.analytics.bigdl.nn.GRU")
    gru += _mod_attr_entry("inputSize", _attr_i(nin))
    gru += _mod_attr_entry("outputSize", _attr_i(h))
    gru += _mod_attr_entry("p", _attr_d(0.0))
    gru += _mod_attr_entry("preTopology",
                           _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    gru += enc_int64(15, 1)
    gru += enc_bytes(16, _mod_tensor(w_h2g))
    gru += enc_bytes(16, _mod_tensor(w_new))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(gru))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        pre = x[:, t] @ w_pre.T + b_pre
        rz = pre[:, :2*h] + hs @ w_h2g.T
        r, z = sig(rz[:, :h]), sig(rz[:, h:])
        hhat = np.tanh(pre[:, 2*h:] + (r * hs) @ w_new.T)
        hs = (1.0 - z) * hhat + z * hs
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_lstm_dropout_read():
    """LSTM(p=0.5) wire layout: NO preTopology, per-gate
    Sequential(Dropout, Linear) stacks in the cell's flat params
    (LSTM.scala:77-116; biased input Linears, bias-free hidden ones,
    reference gate order [i,g,f,o]).  Eval-mode numerics must match the
    fused reconstruction; the loaded cell carries p for training."""
    rng = np.random.RandomState(16)
    nin, h = 3, 4
    wi = [rng.randn(h, nin).astype(np.float32) for _ in range(4)]
    bi = [rng.randn(h).astype(np.float32) for _ in range(4)]
    wh = [rng.randn(h, h).astype(np.float32) for _ in range(4)]

    lstm = enc_string(1, "lstm_p")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(nin))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("p", _attr_d(0.5))
    lstm += enc_int64(15, 1)
    for k in range(4):
        lstm += enc_bytes(16, _mod_tensor(wi[k]))
        lstm += enc_bytes(16, _mod_tensor(bi[k]))
    for k in range(4):
        lstm += enc_bytes(16, _mod_tensor(wh[k]))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(lstm))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    cells = [c for c in m.modules() if type(c).__name__ == "LSTM"]
    assert cells and cells[0].dropout_p == 0.5

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    m.evaluate()
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    w_pre = np.concatenate(wi, 0)          # ref order [i, g, f, o]
    b_pre = np.concatenate(bi, 0)
    w_h2g = np.concatenate(wh, 0)
    hs = np.zeros((B, h), np.float32)
    cs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        z = x[:, t] @ w_pre.T + b_pre + hs @ w_h2g.T
        i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        cs = sig(i) * np.tanh(g) + sig(f) * cs
        hs = sig(o) * np.tanh(cs)
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_rnncell_dropout_rejected():
    """p>0 read support covers LSTM/GRU only; other cell types keep the
    honest raise (their per-gate graphs are not rebuilt)."""
    cell = enc_string(1, "r")
    cell += enc_string(7, "com.intel.analytics.bigdl.nn.RnnCell")
    cell += _mod_attr_entry("inputSize", _attr_i(2))
    cell += _mod_attr_entry("hiddenSize", _attr_i(2))
    cell += _mod_attr_entry("p", _attr_d(0.5))
    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(cell))
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        with pytest.raises(ValueError, match="p>0 layout"):
            load_bigdl(p)


def test_recurrent_gru_dropout_read():
    """GRU(p=0.3) wire layout (GRU.scala:90-105,132-146): i2g [r,z] +
    candidate f2g with biases, h2g [r,z] + candidate hidden without."""
    rng = np.random.RandomState(17)
    nin, h = 4, 3
    w_r = rng.randn(h, nin).astype(np.float32)
    b_r = rng.randn(h).astype(np.float32)
    w_z = rng.randn(h, nin).astype(np.float32)
    b_z = rng.randn(h).astype(np.float32)
    w_n = rng.randn(h, nin).astype(np.float32)
    b_n = rng.randn(h).astype(np.float32)
    h_r = rng.randn(h, h).astype(np.float32)
    h_z = rng.randn(h, h).astype(np.float32)
    h_n = rng.randn(h, h).astype(np.float32)

    gru = enc_string(1, "gru_p")
    gru += enc_string(7, "com.intel.analytics.bigdl.nn.GRU")
    gru += _mod_attr_entry("inputSize", _attr_i(nin))
    gru += _mod_attr_entry("outputSize", _attr_i(h))
    gru += _mod_attr_entry("p", _attr_d(0.3))
    gru += enc_int64(15, 1)
    # topo interleaving: i2g pairs, then h2g mats, then candidate pair,
    # then candidate hidden — the bias-adjacency classifier must not
    # depend on a single global order
    gru += enc_bytes(16, _mod_tensor(w_r)) + enc_bytes(16, _mod_tensor(b_r))
    gru += enc_bytes(16, _mod_tensor(w_z)) + enc_bytes(16, _mod_tensor(b_z))
    gru += enc_bytes(16, _mod_tensor(h_r)) + enc_bytes(16, _mod_tensor(h_z))
    gru += enc_bytes(16, _mod_tensor(w_n)) + enc_bytes(16, _mod_tensor(b_n))
    gru += enc_bytes(16, _mod_tensor(h_n))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(gru))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    m.evaluate()
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        r = sig(x[:, t] @ w_r.T + b_r + hs @ h_r.T)
        z = sig(x[:, t] @ w_z.T + b_z + hs @ h_z.T)
        hhat = np.tanh(x[:, t] @ w_n.T + b_n + (r * hs) @ h_n.T)
        hs = (1.0 - z) * hhat + z * hs
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_rnncell_read():
    """RnnCell (nn/RNN.scala): input Linear in preTopology, h2h Linear
    (weight + its own bias) in the cell params; the two biases sum into
    our single fused bias.  Non-default ReLU activation passes through."""
    rng = np.random.RandomState(13)
    nin, h = 3, 5
    w_pre = rng.randn(h, nin).astype(np.float32)
    b_pre = rng.randn(h).astype(np.float32)
    w_h2h = rng.randn(h, h).astype(np.float32)
    b_h2h = rng.randn(h).astype(np.float32)

    relu = enc_string(1, "act") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.ReLU")
    cell = enc_string(1, "rnn1")
    cell += enc_string(7, "com.intel.analytics.bigdl.nn.RnnCell")
    cell += _mod_attr_entry("inputSize", _attr_i(nin))
    cell += _mod_attr_entry("hiddenSize", _attr_i(h))
    cell += _mod_attr_entry("activation", _attr_mod(relu))
    cell += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2h", w_pre, b_pre)))
    cell += enc_int64(15, 1)
    cell += enc_bytes(16, _mod_tensor(w_h2h))
    cell += enc_bytes(16, _mod_tensor(b_h2h))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(cell))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 3, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))
    hs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        hs = np.maximum(x[:, t] @ w_pre.T + b_pre + hs @ w_h2h.T + b_h2h,
                        0.0)
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_parameterized_activation_rejected():
    prelu = enc_string(1, "act") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.PReLU") \
        + _mod_attr_entry("nOutputPlane", _attr_i(2))
    cell = enc_string(1, "r")
    cell += enc_string(7, "com.intel.analytics.bigdl.nn.RnnCell")
    cell += _mod_attr_entry("inputSize", _attr_i(2))
    cell += _mod_attr_entry("hiddenSize", _attr_i(2))
    cell += _mod_attr_entry("activation", _attr_mod(prelu))
    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(cell))
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        with pytest.raises(ValueError, match="parameterized activation"):
            load_bigdl(p)


def test_recurrent_lstm_nondefault_activation():
    """LSTM(activation=Sigmoid) must load with the serialized activation
    applied (not silently fall back to tanh)."""
    rng = np.random.RandomState(14)
    nin, h = 2, 3
    w_pre = rng.randn(4 * h, nin).astype(np.float32)
    b_pre = rng.randn(4 * h).astype(np.float32)
    w_h2g = rng.randn(4 * h, h).astype(np.float32)

    sigm = enc_string(1, "sa") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Sigmoid")
    lstm = enc_string(1, "lstm1")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(nin))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("activation", _attr_mod(sigm))
    lstm += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    lstm += enc_int64(15, 1)
    lstm += enc_bytes(16, _mod_tensor(w_h2g))
    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(lstm))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)
    B, T = 2, 3
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    cs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        z = x[:, t] @ w_pre.T + b_pre + hs @ w_h2g.T
        i, g, f, o = z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:]
        cs = sig(i) * sig(g) + sig(f) * cs      # activation=Sigmoid
        hs = sig(o) * sig(cs)
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_birecurrent_lstm_read():
    """BiRecurrent(LSTM) wire layout (nn/BiRecurrent.scala:48-66): the
    birnn Sequential rides a module attr with forward and
    Reverse-wrapped backward Recurrents; default merge is CAddTable."""
    rng = np.random.RandomState(21)
    nin, h = 3, 4

    def lstm_tree(name, wp, bp, wh):
        t = enc_string(1, name)
        t += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
        t += _mod_attr_entry("inputSize", _attr_i(nin))
        t += _mod_attr_entry("hiddenSize", _attr_i(h))
        t += _mod_attr_entry("p", _attr_d(0.0))
        t += _mod_attr_entry(
            "preTopology", _attr_mod(_linear_module(name + "_i2g", wp, bp)))
        t += enc_int64(15, 1)
        t += enc_bytes(16, _mod_tensor(wh))
        return t

    wpf = rng.randn(4 * h, nin).astype(np.float32)
    bpf = rng.randn(4 * h).astype(np.float32)
    whf = rng.randn(4 * h, h).astype(np.float32)
    wpb = rng.randn(4 * h, nin).astype(np.float32)
    bpb = rng.randn(4 * h).astype(np.float32)
    whb = rng.randn(4 * h, h).astype(np.float32)

    fwd = _recurrent_tree("rec_f", lstm_tree("lstm_f", wpf, bpf, whf))
    rev = _recurrent_tree("rec_b", lstm_tree("lstm_b", wpb, bpb, whb))

    bi = enc_string(1, "bi")
    bi += enc_string(7, "com.intel.analytics.bigdl.nn.BiRecurrent")
    bi += _mod_attr_entry("birnn", _attr_mod(_birnn_bytes(fwd, rev)))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bi.bigdl")
        with open(p, "wb") as f:
            f.write(bi)
        m = load_bigdl(p)

    B, T = 2, 5
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))

    def run_lstm(xs, wp, bp, wh):
        hs = np.zeros((B, h), np.float32)
        cs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            z = xs[:, t] @ wp.T + bp + hs @ wh.T
            i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
            cs = sig(i) * np.tanh(g) + sig(f) * cs
            hs = sig(o) * np.tanh(cs)
            out[:, t] = hs
        return out

    yf = run_lstm(x, wpf, bpf, whf)
    yb = run_lstm(x[:, ::-1], wpb, bpb, whb)[:, ::-1]
    np.testing.assert_allclose(got, yf + yb, rtol=1e-4, atol=1e-5)


def _attr_b(v):
    return enc_int64(1, 5) + enc_int64(8, 1 if v else 0)


def test_recurrent_gru_nondefault_activations():
    """GRU(activation=Sigmoid, innerActivation=Tanh) loads with the
    serialized nonlinearities applied (nn/GRU.scala:62-72 ctor params;
    was an honest raise through r4)."""
    rng = np.random.RandomState(15)
    nin, h = 4, 3
    w_pre = rng.randn(3 * h, nin).astype(np.float32)
    b_pre = rng.randn(3 * h).astype(np.float32)
    w_h2g = rng.randn(2 * h, h).astype(np.float32)
    w_new = rng.randn(h, h).astype(np.float32)

    sigm = enc_string(1, "ga") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Sigmoid")
    tanh = enc_string(1, "gi") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Tanh")
    gru = enc_string(1, "gru1")
    gru += enc_string(7, "com.intel.analytics.bigdl.nn.GRU")
    gru += _mod_attr_entry("inputSize", _attr_i(nin))
    gru += _mod_attr_entry("outputSize", _attr_i(h))
    gru += _mod_attr_entry("p", _attr_d(0.0))
    gru += _mod_attr_entry("activation", _attr_mod(sigm))
    gru += _mod_attr_entry("innerActivation", _attr_mod(tanh))
    gru += _mod_attr_entry("preTopology",
                           _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    gru += enc_int64(15, 1)
    gru += enc_bytes(16, _mod_tensor(w_h2g))
    gru += enc_bytes(16, _mod_tensor(w_new))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(gru))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        pre = x[:, t] @ w_pre.T + b_pre
        rz = np.tanh(pre[:, :2*h] + hs @ w_h2g.T)       # inner=Tanh
        r, z = rz[:, :h], rz[:, h:]
        hhat = sig(pre[:, 2*h:] + (r * hs) @ w_new.T)   # act=Sigmoid
        hs = (1.0 - z) * hhat + z * hs
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _recurrent_tree(name, cell_bytes):
    r = enc_string(1, name)
    r += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    r += _mod_attr_entry("topology", _attr_mod(cell_bytes))
    return r


def _rnncell_tree(name, wp, bp, wh, bh, isz, h):
    cell = enc_string(1, name)
    cell += enc_string(7, "com.intel.analytics.bigdl.nn.RnnCell")
    cell += _mod_attr_entry("inputSize", _attr_i(isz))
    cell += _mod_attr_entry("hiddenSize", _attr_i(h))
    cell += _mod_attr_entry(
        "preTopology", _attr_mod(_linear_module(name + "_i", wp, bp)))
    cell += enc_int64(15, 1)
    cell += enc_bytes(16, _mod_tensor(wh))
    cell += enc_bytes(16, _mod_tensor(bh))
    return cell


def _birnn_bytes(fwd_rec, rev_rec, fan_type="ConcatTable"):
    reverse1 = enc_string(1, "rev1") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Reverse")
    reverse2 = enc_string(1, "rev2") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Reverse")
    seq_rev = enc_string(1, "seqr") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Sequential") \
        + enc_bytes(2, reverse1) + enc_bytes(2, rev_rec) \
        + enc_bytes(2, reverse2)
    par = enc_string(1, "par") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.ParallelTable") \
        + enc_bytes(2, fwd_rec) + enc_bytes(2, seq_rev)
    fan = enc_string(1, "fan") \
        + enc_string(7, f"com.intel.analytics.bigdl.nn.{fan_type}")
    madd = enc_string(1, "madd") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.CAddTable")
    return enc_string(1, "birnn") \
        + enc_string(7, "com.intel.analytics.bigdl.nn.Sequential") \
        + enc_bytes(2, fan) + enc_bytes(2, par) + enc_bytes(2, madd)


def test_birecurrent_split_input_read():
    """BiRecurrent(isSplitInput=true): the feature dim halves —
    first half to the forward RNN, second to the backward one
    (BiRecurrent.scala:50 BifurcateSplitTable; was an honest raise
    through r4)."""
    rng = np.random.RandomState(22)
    nin, h = 3, 4           # model feature width = 2*nin

    wpf = rng.randn(h, nin).astype(np.float32)
    bpf = rng.randn(h).astype(np.float32)
    whf = rng.randn(h, h).astype(np.float32)
    bhf = rng.randn(h).astype(np.float32)
    wpb = rng.randn(h, nin).astype(np.float32)
    bpb = rng.randn(h).astype(np.float32)
    whb = rng.randn(h, h).astype(np.float32)
    bhb = rng.randn(h).astype(np.float32)

    fwd = _recurrent_tree(
        "rec_f", _rnncell_tree("cell_f", wpf, bpf, whf, bhf, nin, h))
    rev = _recurrent_tree(
        "rec_b", _rnncell_tree("cell_b", wpb, bpb, whb, bhb, nin, h))

    bi = enc_string(1, "bi")
    bi += enc_string(7, "com.intel.analytics.bigdl.nn.BiRecurrent")
    bi += _mod_attr_entry("isSplitInput", _attr_b(True))
    bi += _mod_attr_entry(
        "birnn", _attr_mod(_birnn_bytes(fwd, rev, "BifurcateSplitTable")))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bi.bigdl")
        with open(p, "wb") as f:
            f.write(bi)
        m = load_bigdl(p)

    B, T = 2, 5
    x = rng.randn(B, T, 2 * nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    def run_rnn(xs, wp, bp, wh, bh):
        hs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            hs = np.tanh(xs[:, t] @ wp.T + bp + hs @ wh.T + bh)
            out[:, t] = hs
        return out

    yf = run_rnn(x[..., :nin], wpf, bpf, whf, bhf)
    yb = run_rnn(x[:, ::-1, nin:], wpb, bpb, whb, bhb)[:, ::-1]
    np.testing.assert_allclose(got, yf + yb, rtol=1e-4, atol=1e-5)


def test_birecurrent_multirnncell_read():
    """BiRecurrent over MultiRNNCell (stacked bidirectional): each
    backward sub-cell's weights land on the '<fwd-sub>_bwd' slot (was
    an honest raise through r4)."""
    rng = np.random.RandomState(23)
    nin, h = 3, 3

    def mrc_tree(name, prefix, ws):
        cells_arr = enc_int64(1, 2) + enc_int64(2, 16)
        cells_arr += enc_bytes(13, _rnncell_tree(
            prefix + "_c1", *ws[0], nin, h))
        cells_arr += enc_bytes(13, _rnncell_tree(
            prefix + "_c2", *ws[1], h, h))
        mrc = enc_string(1, name)
        mrc += enc_string(7, "com.intel.analytics.bigdl.nn.MultiRNNCell")
        mrc += _mod_attr_entry("cells", enc_int64(1, 15)
                               + enc_bytes(15, cells_arr))
        return mrc

    def rand_cell(isz):
        return (rng.randn(h, isz).astype(np.float32),
                rng.randn(h).astype(np.float32),
                rng.randn(h, h).astype(np.float32),
                rng.randn(h).astype(np.float32))

    ws_f = [rand_cell(nin), rand_cell(h)]
    ws_b = [rand_cell(nin), rand_cell(h)]

    fwd = _recurrent_tree("rec_f", mrc_tree("stack_f", "f", ws_f))
    rev = _recurrent_tree("rec_b", mrc_tree("stack_b", "b", ws_b))

    bi = enc_string(1, "bi")
    bi += enc_string(7, "com.intel.analytics.bigdl.nn.BiRecurrent")
    bi += _mod_attr_entry("birnn", _attr_mod(_birnn_bytes(fwd, rev)))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bi.bigdl")
        with open(p, "wb") as f:
            f.write(bi)
        m = load_bigdl(p)

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    def run_rnn(xs, wp, bp, wh, bh):
        hs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            hs = np.tanh(xs[:, t] @ wp.T + bp + hs @ wh.T + bh)
            out[:, t] = hs
        return out

    def run_stack(xs, ws):
        return run_rnn(run_rnn(xs, *ws[0]), *ws[1])

    yf = run_stack(x, ws_f)
    yb = run_stack(x[:, ::-1], ws_b)[:, ::-1]
    np.testing.assert_allclose(got, yf + yb, rtol=1e-4, atol=1e-5)


def test_lookup_table_and_time_distributed_read():
    """NLP-shaped fixture: TimeDistributed(Linear) after LookupTable —
    the wrapped layer's weights ride the 'layer' module attr
    (TimeDistributed.scala ctor reflection)."""
    rng = np.random.RandomState(31)
    n_index, n_out, d = 7, 5, 4
    emb = rng.randn(n_index, d).astype(np.float32)
    w = rng.randn(n_out, d).astype(np.float32)
    b = rng.randn(n_out).astype(np.float32)

    lut = enc_string(1, "emb")
    lut += enc_string(7, "com.intel.analytics.bigdl.nn.LookupTable")
    lut += _mod_attr_entry("nIndex", _attr_i(n_index))
    lut += _mod_attr_entry("nOutput", _attr_i(d))
    lut += enc_int64(15, 1)
    lut += enc_bytes(16, _mod_tensor(emb))

    td = enc_string(1, "td")
    td += enc_string(7, "com.intel.analytics.bigdl.nn.TimeDistributed")
    td += _mod_attr_entry("layer", _attr_mod(_linear_module("fc", w, b)))
    td += enc_int64(15, 1)
    td += enc_bytes(16, _mod_tensor(w))
    td += enc_bytes(16, _mod_tensor(b))

    seq = enc_string(1, "net")
    seq += enc_string(7, "com.intel.analytics.bigdl.nn.Sequential")
    seq += enc_bytes(2, lut) + enc_bytes(2, td)

    with tempfile.TemporaryDirectory() as d2:
        p = os.path.join(d2, "nlp.bigdl")
        with open(p, "wb") as f:
            f.write(seq)
        m = load_bigdl(p)

    ids = np.array([[1, 3, 7], [2, 5, 1]], np.float32)   # 1-based
    got = np.asarray(m.forward(ids))
    want = emb[ids.astype(int) - 1] @ w.T + b
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_temporal_convolution_read_layout():
    """Reference TemporalConvolution weight is (out, in*kW) with column
    order k*inputFrameSize + i (unfold layout); our fused layout is
    (out, in, kW) — the loader must reorder, not just reshape."""
    rng = np.random.RandomState(32)
    fin, fout, kw = 3, 2, 2
    w_ref = rng.randn(fout, fin * kw).astype(np.float32)
    b = rng.randn(fout).astype(np.float32)

    tc = enc_string(1, "tc")
    tc += enc_string(7, "com.intel.analytics.bigdl.nn.TemporalConvolution")
    tc += _mod_attr_entry("inputFrameSize", _attr_i(fin))
    tc += _mod_attr_entry("outputFrameSize", _attr_i(fout))
    tc += _mod_attr_entry("kernelW", _attr_i(kw))
    tc += _mod_attr_entry("strideW", _attr_i(1))
    tc += enc_int64(15, 1)
    tc += enc_bytes(16, _mod_tensor(w_ref))
    tc += enc_bytes(16, _mod_tensor(b))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "tc.bigdl")
        with open(p, "wb") as f:
            f.write(tc)
        m = load_bigdl(p)

    B, T = 2, 5
    x = rng.randn(B, T, fin).astype(np.float32)
    got = np.asarray(m.forward(x))
    # reference math: out[t] = sum_k x[t+k] @ W[:, k*fin:(k+1)*fin].T + b
    want = np.zeros((B, T - kw + 1, fout), np.float32)
    for t in range(T - kw + 1):
        acc = b.copy()[None].repeat(B, 0)
        for k in range(kw):
            acc = acc + x[:, t + k] @ w_ref[:, k*fin:(k+1)*fin].T
        want[:, t] = acc
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_dilated_conv_and_padding_read():
    rng = np.random.RandomState(33)
    w = rng.randn(3, 2, 3, 3).astype(np.float32)
    b = rng.randn(3).astype(np.float32)

    dc = enc_string(1, "dc")
    dc += enc_string(7,
                     "com.intel.analytics.bigdl.nn.SpatialDilatedConvolution")
    for k, v in (("nInputPlane", 2), ("nOutputPlane", 3), ("kW", 3),
                 ("kH", 3), ("dW", 1), ("dH", 1), ("padW", 2), ("padH", 2),
                 ("dilationW", 2), ("dilationH", 2)):
        dc += _mod_attr_entry(k, _attr_i(v))
    dc += enc_int64(15, 1)
    dc += enc_bytes(16, _mod_tensor(w)) + enc_bytes(16, _mod_tensor(b))

    zp = enc_string(1, "zp")
    zp += enc_string(7, "com.intel.analytics.bigdl.nn.SpatialZeroPadding")
    for k in ("padLeft", "padRight", "padTop", "padBottom"):
        zp += _mod_attr_entry(k, _attr_i(1))

    seq = enc_string(1, "net")
    seq += enc_string(7, "com.intel.analytics.bigdl.nn.Sequential")
    seq += enc_bytes(2, zp) + enc_bytes(2, dc)

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "dil.bigdl")
        with open(p, "wb") as f:
            f.write(seq)
        m = load_bigdl(p)
    x = rng.randn(2, 2, 6, 6).astype(np.float32)
    got = np.asarray(m.forward(x))
    assert got.shape == (2, 3, 8, 8)
    kinds = [type(c).__name__ for c in m.modules()]
    assert "SpatialDilatedConvolution" in kinds
    assert "SpatialZeroPadding" in kinds


def test_new_types_roundtrip():
    """Full round-trip for the round-4 reader additions: writer emits
    ctor attrs + reference weight layouts (temporal conv columns are
    re-unfolded), reader restores them exactly."""
    m = nn.Sequential(nn.LookupTable(9, 6),
                      nn.TemporalConvolution(6, 5, 2),
                      nn.TimeDistributed(nn.Linear(5, 4)),
                      nn.Select(2, -1))
    m.reset(3)
    ids = (np.random.RandomState(1).randint(0, 9, (3, 7)) + 1) \
        .astype(np.float32)
    m2 = _roundtrip(m, ids)
    kinds = [type(c).__name__ for c in m2.modules()]
    for k in ("LookupTable", "TemporalConvolution", "TimeDistributed"):
        assert k in kinds, kinds


def test_module_attr_datatype_is_module_13():
    """save_bigdl must tag module-valued attrs DataType.MODULE = 13
    (bigdl.proto:112); the reference DataConverter dispatches on
    dataType, so 12 (INITMETHOD) would route to the wrong converter
    and the file would fail to load in the reference."""
    m = nn.Sequential(nn.TimeDistributed(nn.Linear(5, 4)))
    m.reset(3)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "td.bigdl")
        save_bigdl(m, p)
        with open(p, "rb") as f:
            buf = f.read()

    found = []

    def walk_module(mod_bytes):
        for field, wire, val in proto.iter_fields(mod_bytes):
            if field == 2 and wire == 2:        # subModules
                walk_module(val)
            elif field == 8 and wire == 2:      # attr map entry
                key, attr = None, None
                for f2, w2, v2 in proto.iter_fields(val):
                    if f2 == 1 and w2 == 2:
                        key = v2.decode()
                    elif f2 == 2 and w2 == 2:
                        attr = v2
                if key == "layer" and attr is not None:
                    dtype = None
                    for f3, w3, v3 in proto.iter_fields(attr):
                        if f3 == 1 and w3 == 0:
                            dtype = v3
                    found.append(dtype)

    walk_module(buf)
    assert found == [13], found


def test_padding_types_roundtrip():
    m = nn.Sequential(
        nn.SpatialZeroPadding(1, 2, 1, 0),
        nn.SpatialDilatedConvolution(2, 3, 3, 3, 1, 1, 1, 1, 2, 2),
        nn.Padding(1, 2, 3))
    m.reset(4)
    x = np.random.RandomState(2).rand(2, 2, 6, 6).astype(np.float32)
    _roundtrip(m, x)


def test_time_distributed_bn_running_stats():
    """BN wrapped in TimeDistributed: running stats ride the wrapped
    module inside the 'layer' attr and must still load (review r4)."""
    n = 3
    rmean = np.array([0.2, -0.4, 1.0], np.float32)
    rvar = np.array([1.5, 0.5, 2.0], np.float32)

    def tensor(arr):
        body = enc_int64(1, 2)
        for d in arr.shape:
            body += enc_int64(2, d)
        st = enc_int64(1, 2) + enc_bytes(2, arr.astype("<f4").tobytes())
        return body + enc_bytes(8, st)

    attr_tensor = lambda a: enc_int64(1, 10) + enc_bytes(10, tensor(a))

    bn = enc_string(1, "bn")
    bn += enc_string(7, "com.intel.analytics.bigdl.nn.BatchNormalization")
    bn += _mod_attr_entry("nOutput", _attr_i(n))
    bn += enc_int64(15, 1)
    bn += enc_bytes(16, tensor(np.ones(n, np.float32)))
    bn += enc_bytes(16, tensor(np.zeros(n, np.float32)))
    bn += _mod_attr_entry("runningMean", attr_tensor(rmean))
    bn += _mod_attr_entry("runningVar", attr_tensor(rvar))

    td = enc_string(1, "td")
    td += enc_string(7, "com.intel.analytics.bigdl.nn.TimeDistributed")
    td += _mod_attr_entry("layer", _attr_mod(bn))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "tdbn.bigdl")
        with open(p, "wb") as f:
            f.write(td)
        m = load_bigdl(p)
    m.evaluate()
    x = np.random.RandomState(9).rand(2, 4, n).astype(np.float32)
    got = np.asarray(m.forward(x))
    want = (x - rmean) / np.sqrt(rvar + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_multi_rnn_cell_stacked_read():
    """Recurrent(MultiRNNCell([LSTM, LSTM])): cells ride an ArrayValue
    of modules (MultiRNNCell.scala:205 'cells' attr)."""
    rng = np.random.RandomState(41)
    nin, h = 3, 3    # stacked: layer-2 input == layer-1 hidden

    def lstm_bytes(name, wp, bp, wh, isz):
        t = enc_string(1, name)
        t += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
        t += _mod_attr_entry("inputSize", _attr_i(isz))
        t += _mod_attr_entry("hiddenSize", _attr_i(h))
        t += _mod_attr_entry("p", _attr_d(0.0))
        t += _mod_attr_entry(
            "preTopology", _attr_mod(_linear_module(name + "_i", wp, bp)))
        t += enc_int64(15, 1)
        t += enc_bytes(16, _mod_tensor(wh))
        return t

    ws = []
    for isz in (nin, h):
        ws.append((rng.randn(4 * h, isz).astype(np.float32),
                   rng.randn(4 * h).astype(np.float32),
                   rng.randn(4 * h, h).astype(np.float32)))

    cells_arr = enc_int64(1, 2) + enc_int64(2, 16)   # size, datatype MODULE-ish
    cells_arr += enc_bytes(13, lstm_bytes("l1", *ws[0], nin))
    cells_arr += enc_bytes(13, lstm_bytes("l2", *ws[1], h))
    mrc = enc_string(1, "stack")
    mrc += enc_string(7, "com.intel.analytics.bigdl.nn.MultiRNNCell")
    mrc += _mod_attr_entry("cells", enc_int64(1, 15)
                           + enc_bytes(15, cells_arr))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("topology", _attr_mod(mrc))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "stack.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))

    def run(xs, wp, bp, wh):
        hs = np.zeros((B, h), np.float32)
        cs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            z = xs[:, t] @ wp.T + bp + hs @ wh.T
            i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
            cs = sig(i) * np.tanh(g) + sig(f) * cs
            hs = sig(o) * np.tanh(cs)
            out[:, t] = hs
        return out

    want = run(run(x, *ws[0]), *ws[1])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_decoder_read():
    """RecurrentDecoder(seqLength, LSTM) with includePreTopology: the
    cell's flat params duplicate the preTopology Linear — the loader
    must not confuse it with the hidden Linear (input == hidden size)."""
    rng = np.random.RandomState(42)
    h = 4
    wp = rng.randn(4 * h, h).astype(np.float32)   # input size == h!
    bp = rng.randn(4 * h).astype(np.float32)
    wh = rng.randn(4 * h, h).astype(np.float32)

    lstm = enc_string(1, "dcell")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(h))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("p", _attr_d(0.0))
    lstm += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2g", wp, bp)))
    lstm += enc_int64(15, 1)
    # includePreTopology=true flat order: [W_pre, b_pre, W_h2g]
    lstm += enc_bytes(16, _mod_tensor(wp))
    lstm += enc_bytes(16, _mod_tensor(bp))
    lstm += enc_bytes(16, _mod_tensor(wh))

    T_steps = 3
    dec = enc_string(1, "dec")
    dec += enc_string(7, "com.intel.analytics.bigdl.nn.RecurrentDecoder")
    dec += _mod_attr_entry("seqLength", _attr_i(T_steps))
    dec += _mod_attr_entry("topology", _attr_mod(lstm))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "dec.bigdl")
        with open(p, "wb") as f:
            f.write(dec)
        m = load_bigdl(p)

    B = 2
    x0 = rng.randn(B, h).astype(np.float32)
    got = np.asarray(m.forward(x0))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    cs = np.zeros((B, h), np.float32)
    cur = x0
    outs = []
    for _ in range(T_steps):
        z = cur @ wp.T + bp + hs @ wh.T
        i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        cs = sig(i) * np.tanh(g) + sig(f) * cs
        hs = sig(o) * np.tanh(cs)
        cur = hs
        outs.append(hs)
    want = np.stack(outs, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _bn1d_module(name, gamma, beta, rmean, rvar, eps=1e-5, momentum=0.1):
    """BatchNormalization leaf in wire form: gamma/beta as parameters,
    running stats as tensor attrs (nn/BatchNormalization.scala:346)."""
    n = gamma.shape[0]
    m = enc_string(1, name)
    m += enc_string(7, "com.intel.analytics.bigdl.nn.BatchNormalization")
    m += _mod_attr_entry("nOutput", _attr_i(n))
    m += _mod_attr_entry("eps", _attr_d(eps))
    m += _mod_attr_entry("momentum", _attr_d(momentum))
    m += _mod_attr_entry("affine", _attr_b(True))
    m += enc_int64(15, 1)
    m += enc_bytes(16, _mod_tensor(gamma))
    m += enc_bytes(16, _mod_tensor(beta))
    m += _mod_attr_entry(
        "runningMean", enc_int64(1, 10) + enc_bytes(10, _mod_tensor(rmean)))
    m += _mod_attr_entry(
        "runningVar", enc_int64(1, 10) + enc_bytes(10, _mod_tensor(rvar)))
    return m


def _td_module(name, inner_bytes):
    m = enc_string(1, name)
    m += enc_string(7, "com.intel.analytics.bigdl.nn.TimeDistributed")
    m += _mod_attr_entry("layer", _attr_mod(inner_bytes))
    m += _mod_attr_entry("maskZero", _attr_b(False))
    return m


def _seq_module(name, sub_bytes_list):
    m = enc_string(1, name)
    m += enc_string(7, "com.intel.analytics.bigdl.nn.Sequential")
    for sb in sub_bytes_list:
        m += enc_bytes(2, sb)
    return m


def _bnorm_recurrent_tree(name, cell_bytes, pre_linear_bytes, bn_bytes,
                          eps=1e-5, momentum=0.1):
    """Recurrent(batchNormParams) wire form (Recurrent.scala:111-119 +
    :776 doSerializeModule): bnorm flag + bnormEps/bnormMomentum attrs,
    topology cell, preTopology = Sequential[TimeDistributed(pre Linear),
    TimeDistributed(BN)]."""
    r = enc_string(1, name)
    r += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    r += _mod_attr_entry("bnorm", _attr_b(True))
    r += _mod_attr_entry("bnormEps", _attr_d(eps))
    r += _mod_attr_entry("bnormMomentum", _attr_d(momentum))
    r += _mod_attr_entry("bnormAffine", _attr_b(True))
    r += _mod_attr_entry("topology", _attr_mod(cell_bytes))
    r += _mod_attr_entry("preTopology", _attr_mod(_seq_module(
        name + "_pre",
        [_td_module(name + "_td0", pre_linear_bytes),
         _td_module(name + "_td1", bn_bytes)])))
    return r


def test_recurrent_lstm_bnorm_read():
    """Recurrent(LSTM, BatchNormParams) loads: the preTopology Linear's
    output is batch-normalized over (batch, time) BEFORE the recurrence
    (Recurrent.scala:111-119); BN gamma/beta/stats are in the
    REFERENCE's [i, g, f, o] gate order and must ride the same
    permutation as the projection weights.  Was an honest raise
    through r4."""
    rng = np.random.RandomState(31)
    nin, h = 3, 4
    w_pre = rng.randn(4 * h, nin).astype(np.float32)
    b_pre = rng.randn(4 * h).astype(np.float32)
    w_h2g = rng.randn(4 * h, h).astype(np.float32)
    gamma = (1.0 + 0.1 * rng.randn(4 * h)).astype(np.float32)
    beta = rng.randn(4 * h).astype(np.float32)
    rmean = rng.randn(4 * h).astype(np.float32)
    rvar = (0.5 + rng.rand(4 * h)).astype(np.float32)
    eps = 1e-5

    lstm = enc_string(1, "lstm1")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(nin))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("p", _attr_d(0.0))
    lstm += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    lstm += enc_int64(15, 1)
    lstm += enc_bytes(16, _mod_tensor(w_h2g))

    rec = _bnorm_recurrent_tree(
        "rec", lstm, _linear_module("i2g", w_pre, b_pre),
        _bn1d_module("bn", gamma, beta, rmean, rvar, eps=eps))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)
    m.evaluate()

    B, T = 2, 5
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    # numpy reference entirely in the REFERENCE's [i, g, f, o] order
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((B, h), np.float32)
    cs = np.zeros((B, h), np.float32)
    want = np.zeros((B, T, h), np.float32)
    for t in range(T):
        pre = x[:, t] @ w_pre.T + b_pre
        u = gamma * (pre - rmean) / np.sqrt(rvar + eps) + beta
        z = u + hs @ w_h2g.T
        i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        cs = sig(i) * np.tanh(g) + sig(f) * cs
        hs = sig(o) * np.tanh(cs)
        want[:, t] = hs
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # the loaded model must also TRAIN: one grad through the bn path
    import jax
    import jax.numpy as jnp
    params, state = m._params, m._state

    def loss(p):
        y, _ = m.run(p, x, state=state, training=True,
                     rng=jax.random.PRNGKey(0))
        return jnp.sum(y * y)

    g = jax.grad(loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(g))


def test_birecurrent_gru_bnorm_read():
    """BiRecurrent(GRU, BatchNormParams): EACH direction carries its own
    BatchNorm instance (BiRecurrent.scala:45-46) — distinct gamma/beta/
    stats per direction; GRU projection order [r, z, n] needs no
    permutation."""
    rng = np.random.RandomState(32)
    nin, h = 4, 3
    eps = 1e-5

    def gru_tree(name, wp, bp, wh2g, wnew):
        t = enc_string(1, name)
        t += enc_string(7, "com.intel.analytics.bigdl.nn.GRU")
        t += _mod_attr_entry("inputSize", _attr_i(nin))
        t += _mod_attr_entry("outputSize", _attr_i(h))
        t += _mod_attr_entry("p", _attr_d(0.0))
        t += _mod_attr_entry(
            "preTopology", _attr_mod(_linear_module(name + "_i2g", wp, bp)))
        t += enc_int64(15, 1)
        t += enc_bytes(16, _mod_tensor(wh2g))
        t += enc_bytes(16, _mod_tensor(wnew))
        return t

    dirs = {}
    for tag in ("f", "b"):
        dirs[tag] = dict(
            wp=rng.randn(3 * h, nin).astype(np.float32),
            bp=rng.randn(3 * h).astype(np.float32),
            wh2g=rng.randn(2 * h, h).astype(np.float32),
            wnew=rng.randn(h, h).astype(np.float32),
            gamma=(1.0 + 0.1 * rng.randn(3 * h)).astype(np.float32),
            beta=rng.randn(3 * h).astype(np.float32),
            rmean=rng.randn(3 * h).astype(np.float32),
            rvar=(0.5 + rng.rand(3 * h)).astype(np.float32))

    f, b = dirs["f"], dirs["b"]
    fwd = _bnorm_recurrent_tree(
        "rec_f", gru_tree("gru_f", f["wp"], f["bp"], f["wh2g"], f["wnew"]),
        _linear_module("gru_f_i2g", f["wp"], f["bp"]),
        _bn1d_module("bn_f", f["gamma"], f["beta"], f["rmean"], f["rvar"],
                     eps=eps))
    rev = _bnorm_recurrent_tree(
        "rec_b", gru_tree("gru_b", b["wp"], b["bp"], b["wh2g"], b["wnew"]),
        _linear_module("gru_b_i2g", b["wp"], b["bp"]),
        _bn1d_module("bn_b", b["gamma"], b["beta"], b["rmean"], b["rvar"],
                     eps=eps))

    bi = enc_string(1, "bi")
    bi += enc_string(7, "com.intel.analytics.bigdl.nn.BiRecurrent")
    bi += _mod_attr_entry("bnorm", _attr_b(True))
    bi += _mod_attr_entry("bnormEps", _attr_d(eps))
    bi += _mod_attr_entry("bnormMomentum", _attr_d(0.1))
    bi += _mod_attr_entry("birnn", _attr_mod(_birnn_bytes(fwd, rev)))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bi.bigdl")
        with open(p, "wb") as f2:
            f2.write(bi)
        m = load_bigdl(p)
    m.evaluate()

    B, T = 2, 4
    x = rng.randn(B, T, nin).astype(np.float32)
    got = np.asarray(m.forward(x))

    sig = lambda v: 1.0 / (1.0 + np.exp(-v))

    def run_gru(xs, dd):
        hs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            pre = xs[:, t] @ dd["wp"].T + dd["bp"]
            u = dd["gamma"] * (pre - dd["rmean"]) / np.sqrt(
                dd["rvar"] + eps) + dd["beta"]
            rz = u[:, :2*h] + hs @ dd["wh2g"].T
            r, z = sig(rz[:, :h]), sig(rz[:, h:])
            hhat = np.tanh(u[:, 2*h:] + (r * hs) @ dd["wnew"].T)
            hs = (1.0 - z) * hhat + z * hs
            out[:, t] = hs
        return out

    yf = run_gru(x, f)
    yb = run_gru(x[:, ::-1], b)[:, ::-1]
    np.testing.assert_allclose(got, yf + yb, rtol=1e-4, atol=1e-5)


def test_recurrent_mask_zero_read():
    """A maskZero attr on a Recurrent node enables padded-row masking
    (Recurrent.scala:39-49 semantics).  NOTE: the reference's own
    serializer never writes this attr (Recurrent.scala doSerializeModule
    writes only topology/preTopology/bnorm*), so reference-saved files
    lose the flag even reference-to-reference; this covers the
    forward-compat read + our own masking numerics.  The
    TimeDistributed flag below IS reference wire format."""
    rng = np.random.RandomState(33)
    nin, h = 3, 4
    w_pre = rng.randn(4 * h, nin).astype(np.float32)
    b_pre = rng.randn(4 * h).astype(np.float32)
    w_h2g = rng.randn(4 * h, h).astype(np.float32)

    lstm = enc_string(1, "lstm1")
    lstm += enc_string(7, "com.intel.analytics.bigdl.nn.LSTM")
    lstm += _mod_attr_entry("inputSize", _attr_i(nin))
    lstm += _mod_attr_entry("hiddenSize", _attr_i(h))
    lstm += _mod_attr_entry("p", _attr_d(0.0))
    lstm += _mod_attr_entry("preTopology",
                            _attr_mod(_linear_module("i2g", w_pre, b_pre)))
    lstm += enc_int64(15, 1)
    lstm += enc_bytes(16, _mod_tensor(w_h2g))

    rec = enc_string(1, "rec")
    rec += enc_string(7, "com.intel.analytics.bigdl.nn.Recurrent")
    rec += _mod_attr_entry("maskZero", _attr_b(True))
    rec += _mod_attr_entry("topology", _attr_mod(lstm))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "rec.bigdl")
        with open(p, "wb") as f:
            f.write(rec)
        m = load_bigdl(p)
    assert m.mask_zero is True

    B, T = 2, 5
    x = rng.randn(B, T, nin).astype(np.float32)
    x[1, 3:] = 0.0  # sample 1 padded to length 3
    got = np.asarray(m.forward(x))
    assert np.all(got[1, 3:] == 0)
    # the unpadded sample matches the plain numpy recurrence
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    hs = np.zeros((1, h), np.float32)
    cs = np.zeros((1, h), np.float32)
    for t in range(T):
        z = x[:1, t] @ w_pre.T + b_pre + hs @ w_h2g.T
        i, g, f, o = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        cs = sig(i) * np.tanh(g) + sig(f) * cs
        hs = sig(o) * np.tanh(cs)
        np.testing.assert_allclose(got[0, t], hs[0], rtol=1e-4, atol=1e-5)


def test_time_distributed_mask_zero_read():
    rng = np.random.RandomState(34)
    w = rng.randn(4, 3).astype(np.float32)
    b = rng.randn(4).astype(np.float32)
    td = enc_string(1, "td")
    td += enc_string(7, "com.intel.analytics.bigdl.nn.TimeDistributed")
    td += _mod_attr_entry("layer", _attr_mod(_linear_module("fc", w, b)))
    td += _mod_attr_entry("maskZero", _attr_b(True))

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "td.bigdl")
        with open(p, "wb") as f:
            f.write(td)
        m = load_bigdl(p)
    assert m.mask_zero is True
    x = rng.randn(2, 3, 3).astype(np.float32)
    x[0, 1] = 0.0
    got = np.asarray(m.forward(x))
    want = x @ w.T + b
    want[0, 1] = 0.0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_birecurrent_bnorm_split_input_custom_activation_compose():
    """The three r5 reader features in ONE fixture: per-direction
    BatchNormParams + GRU(activation=Sigmoid) + isSplitInput — exact
    numerics vs an independent numpy recurrence."""
    rng = np.random.RandomState(40)
    nin, h = 4, 3
    eps = 1e-5

    def gru_tree(name, wp, bp, wh2g, wnew):
        t = enc_string(1, name)
        t += enc_string(7, "com.intel.analytics.bigdl.nn.GRU")
        t += _mod_attr_entry("inputSize", _attr_i(nin))
        t += _mod_attr_entry("outputSize", _attr_i(h))
        t += _mod_attr_entry("p", _attr_d(0.0))
        act = enc_string(1, name + "_act")
        act += enc_string(7, "com.intel.analytics.bigdl.nn.Sigmoid")
        t += _mod_attr_entry("activation", _attr_mod(act))
        t += _mod_attr_entry("preTopology", _attr_mod(
            _linear_module(name + "_i2g", wp, bp)))
        t += enc_int64(15, 1)
        t += enc_bytes(16, _mod_tensor(wh2g))
        t += enc_bytes(16, _mod_tensor(wnew))
        return t

    d = {}
    for tag in ("f", "b"):
        d[tag] = dict(
            wp=rng.randn(3 * h, nin).astype(np.float32),
            bp=rng.randn(3 * h).astype(np.float32),
            wh2g=rng.randn(2 * h, h).astype(np.float32),
            wnew=rng.randn(h, h).astype(np.float32),
            gamma=(1.0 + 0.1 * rng.randn(3 * h)).astype(np.float32),
            beta=rng.randn(3 * h).astype(np.float32),
            rmean=rng.randn(3 * h).astype(np.float32),
            rvar=(0.5 + rng.rand(3 * h)).astype(np.float32))

    def rec_tree(name, tag):
        dd = d[tag]
        return _bnorm_recurrent_tree(
            name, gru_tree(f"gru_{tag}", dd["wp"], dd["bp"], dd["wh2g"],
                           dd["wnew"]),
            _linear_module(f"gru_{tag}_i2g", dd["wp"], dd["bp"]),
            _bn1d_module(f"bn_{tag}", dd["gamma"], dd["beta"],
                         dd["rmean"], dd["rvar"], eps=eps))

    bi = enc_string(1, "bi")
    bi += enc_string(7, "com.intel.analytics.bigdl.nn.BiRecurrent")
    bi += _mod_attr_entry("bnorm", _attr_b(True))
    bi += _mod_attr_entry("bnormEps", _attr_d(eps))
    bi += _mod_attr_entry("isSplitInput", _attr_b(True))
    bi += _mod_attr_entry("birnn", _attr_mod(_birnn_bytes(
        rec_tree("rec_f", "f"), rec_tree("rec_b", "b"),
        "BifurcateSplitTable")))

    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "bi.bigdl")
        with open(p, "wb") as f2:
            f2.write(bi)
        m = load_bigdl(p)
    m.evaluate()

    B, T = 2, 4
    x = rng.randn(B, T, 2 * nin).astype(np.float32)
    got = np.asarray(m.forward(x))
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))

    def run(xs, dd):
        hs = np.zeros((B, h), np.float32)
        out = np.zeros((B, xs.shape[1], h), np.float32)
        for t in range(xs.shape[1]):
            pre = xs[:, t] @ dd["wp"].T + dd["bp"]
            u = dd["gamma"] * (pre - dd["rmean"]) / np.sqrt(
                dd["rvar"] + eps) + dd["beta"]
            rz = u[:, :2*h] + hs @ dd["wh2g"].T
            r, z = sig(rz[:, :h]), sig(rz[:, h:])
            hhat = sig(u[:, 2*h:] + (r * hs) @ dd["wnew"].T)  # Sigmoid cand
            hs = (1.0 - z) * hhat + z * hs
            out[:, t] = hs
        return out

    yf = run(x[..., :nin], d["f"])
    yb = run(x[..., nin:][:, ::-1], d["b"])[:, ::-1]
    np.testing.assert_allclose(got, yf + yb, rtol=1e-4, atol=1e-5)
