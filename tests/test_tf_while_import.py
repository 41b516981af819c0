"""TF v1 while-loop import: Enter/Merge/LoopCond/
Switch/NextIteration/Exit frames lower to ONE lax.while_loop
(≙ nn/tf/ControlOps.scala:182-229 + nn/FrameManager.scala:31, which
interpret the same frames at runtime).

Two fixture sources: a hand-encoded counter graph (independent of any
TF install) and graphs emitted by the REAL tensorflow with control-flow
v2 disabled (the exact wire format the reference consumes)."""
import numpy as np
import pytest

from bigdl_tpu.utils import proto
from bigdl_tpu.utils.tf_import import load_tf_graph, _node, _enc_tensor
from bigdl_tpu.utils.proto import enc_bytes, enc_string


def _const(name, arr):
    arr = np.asarray(arr)
    dt = 1 if arr.dtype == np.float32 else 3
    return _node(name, "Const",
                 attrs={"dtype": proto.enc_int64(6, dt),
                        "value": enc_bytes(8, _enc_tensor(arr))})


def _str_attr(s):
    return enc_string(2, s)


def test_hand_encoded_counter_loop():
    """while (i < 10) { i += 1; s += i }  from raw frame nodes."""
    g = b""
    g += _const("i0", np.asarray(0, np.int32))
    g += _const("s0", np.asarray(0, np.int32))
    g += _const("limit", np.asarray(10, np.int32))
    g += _const("one", np.asarray(1, np.int32))
    g += _node("enter_i", "Enter", ["i0"], {"frame_name": _str_attr("w")})
    g += _node("enter_s", "Enter", ["s0"], {"frame_name": _str_attr("w")})
    g += _node("merge_i", "Merge", ["enter_i", "next_i"])
    g += _node("merge_s", "Merge", ["enter_s", "next_s"])
    g += _node("less", "Less", ["merge_i", "limit"])
    g += _node("cond", "LoopCond", ["less"])
    g += _node("switch_i", "Switch", ["merge_i", "cond"])
    g += _node("switch_s", "Switch", ["merge_s", "cond"])
    g += _node("body_i", "AddV2", ["switch_i:1", "one"])
    g += _node("body_s", "AddV2", ["switch_s:1", "body_i"])
    g += _node("next_i", "NextIteration", ["body_i"])
    g += _node("next_s", "NextIteration", ["body_s"])
    g += _node("exit_i", "Exit", ["switch_i"])
    g += _node("exit_s", "Exit", ["switch_s"])

    m = load_tf_graph(g, [], ["exit_i", "exit_s"])
    i_out, s_out = m.forward([])
    assert int(i_out) == 10
    assert int(s_out) == sum(range(1, 11))   # 55


def _tf1_graphdef(build):
    """Build a graph with v1 frame-based control flow WITHOUT leaking
    global TF state into other tests (disable_control_flow_v2 is global
    and would change how tf_keras builds LSTMs later in this process)."""
    tf = pytest.importorskip("tensorflow")
    tf1 = tf.compat.v1
    tf1.disable_control_flow_v2()
    try:
        g = tf1.Graph()
        with g.as_default():     # graph mode for this block, eager stays on
            build(tf, tf1)
        return g.as_graph_def().SerializeToString()
    finally:
        tf1.enable_control_flow_v2()


def test_tf_counter_while_loop():
    """tf.compat.v1.while_loop counter: the genuine TF frame layout."""
    def build(tf, tf1):
        i0 = tf1.constant(0, name="i0")
        a0 = tf1.constant(1.0, name="a0")
        _, a = tf1.while_loop(
            lambda i, a: tf.less(i, 7),
            lambda i, a: (tf.add(i, 1), tf.multiply(a, 2.0)),
            [i0, a0], name="loop")
        tf1.identity(a, name="out")

    m = load_tf_graph(_tf1_graphdef(build), [], ["out"])
    assert float(m.forward([])) == 128.0     # 2**7


def test_tf_rnn_style_while_loop():
    """Loop-form RNN: h_{t+1} = tanh(h W + b), T steps, with the input
    captured as a loop-invariant Enter — numerics vs numpy."""
    rng = np.random.RandomState(5)
    w = rng.randn(4, 4).astype(np.float32) * 0.5
    b = rng.randn(4).astype(np.float32) * 0.1
    x0 = rng.randn(2, 4).astype(np.float32)
    T = 6

    def build(tf, tf1):
        x = tf1.placeholder(tf.float32, shape=(2, 4), name="x")
        wc = tf1.constant(w, name="w")
        bc = tf1.constant(b, name="b")
        t0 = tf1.constant(0, name="t0")

        def cond(t, h):
            return tf.less(t, T)

        def body(t, h):
            return tf.add(t, 1), tf.tanh(tf.matmul(h, wc) + bc)

        _, h = tf1.while_loop(cond, body, [t0, x], name="rnn")
        tf1.identity(h, name="out")

    m = load_tf_graph(_tf1_graphdef(build), ["x"], ["out"])
    got = np.asarray(m.forward(x0))
    want = x0
    for _ in range(T):
        want = np.tanh(want @ w + b)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_while_loop_under_jit():
    """The lowered loop must trace under jit (the whole point of the
    lax.while_loop lowering: no per-iteration host dispatch)."""
    import jax

    rng = np.random.RandomState(6)
    w = rng.randn(3, 3).astype(np.float32) * 0.4
    x0 = rng.randn(2, 3).astype(np.float32)

    def build(tf, tf1):
        x = tf1.placeholder(tf.float32, shape=(2, 3), name="x")
        wc = tf1.constant(w, name="w")
        t0 = tf1.constant(0, name="t0")
        _, h = tf1.while_loop(
            lambda t, h: tf.less(t, 4),
            lambda t, h: (tf.add(t, 1), tf.nn.relu(tf.matmul(h, wc))),
            [t0, x], name="jl")
        tf1.identity(h, name="out")

    m = load_tf_graph(_tf1_graphdef(build), ["x"], ["out"])
    params, state = m.init_params(0)

    from bigdl_tpu.nn.module import Ctx
    f = jax.jit(lambda p, a: m.apply(p, a, Ctx(state=state, training=False)))
    got = np.asarray(f(params, x0))
    want = x0
    for _ in range(4):
        want = np.maximum(want @ w, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_malformed_frame_rejected():
    """A frame with no LoopCond (degenerate Enter chain) is an honest
    raise, not a wrong answer."""
    g = b""
    g += _const("i0", np.asarray(0, np.int32))
    g += _node("enter_a", "Enter", ["i0"], {"frame_name": _str_attr("outer")})
    g += _node("enter_b", "Enter", ["enter_a"],
               {"frame_name": _str_attr("inner")})
    g += _node("exit_b", "Exit", ["enter_b"])
    with pytest.raises(NotImplementedError, match="LoopCond"):
        load_tf_graph(g, [], ["exit_b"])


def test_nested_while_loops():
    """tf.while_loop INSIDE tf.while_loop (seq2seq-decoder shape):
    frames rewrite innermost-first (≙ FrameManager.createFrame
    parentFrame nesting, nn/FrameManager.scala:40,115-120); numerics
    vs real TF."""
    def build(tf, tf1):
        i0 = tf1.constant(0, name="i0")
        s0 = tf1.constant(0.0, name="s0")

        def outer_body(i, s):
            # inner loop: adds (i+1) * 3 to s via 3 increments of 1.0*(i+1)
            def inner_body(j, t):
                return tf.add(j, 1), tf.add(t, tf.cast(i + 1, tf.float32))

            _, t = tf1.while_loop(
                lambda j, t: tf.less(j, 3), inner_body,
                [tf1.constant(0), s], name="inner")
            return tf.add(i, 1), t

        _, s = tf1.while_loop(
            lambda i, s: tf.less(i, 4), outer_body, [i0, s0], name="outer")
        tf1.identity(s, name="out")

    m = load_tf_graph(_tf1_graphdef(build), [], ["out"])
    # sum_{i=1..4} 3*i = 30
    assert float(m.forward([])) == 30.0


def test_cond_inside_while_body():
    """tf.cond inside a while body: the non-LoopCond Switch/Merge pair
    lowers to a predicate select (≙ the reference interpreting
    Switch/Merge freely inside frames, nn/tf/ControlOps.scala);
    numerics vs a python re-simulation."""
    def build(tf, tf1):
        x = tf1.placeholder(tf.float32, shape=(), name="x")
        i0 = tf1.constant(0, name="i0")

        def body(i, v):
            v2 = tf1.cond(tf.less(v, 10.0),
                          lambda: v * 3.0,
                          lambda: v - 5.0)
            return tf.add(i, 1), v2

        _, v = tf1.while_loop(
            lambda i, v: tf.less(i, 6), body, [i0, x], name="cw")
        tf1.identity(v, name="out")

    m = load_tf_graph(_tf1_graphdef(build), ["x"], ["out"])
    for x0 in (1.0, 7.0, 40.0):
        want = x0
        for _ in range(6):
            want = want * 3.0 if want < 10.0 else want - 5.0
        got = float(m.forward(np.float32(x0)))
        assert got == want, (x0, got, want)


def test_imported_loop_trains():
    """while_max_iters=N lowers the imported loop to the bounded scan:
    gradients flow through the imported graph and one SGD step reduces
    the loss (≙ utils/tf/Session.scala:634 training over
    DynamicGraph.generateBackward)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import Ctx

    rng = np.random.RandomState(8)
    w = rng.randn(3, 3).astype(np.float32) * 0.4
    x0 = rng.randn(2, 3).astype(np.float32)
    T = 4

    def build(tf, tf1):
        x = tf1.placeholder(tf.float32, shape=(2, 3), name="x")
        wc = tf1.constant(w, name="w")
        t0 = tf1.constant(0, name="t0")
        _, h = tf1.while_loop(
            lambda t, h: tf.less(t, T),
            lambda t, h: (tf.add(t, 1), tf.tanh(tf.matmul(h, wc))),
            [t0, x], name="tl")
        tf1.identity(h, name="out")

    m = load_tf_graph(_tf1_graphdef(build), ["x"], ["out"],
                      while_max_iters=8)
    params, state = m.init_params(0)

    # forward parity with the unbounded lowering first
    want = x0
    for _ in range(T):
        want = np.tanh(want @ w)
    got = np.asarray(m.apply(params, x0, Ctx(state=state)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # gradient wrt the INPUT flows through the scan (imported consts are
    # graph weights; train the input embedding as the reference Session
    # trains placeholders-fed activations)
    def loss(a):
        out = m.apply(params, a, Ctx(state=state))
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(jnp.asarray(x0))
    assert np.isfinite(np.asarray(g)).all()
    assert np.abs(np.asarray(g)).sum() > 0
    # one gradient step reduces the loss
    l0 = float(loss(jnp.asarray(x0)))
    l1 = float(loss(jnp.asarray(x0) - 0.05 * g))
    assert l1 < l0


def test_strided_slice_ellipsis_new_axis_masks():
    """x[1, ..., tf.newaxis, ::2] — ellipsis + new_axis + shrink masks
    against real TF numerics."""
    tf = pytest.importorskip("tensorflow")
    x0 = np.arange(2 * 3 * 4 * 6, dtype=np.float32).reshape(2, 3, 4, 6)

    @tf.function
    def f(x):
        return x[1, ..., tf.newaxis, ::2]

    cf = f.get_concrete_function(tf.TensorSpec((2, 3, 4, 6), tf.float32))
    gd = cf.graph.as_graph_def().SerializeToString()
    want = np.asarray(f(tf.constant(x0)))

    ph = [n.name for n in cf.graph.as_graph_def().node
          if n.op == "Placeholder"][0]
    out = [n.name for n in cf.graph.as_graph_def().node
           if n.op == "Identity"][-1]
    m = load_tf_graph(gd, [ph], [out])
    got = np.asarray(m.forward(x0))
    assert got.shape == want.shape == (3, 4, 1, 3)
    np.testing.assert_allclose(got, want)


def test_strided_slice_newaxis_leading():
    tf = pytest.importorskip("tensorflow")
    x0 = np.arange(12, dtype=np.float32).reshape(3, 4)

    @tf.function
    def f(x):
        return x[tf.newaxis, :, 2]

    cf = f.get_concrete_function(tf.TensorSpec((3, 4), tf.float32))
    gd = cf.graph.as_graph_def().SerializeToString()
    want = np.asarray(f(tf.constant(x0)))
    ph = [n.name for n in cf.graph.as_graph_def().node
          if n.op == "Placeholder"][0]
    out = [n.name for n in cf.graph.as_graph_def().node
           if n.op == "Identity"][-1]
    m = load_tf_graph(gd, [ph], [out])
    got = np.asarray(m.forward(x0))
    assert got.shape == want.shape == (1, 3)
    np.testing.assert_allclose(got, want)


def test_topk_and_fused_bn_side_outputs():
    """Multi-output slots beyond Split/Unpack/Switch: TopKV2
    values+indices, FusedBatchNorm batch_mean slot."""
    tf = pytest.importorskip("tensorflow")
    x0 = np.random.RandomState(3).rand(2, 8).astype(np.float32)

    @tf.function
    def f(x):
        vals, idx = tf.math.top_k(x, k=3)
        return vals * 2.0, idx

    cf = f.get_concrete_function(tf.TensorSpec((2, 8), tf.float32))
    gd = cf.graph.as_graph_def().SerializeToString()
    ph = [n.name for n in cf.graph.as_graph_def().node
          if n.op == "Placeholder"][0]
    outs = [n.name for n in cf.graph.as_graph_def().node
            if n.op == "Identity"][-2:]
    m = load_tf_graph(gd, [ph], outs)
    got_v, got_i = m.forward(x0)
    want_v, want_i = [np.asarray(t) for t in f(tf.constant(x0))]
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_fused_bn_side_output_slots():
    """FusedBatchNormV3 side outputs (:1/:2 = frozen moving stats in the
    inference form) must resolve; is_training graphs are rejected."""
    from bigdl_tpu.utils.tf_import import _node, _enc_tensor

    n = 4
    x0 = np.random.RandomState(4).rand(2, 3, 3, n).astype(np.float32)
    scale = np.random.RandomState(5).rand(n).astype(np.float32) + 0.5
    offset = np.zeros(n, np.float32)
    mean = np.random.RandomState(6).rand(n).astype(np.float32)
    var = np.random.RandomState(7).rand(n).astype(np.float32) + 0.5

    g = b""
    g += _node("x", "Placeholder", attrs={"dtype": proto.enc_int64(6, 1)})
    for nm, arr in (("scale", scale), ("offset", offset),
                    ("mean", mean), ("var", var)):
        g += _const(nm, arr)
    g += _node("bn", "FusedBatchNormV3",
               ["x", "scale", "offset", "mean", "var"],
               {"epsilon": proto.enc_float(4, 1e-3)})
    g += _node("use_mean", "AddV2", ["bn:1", "bn:2"])
    m = load_tf_graph(g, ["x"], ["bn", "use_mean"])
    y, mv = m.forward(x0)
    want = (x0 - mean) / np.sqrt(var + 1e-3) * scale + offset
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mv), mean + var, rtol=1e-6)
