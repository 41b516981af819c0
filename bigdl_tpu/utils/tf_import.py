"""TensorFlow GraphDef import/export subset (≙ utils/tf/TensorflowLoader.scala,
TensorflowSaver.scala, Tensorflow.scala, TFUtils.scala).

The reference parses a frozen GraphDef protobuf and pattern-matches node
clusters into BigDL layers.  Here the GraphDef is parsed with the in-house
wire decoder (utils.proto) and imported as a `TFGraph` Module that
evaluates nodes topologically with jnp ops — under jit XLA fuses the whole
imported graph, so there is no interpreter overhead per step.

Supported import ops (≙ the high-frequency subset of the reference's 159
utils/tf/loaders/): Const, Placeholder, Identity, MatMul, BatchMatMul(V2),
Add(V2), BiasAdd, Sub, Mul, RealDiv, Maximum, Minimum, Relu, Relu6, Elu,
LeakyRelu, Softplus, Sigmoid, Tanh, Softmax, LogSoftmax, Reshape, Squeeze,
ExpandDims, ConcatV2, Mean, Sum, Max, Min, Prod, Pad(V2), MirrorPad,
Transpose, Conv2D, DepthwiseConv2dNative, Conv2DBackpropInput (deconv),
MaxPool, AvgPool, FusedBatchNorm(+V2/V3), Fill, Pack/Unpack, Split(V),
Slice, StridedSlice, Tile, Gather(V2), TopK(V2), Range, Shape, Rank, Size, Cast,
StopGradient, Neg, Exp, Log, Sqrt, Rsqrt, Square, SquaredDifference, Abs,
Floor, Ceil, Round, Pow, FloorDiv, FloorMod, ArgMax, ArgMin, ZerosLike,
OnesLike, comparisons (Greater/Less/Equal/...), logical ops, Select(V2),
and constant-folded Switch/Merge control flow with dead-branch pruning
(an untaken is_training branch may contain unsupported ops).
Attention-era graphs are out of scope (use the native model zoo instead).

`save_tf_graph` exports Sequential models built from Linear /
activations / Reshape / View / SpatialConvolution / max+avg pooling /
BatchNormalization (inference-folded) back to a frozen GraphDef that
this importer (and TensorFlow) can read; NCHW conv stacks are bracketed
by a single NHWC transpose pair and explicit pads lower to Pad /
PadV2(-inf) nodes (round-trip tested in tests/test_tf_interop.py).
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import proto
from .proto import iter_fields, enc_bytes, enc_string, _varint, _key
from ..nn.module import Module

# TF DataType enum subset
_DT = {1: np.float32, 2: np.float64, 3: np.int32, 4: np.uint8, 5: np.int16,
       6: np.int8, 7: object, 9: np.int64, 10: np.bool_}
_DT_REV = {np.dtype(np.float32): 1, np.dtype(np.float64): 2,
           np.dtype(np.int32): 3, np.dtype(np.int64): 9,
           np.dtype(np.bool_): 10}


@dataclass
class NodeDef:
    name: str
    op: str
    inputs: List[str] = field(default_factory=list)
    attrs: Dict[str, object] = field(default_factory=dict)


def _decode_shape(buf: bytes) -> Tuple[int, ...]:
    dims = []
    for f, w, v in iter_fields(buf):
        if f == 2 and w == 2:  # dim
            for f2, w2, v2 in iter_fields(v):
                if f2 == 1 and w2 == 0:
                    # zig-zag-free int64; -1 encodes as huge varint
                    size = v2 if v2 < 1 << 62 else v2 - (1 << 64)
                    dims.append(size)
    return tuple(dims)


def _decode_tensor(buf: bytes) -> np.ndarray:
    dtype = np.float32
    shape: Tuple[int, ...] = ()
    content = None
    floats: List[float] = []
    ints: List[int] = []
    for f, w, v in iter_fields(buf):
        if f == 1 and w == 0:
            dtype = _DT.get(v, np.float32)
        elif f == 2 and w == 2:
            shape = _decode_shape(v)
        elif f == 4 and w == 2:
            content = v
        elif f == 5:  # float_val (packed or single)
            if w == 2:
                floats.extend(struct.unpack(f"<{len(v) // 4}f", v))
            else:
                floats.append(v)
        elif f in (7, 10):  # int_val / int64_val
            if w == 2:
                i = 0
                while i < len(v):
                    n, i = proto._read_varint(v, i)
                    ints.append(n)
            else:
                ints.append(v)
    if content is not None:
        arr = np.frombuffer(content, dtype=dtype)
    elif floats:
        arr = np.asarray(floats, dtype)
        if arr.size == 1 and shape and int(np.prod(shape)) > 1:
            arr = np.full(shape, arr[0], dtype)
    elif ints:
        arr = np.asarray(ints, dtype)
        if arr.size == 1 and shape and int(np.prod(shape)) > 1:
            arr = np.full(shape, arr[0], dtype)
    else:
        arr = np.zeros(shape, dtype)
    return arr.reshape(shape) if shape else arr.reshape(())


def _decode_attr(buf: bytes):
    for f, w, v in iter_fields(buf):
        if f == 2 and w == 2:
            return v.decode("utf-8", "replace")  # s
        if f == 3 and w == 0:
            return v if v < 1 << 62 else v - (1 << 64)  # i
        if f == 4 and w == 5:
            return v  # f
        if f == 5 and w == 0:
            return bool(v)  # b
        if f == 6 and w == 0:
            return ("dtype", v)  # type enum
        if f == 7 and w == 2:
            return _decode_shape(v)  # shape
        if f == 8 and w == 2:
            return _decode_tensor(v)  # tensor
        if f == 1 and w == 2:  # list (AttrValue.ListValue)
            out = []
            for f2, w2, v2 in iter_fields(v):
                if f2 == 3:              # i (packed by proto3, or single)
                    if w2 == 2:
                        i = 0
                        while i < len(v2):
                            n, i = proto._read_varint(v2, i)
                            out.append(n)
                    else:
                        out.append(v2)
                elif f2 == 4:            # f (packed fixed32 or single)
                    if w2 == 2:
                        out.extend(np.frombuffer(v2, "<f4").tolist())
                    else:
                        out.append(v2)
                elif f2 == 2 and w2 == 2:  # s
                    out.append(v2.decode("utf-8", "replace"))
            return out
    return None


def parse_graphdef(data: bytes) -> List[NodeDef]:
    nodes = []
    for f, w, v in iter_fields(data):
        if f == 1 and w == 2:  # node
            node = NodeDef("", "")
            for f2, w2, v2 in iter_fields(v):
                if f2 == 1 and w2 == 2:
                    node.name = v2.decode("utf-8")
                elif f2 == 2 and w2 == 2:
                    node.op = v2.decode("utf-8")
                elif f2 == 3 and w2 == 2:
                    node.inputs.append(v2.decode("utf-8"))
                elif f2 == 5 and w2 == 2:  # attr map entry
                    key = None
                    val = None
                    for f3, w3, v3 in iter_fields(v2):
                        if f3 == 1 and w3 == 2:
                            key = v3.decode("utf-8")
                        elif f3 == 2 and w3 == 2:
                            val = _decode_attr(v3)
                    if key is not None:
                        node.attrs[key] = val
            nodes.append(node)
    return nodes


# --------------------------------------------------------------------- #
# op implementations (jnp; NHWC like TF)                                #
# --------------------------------------------------------------------- #
def _conv2d(x, w, strides, padding, feature_group_count=1):
    # TF: x NHWC, w HWIO
    sh, sw = int(strides[1]), int(strides[2])
    return lax.conv_general_dilated(
        x, w, (sh, sw), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count)


def _pool(x, ksize, strides, padding, reducer, init):
    kh, kw = int(ksize[1]), int(ksize[2])
    sh, sw = int(strides[1]), int(strides[2])
    return lax.reduce_window(x, init, reducer, (1, kh, kw, 1),
                             (1, sh, sw, 1), padding)


def _avg_pool(x, ksize, strides, padding):
    """TF AvgPool: with SAME padding the average divides by the number of
    IN-BOUNDS window elements at each position, not the full kernel area."""
    summed = _pool(x, ksize, strides, padding, lax.add, 0.0)
    if str(padding).upper() == "SAME":
        # counts depend only on the spatial shape: one (1, H, W, 1) pass
        ones = jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype)
        counts = _pool(ones, ksize, strides, padding, lax.add, 0.0)
        return summed / counts
    return summed / (int(ksize[1]) * int(ksize[2]))


def _fused_bn(env_args, attrs):
    x, scale, offset, mean, var = env_args
    if attrs.get("is_training"):
        raise NotImplementedError(
            "FusedBatchNorm with is_training=true: batch statistics are "
            "data-dependent; freeze the graph for inference first")
    eps = attrs.get("epsilon", 1e-3) or 1e-3
    inv = 1.0 / jnp.sqrt(var + eps)
    y = (x - mean) * inv * scale + offset
    # inference form: batch_mean/batch_var outputs (slots 1/2) are the
    # frozen moving stats; slots 3-5 (reserved spaces, V3 has three)
    # mirror them — lets graphs that consume the side outputs import
    return _MultiOut((y, mean, var, mean, var, var))


def _top_k(a, at):
    k = int(np.asarray(a[1]).reshape(())) if len(a) > 1 else int(at["k"])
    vals, idx = lax.top_k(a[0], k)
    return _MultiOut((vals, idx.astype(jnp.int32)))


class _MultiOut(tuple):
    """Value of a multi-output node (Split/Unpack/Switch): index with the
    `node:k` output-slot syntax."""


_DEAD = object()   # untaken Switch branch (pruned by dead propagation)


def _conv2d_backprop_input(a, at):
    """TF Conv2DBackpropInput = transposed conv (the deconv op slim uses
    for upsampling): a = [input_sizes, filter HWIO, out_backprop NHWC]."""
    input_sizes = tuple(int(i) for i in np.asarray(a[0]))
    w, y = a[1], a[2]
    sh, sw = int(at["strides"][1]), int(at["strides"][2])
    out = lax.conv_transpose(y, w, (sh, sw), str(at["padding"]).upper(),
                             dimension_numbers=("NHWC", "HWIO", "NHWC"),
                             transpose_kernel=True)
    if out.shape != input_sizes:    # SAME with even sizes can overshoot
        out = out[:, :input_sizes[1], :input_sizes[2], :]
    return out


def _strided_slice(a, at):
    """Const-indexed subset: begin/end/strides consts + ALL five masks
    (begin/end/shrink_axis/ellipsis/new_axis — strided_slice op spec)."""
    x = a[0]
    begin = [int(i) for i in np.asarray(a[1])]
    end = [int(i) for i in np.asarray(a[2])]
    strides = [int(i) for i in np.asarray(a[3])] if len(a) > 3 \
        else [1] * len(begin)
    bm = int(at.get("begin_mask") or 0)
    em = int(at.get("end_mask") or 0)
    sm = int(at.get("shrink_axis_mask") or 0)
    elm = int(at.get("ellipsis_mask") or 0)
    nam = int(at.get("new_axis_mask") or 0)
    if bin(elm).count("1") > 1:
        raise ValueError("StridedSlice: multiple ellipsis bits")
    nspec = len(begin)
    # input dims consumed by the non-ellipsis, non-new-axis spec slots
    consumed = sum(1 for i in range(nspec)
                   if not (elm >> i) & 1 and not (nam >> i) & 1)
    idx, shrink = [], []
    out_dim = 0       # axis in the pre-squeeze result (tracks new axes)
    for i in range(nspec):
        if (elm >> i) & 1:
            fill = x.ndim - consumed
            idx.extend([slice(None)] * fill)
            out_dim += fill
        elif (nam >> i) & 1:
            idx.append(None)                      # np.newaxis
            out_dim += 1
        elif (sm >> i) & 1:
            b = begin[i]
            idx.append(slice(b, b + 1 if b != -1 else None, 1))
            shrink.append(out_dim)
            out_dim += 1
        else:
            idx.append(slice(None if bm & (1 << i) else begin[i],
                             None if em & (1 << i) else end[i],
                             strides[i]))
            out_dim += 1
    out = x[tuple(idx)]
    return jnp.squeeze(out, axis=tuple(shrink)) if shrink else out


def _tf_slice(a, at):
    begin = [int(i) for i in np.asarray(a[1])]
    size = [int(i) for i in np.asarray(a[2])]
    return a[0][tuple(slice(b, None if s == -1 else b + s)
                      for b, s in zip(begin, size))]


def _cast(a, at):
    dst = at.get("DstT")
    if isinstance(dst, tuple) and dst[0] == "dtype":
        return a[0].astype(_DT.get(dst[1], np.float32))
    return a[0]


def _reduce(fn):
    return lambda a, at: fn(
        a[0], axis=tuple(int(i) for i in np.atleast_1d(np.asarray(a[1]))),
        keepdims=bool(at.get("keep_dims")))


_OP_IMPLS = {
    "Identity": lambda a, at: a[0],
    "MatMul": lambda a, at: jnp.matmul(
        a[0].T if at.get("transpose_a") else a[0],
        a[1].T if at.get("transpose_b") else a[1]),
    "Add": lambda a, at: a[0] + a[1],
    "AddV2": lambda a, at: a[0] + a[1],
    "BiasAdd": lambda a, at: a[0] + a[1],
    "Sub": lambda a, at: a[0] - a[1],
    "Mul": lambda a, at: a[0] * a[1],
    "RealDiv": lambda a, at: a[0] / a[1],
    "Maximum": lambda a, at: jnp.maximum(a[0], a[1]),
    "Minimum": lambda a, at: jnp.minimum(a[0], a[1]),
    "Relu": lambda a, at: jax.nn.relu(a[0]),
    "Relu6": lambda a, at: jnp.clip(a[0], 0, 6),
    "Sigmoid": lambda a, at: jax.nn.sigmoid(a[0]),
    "Tanh": lambda a, at: jnp.tanh(a[0]),
    "Softmax": lambda a, at: jax.nn.softmax(a[0], axis=-1),
    "LogSoftmax": lambda a, at: jax.nn.log_softmax(a[0], axis=-1),
    "Reshape": lambda a, at: jnp.reshape(
        a[0], tuple(int(d) for d in np.asarray(a[1]))),
    "Squeeze": lambda a, at: jnp.squeeze(
        a[0], axis=tuple(at["squeeze_dims"]) if at.get("squeeze_dims")
        else None),
    "ExpandDims": lambda a, at: jnp.expand_dims(a[0], int(a[1])),
    "ConcatV2": lambda a, at: jnp.concatenate(a[:-1], axis=int(a[-1])),
    "Mean": lambda a, at: jnp.mean(
        a[0], axis=tuple(int(i) for i in np.atleast_1d(np.asarray(a[1]))),
        keepdims=bool(at.get("keep_dims"))),
    "Sum": lambda a, at: jnp.sum(
        a[0], axis=tuple(int(i) for i in np.atleast_1d(np.asarray(a[1]))),
        keepdims=bool(at.get("keep_dims"))),
    "Max": lambda a, at: jnp.max(
        a[0], axis=tuple(int(i) for i in np.atleast_1d(np.asarray(a[1]))),
        keepdims=bool(at.get("keep_dims"))),
    "Pad": lambda a, at: jnp.pad(
        a[0], [(int(p[0]), int(p[1])) for p in np.asarray(a[1])]),
    "Transpose": lambda a, at: jnp.transpose(
        a[0], tuple(int(i) for i in np.asarray(a[1]))),
    "Conv2D": lambda a, at: _conv2d(a[0], a[1], at["strides"],
                                    at["padding"]),
    "DepthwiseConv2dNative": lambda a, at: _conv2d(
        a[0],
        a[1].reshape(a[1].shape[0], a[1].shape[1], 1, -1),
        at["strides"], at["padding"],
        feature_group_count=a[0].shape[-1]),
    "MaxPool": lambda a, at: _pool(a[0], at["ksize"], at["strides"],
                                   at["padding"], lax.max, -jnp.inf),
    "AvgPool": lambda a, at: _avg_pool(a[0], at["ksize"], at["strides"],
                                       at["padding"]),
    "TopKV2": _top_k,
    "TopK": _top_k,
    "FusedBatchNorm": _fused_bn,
    "FusedBatchNormV2": _fused_bn,
    "FusedBatchNormV3": _fused_bn,
    # -- breadth for real exported GraphDefs
    #    (≙ utils/tf/loaders/ 159 op loaders) ----------------------------- #
    "Fill": lambda a, at: jnp.full(
        tuple(int(d) for d in np.asarray(a[0])), a[1]),
    "Pack": lambda a, at: jnp.stack(a, axis=int(at.get("axis") or 0)),
    "Unpack": lambda a, at: _MultiOut(
        jnp.moveaxis(a[0], int(at.get("axis") or 0), 0)),
    "Split": lambda a, at: _MultiOut(
        jnp.split(a[1], int(at["num_split"]), axis=int(a[0]))),
    "SplitV": lambda a, at: _MultiOut(jnp.split(
        a[0], np.cumsum([int(s) for s in np.asarray(a[1])])[:-1].tolist(),
        axis=int(a[2]))),
    "Conv2DBackpropInput": _conv2d_backprop_input,
    "PadV2": lambda a, at: jnp.pad(
        a[0], [(int(p[0]), int(p[1])) for p in np.asarray(a[1])],
        constant_values=np.asarray(a[2]).item()),
    "MirrorPad": lambda a, at: jnp.pad(
        a[0], [(int(p[0]), int(p[1])) for p in np.asarray(a[1])],
        mode="reflect" if str(at.get("mode", "REFLECT")).upper()
        == "REFLECT" else "symmetric"),
    "Min": _reduce(jnp.min),
    "Prod": _reduce(jnp.prod),
    "Shape": lambda a, at: jnp.asarray(a[0].shape, jnp.int32),
    "Rank": lambda a, at: jnp.asarray(a[0].ndim, jnp.int32),
    "Size": lambda a, at: jnp.asarray(a[0].size, jnp.int32),
    "Cast": _cast,
    "StopGradient": lambda a, at: lax.stop_gradient(a[0]),
    "Neg": lambda a, at: -a[0],
    "Exp": lambda a, at: jnp.exp(a[0]),
    "Log": lambda a, at: jnp.log(a[0]),
    "Sqrt": lambda a, at: jnp.sqrt(a[0]),
    "Rsqrt": lambda a, at: lax.rsqrt(a[0]),
    "Square": lambda a, at: jnp.square(a[0]),
    "SquaredDifference": lambda a, at: jnp.square(a[0] - a[1]),
    "Abs": lambda a, at: jnp.abs(a[0]),
    "Floor": lambda a, at: jnp.floor(a[0]),
    "Ceil": lambda a, at: jnp.ceil(a[0]),
    "Round": lambda a, at: jnp.round(a[0]),
    "Pow": lambda a, at: jnp.power(a[0], a[1]),
    "FloorDiv": lambda a, at: jnp.floor_divide(a[0], a[1]),
    "FloorMod": lambda a, at: jnp.mod(a[0], a[1]),
    "Softplus": lambda a, at: jax.nn.softplus(a[0]),
    "Elu": lambda a, at: jax.nn.elu(a[0]),
    "LeakyRelu": lambda a, at: jax.nn.leaky_relu(
        a[0], 0.2 if at.get("alpha") is None else at["alpha"]),
    "ArgMax": lambda a, at: jnp.argmax(a[0], axis=int(a[1])),
    "ArgMin": lambda a, at: jnp.argmin(a[0], axis=int(a[1])),
    "Tile": lambda a, at: jnp.tile(
        a[0], tuple(int(i) for i in np.asarray(a[1]))),
    "Slice": _tf_slice,
    "StridedSlice": _strided_slice,
    "GatherV2": lambda a, at: jnp.take(
        a[0], jnp.asarray(a[1]), axis=int(a[2]) if len(a) > 2 else 0),
    "Gather": lambda a, at: jnp.take(a[0], jnp.asarray(a[1]), axis=0),
    "Range": lambda a, at: jnp.arange(np.asarray(a[0]).item(),
                                      np.asarray(a[1]).item(),
                                      np.asarray(a[2]).item()),
    "ZerosLike": lambda a, at: jnp.zeros_like(a[0]),
    "OnesLike": lambda a, at: jnp.ones_like(a[0]),
    "Greater": lambda a, at: a[0] > a[1],
    "GreaterEqual": lambda a, at: a[0] >= a[1],
    "Less": lambda a, at: a[0] < a[1],
    "LessEqual": lambda a, at: a[0] <= a[1],
    "Equal": lambda a, at: a[0] == a[1],
    "NotEqual": lambda a, at: a[0] != a[1],
    "LogicalAnd": lambda a, at: jnp.logical_and(a[0], a[1]),
    "LogicalOr": lambda a, at: jnp.logical_or(a[0], a[1]),
    "LogicalNot": lambda a, at: jnp.logical_not(a[0]),
    "Select": lambda a, at: jnp.where(a[0], a[1], a[2]),
    "SelectV2": lambda a, at: jnp.where(a[0], a[1], a[2]),
    "BatchMatMul": lambda a, at: jnp.matmul(
        jnp.swapaxes(a[0], -1, -2) if at.get("adj_x") else a[0],
        jnp.swapaxes(a[1], -1, -2) if at.get("adj_y") else a[1]),
    "BatchMatMulV2": lambda a, at: jnp.matmul(
        jnp.swapaxes(a[0], -1, -2) if at.get("adj_x") else a[0],
        jnp.swapaxes(a[1], -1, -2) if at.get("adj_y") else a[1]),
}


# --------------------------------------------------------------------- #
# while-loop frames (≙ nn/tf/ControlOps.scala:182-229 Enter/Exit/        #
# NextIteration/LoopCondition + nn/FrameManager.scala:31 frame           #
# scheduling).  TF v1 encodes tf.while_loop as a CYCLIC cluster:         #
#   Enter(frame_name) -> Merge <- NextIteration                          #
#   Merge -> [cond subgraph] -> LoopCond -> Switch(pred)                 #
#   Switch:0 -> Exit (loop result), Switch:1 -> [body] -> NextIteration  #
# The reference interprets these frames at runtime; the TPU-native       #
# lowering collapses each frame into ONE synthetic _While node executed  #
# as a `lax.while_loop` (XLA-compiled, no per-iteration dispatch), with  #
# Exit nodes becoming slot-projections of its final carry state.         #
# --------------------------------------------------------------------- #
def _base(ref: str) -> str:
    return ref.split(":")[0].lstrip("^")


def _scan_frame(nodes, consumers, frame, enter_names):
    """Frame membership: forward reachability from the Enters, stopping
    at Exit (the only legal frame escape).  Returns (member, exits), or
    None when the frame contains another frame's Enter — i.e. it has a
    NESTED inner loop that must be rewritten first."""
    member = set(enter_names)
    queue = list(enter_names)
    exits: List[str] = []
    while queue:
        for c in consumers.get(queue.pop(), ()):
            if c in member:
                continue
            cn = nodes[c]
            if cn.op in ("Exit", "RefExit"):
                member.add(c)
                exits.append(c)
                continue
            if cn.op in ("Enter", "RefEnter") \
                    and str(cn.attrs.get("frame_name", "")) != frame:
                return None        # inner frame present: not innermost
            member.add(c)
            queue.append(c)
    return member, exits


def _rewrite_one_frame(out, consumers, frame, member, exits):
    """Collapse one (innermost) frame's nodes into a synthetic `_While`
    node + `_WhileOut` exit stubs.  Mutates `out`."""
    nodes = out
    loop_conds = [m for m in member if nodes[m].op == "LoopCond"]
    if len(loop_conds) != 1:
        raise NotImplementedError(
            f"while frame {frame!r}: expected exactly one LoopCond, "
            f"found {len(loop_conds)}")
    loop_cond = loop_conds[0]

    def switch_pred_base(sw):
        return _base([i for i in nodes[sw].inputs
                      if not i.startswith("^")][1])

    # loop-variable merges: Enter/NextIteration pairs.  Merges with other
    # input patterns are tf.cond joins inside the body — left in the
    # frame body for the evaluator's select lowering.
    merges = sorted(m for m in member if nodes[m].op in ("Merge",
                                                         "RefMerge"))
    merge_info = []           # (merge, enter_ref, next_ref, switch|None)
    for m in merges:
        ins = [i for i in nodes[m].inputs if not i.startswith("^")]
        enter_ref = next((i for i in ins
                          if nodes[_base(i)].op in ("Enter",
                                                    "RefEnter")), None)
        next_ref = next((i for i in ins
                         if nodes[_base(i)].op == "NextIteration"), None)
        if enter_ref is None or next_ref is None:
            continue                    # conditional join, not a loop var
        # the loop-variable Switch is the consumer switching on the
        # frame's LoopCond; switches with other predicates are body
        # conditionals
        sw = next((c for c in consumers.get(m, ())
                   if nodes[c].op in ("Switch", "RefSwitch")
                   and switch_pred_base(c) == loop_cond), None)
        merge_info.append((m, enter_ref, next_ref, sw))

    # Exit -> loop-var index (via its Switch)
    exit_var: Dict[str, int] = {}
    for e in exits:
        e_in = _base([i for i in nodes[e].inputs
                      if not i.startswith("^")][0])
        idx = next((k for k, (_, _, _, sw) in enumerate(merge_info)
                    if sw == e_in), None)
        if idx is None:
            raise NotImplementedError(
                f"while frame {frame!r}: Exit {e!r} does not consume "
                "a loop-variable Switch")
        exit_var[e] = idx

    while_name = f"__while__{frame}"
    frame_nodes = {m: nodes[m] for m in member}
    # every ref a frame node reads from OUTSIDE the frame (Enter
    # sources, plus consts/tensors captured without an Enter) becomes
    # a data input of the synthetic node, so the outer toposort
    # schedules them and the frame evaluator can bind them
    externals: List[str] = []
    for m in sorted(member):
        if nodes[m].op in ("Exit", "RefExit"):
            continue
        for i in nodes[m].inputs:
            if not i.startswith("^") and _base(i) not in member \
                    and i not in externals:
                externals.append(i)
    wnode = NodeDef(while_name, "_While",
                    inputs=list(externals),
                    attrs={"_frame": {
                        "name": frame,
                        "nodes": frame_nodes,
                        "externals": externals,
                        "merge_info": merge_info,
                        "cond_ref": nodes[loop_cond].inputs[0],
                        "loop_cond": loop_cond,
                    }})
    for m in member:
        if m not in exits:
            del out[m]
    out[while_name] = wnode
    for e in exits:
        out[e] = NodeDef(e, "_WhileOut",
                         inputs=[f"{while_name}:{exit_var[e]}"])


def _rewrite_while_frames(nodes: Dict[str, NodeDef]) -> Dict[str, NodeDef]:
    """Collapse TF v1 while frames to synthetic `_While` nodes,
    innermost-first: a frame whose body contains another frame's Enter
    nodes (loops-in-loops, ≙ FrameManager.createFrame(parentFrame),
    nn/FrameManager.scala:40,115-120) waits until the inner frame has
    been rewritten into an ordinary `_While` node, then collapses around
    it like any other body op."""
    out = dict(nodes)
    # each pass collapses exactly one frame, so the total frame count
    # (NOT the nesting depth) bounds the passes
    n_frames = len({str(n.attrs.get("frame_name", ""))
                    for n in nodes.values()
                    if n.op in ("Enter", "RefEnter")})
    for _ in range(n_frames):
        enters_by_frame: Dict[str, List[str]] = {}
        for n in out.values():
            if n.op in ("Enter", "RefEnter"):
                enters_by_frame.setdefault(
                    str(n.attrs.get("frame_name", "")), []).append(n.name)
        if not enters_by_frame:
            break
        consumers: Dict[str, List[str]] = {}
        for n in out.values():
            for i in n.inputs:
                consumers.setdefault(_base(i), []).append(n.name)
        progressed = False
        for frame, enter_names in sorted(enters_by_frame.items()):
            info = _scan_frame(out, consumers, frame, enter_names)
            if info is None:
                continue                    # has an inner frame: later pass
            _rewrite_one_frame(out, consumers, frame, *info)
            progressed = True
            break                           # node set changed: rescan
        if not progressed:
            raise NotImplementedError(
                "while frames: no innermost frame found "
                f"(malformed nesting among {sorted(enters_by_frame)})")
    return out


class TFGraph(Module):
    """Imported GraphDef as a Module: topological jnp evaluation, jittable
    (≙ utils/tf/Session.scala's BigDLSessionImpl graph execution).
    tf.while_loop frames lower to `lax.while_loop` (see
    `_rewrite_while_frames`)."""

    def __init__(self, nodes: List[NodeDef], inputs: Sequence[str],
                 outputs: Sequence[str], name=None, while_max_iters=None):
        super().__init__(name=name)
        self.nodes = _rewrite_while_frames({n.name: n for n in nodes})
        self.input_names = list(inputs)
        self.output_names = list(outputs)
        # bounded-scan lowering for every imported loop: trades "always
        # run max_iters masked iterations" for reverse-differentiability
        # (same contract as nn.WhileLoop(max_iters=...) — the TPU-native
        # DynamicGraph.generateBackward, nn/DynamicGraph.scala:32)
        self.while_max_iters = while_max_iters
        self.consts: Dict[str, np.ndarray] = {
            n.name: n.attrs["value"]
            for n in self.nodes.values() if n.op == "Const"}
        self._order = self._toposort()

    def _toposort(self) -> List[str]:
        order, seen = [], set()

        def visit(name):
            base = _base(name)
            if base in seen:
                return
            seen.add(base)
            node = self.nodes.get(base)
            if node is None:
                raise KeyError(f"graph references unknown node {base!r}")
            for inp in node.inputs:
                visit(inp)
            order.append(base)

        for out in self.output_names:
            visit(out)
        return order

    @staticmethod
    def _resolve(env, ref):
        """`node:k` output-slot lookup into a node's env value."""
        base, _, slot = ref.partition(":")
        v = env[base]
        if v is _DEAD:
            return _DEAD        # any slot of a dead node is dead
        if isinstance(v, _MultiOut):
            return v[int(slot or 0)]
        if slot and int(slot) != 0:
            raise NotImplementedError(
                f"output slot {ref!r}: node {base!r} exposes only its "
                "primary output here (secondary outputs of this op are "
                "not implemented)")
        return v

    def apply(self, params, x, ctx):
        xs = x if isinstance(x, (list, tuple)) else [x]
        env: Dict[str, object] = {}
        for name, val in zip(self.input_names, xs):
            env[name] = val
        for name in self._order:
            if name in env:
                continue
            node = self.nodes[name]
            if node.op == "Const":
                env[name] = jnp.asarray(self.consts[name])
                continue
            if node.op in ("Placeholder", "PlaceholderV2"):
                raise ValueError(f"unbound Placeholder {name!r}; pass it via "
                                 f"inputs={self.input_names}")
            args = [self._resolve(env, i) for i in node.inputs
                    if not i.startswith("^")]
            # dead propagation: anything fed (only) by an untaken Switch
            # branch is dead too — unsupported ops inside the untaken
            # branch of a folded is_training cond must not fail the import
            # (≙ TensorflowLoader's control-flow pruning)
            if node.op == "Merge":
                live_idx = next((i for i, v in enumerate(args)
                                 if v is not _DEAD), None)
                if live_idx is None:
                    env[name] = _DEAD
                    continue
                env[name] = _MultiOut((args[live_idx],
                                       jnp.asarray(live_idx, jnp.int32)))
                continue
            if any(v is _DEAD for v in args):
                env[name] = _DEAD
                continue
            if node.op == "_While":
                env[name] = _MultiOut(
                    self._run_while(node.attrs["_frame"], args, env))
                continue
            if node.op == "_WhileOut":
                env[name] = args[0]
                continue
            if node.op in ("Switch", "RefSwitch"):
                try:
                    pred = bool(np.asarray(args[1]).reshape(()))
                except Exception as e:
                    raise NotImplementedError(
                        f"dynamic Switch {name!r}: predicate depends on "
                        "graph inputs; only constant-foldable control "
                        f"flow is supported ({type(e).__name__})") from e
                env[name] = _MultiOut((args[0] if not pred else _DEAD,
                                       args[0] if pred else _DEAD))
                continue
            impl = _OP_IMPLS.get(node.op)
            if impl is None:
                raise NotImplementedError(
                    f"TF op {node.op!r} (node {name!r}) not supported")
            env[name] = impl(args, node.attrs)
        outs = [self._resolve(env, o) for o in self.output_names]
        if any(o is _DEAD for o in outs):
            raise ValueError("graph output is on an untaken Switch branch")
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------------------ #
    # while-frame execution: one lax.while_loop per frame                 #
    # ------------------------------------------------------------------ #
    def _run_while(self, frame, ext_vals, outer_env):
        fnodes: Dict[str, NodeDef] = frame["nodes"]
        merge_info = frame["merge_info"]
        loop_cond = frame.get("loop_cond")
        loopvar_merges = {m for m, _, _, _ in merge_info}
        ext_env = dict(zip(frame["externals"], ext_vals))

        def data_inputs(nd):
            return [i for i in nd.inputs if not i.startswith("^")]

        def branch_slots(ref, visited):
            """{(pred_ref, slot)} of the body-conditional Switch slots
            `ref` transitively consumes — the join identity a tf.cond
            Merge needs.  Stops at loop-var merges and frame borders."""
            b2 = _base(ref)
            nd2 = fnodes.get(b2)
            found = set()
            if nd2 is None or b2 in loopvar_merges:
                return found
            if nd2.op in ("Switch", "RefSwitch"):
                ins2 = data_inputs(nd2)
                if _base(ins2[1]) != loop_cond:
                    found.add((ins2[1], int(ref.partition(":")[2] or 0)))
                return found
            if b2 in visited:
                return found
            visited.add(b2)
            for i in data_inputs(nd2):
                found |= branch_slots(i, visited)
            return found

        def feval(ref, env):
            b = _base(ref)
            if b not in env:
                nd = fnodes.get(b)
                if nd is None:
                    # defined outside the frame: bound via the synthetic
                    # node's inputs (loop constants under while tracing)
                    if ref in ext_env:
                        return ext_env[ref]
                    return TFGraph._resolve(outer_env, ref)
                if nd.op == "Const":
                    env[b] = jnp.asarray(nd.attrs["value"])
                elif nd.op in ("Enter", "RefEnter", "Identity", "LoopCond",
                               "NextIteration", "StopGradient"):
                    env[b] = feval(nd.inputs[0], env)
                elif nd.op == "_While":
                    # an inner (nested) loop already collapsed by the
                    # innermost-first rewrite: run it like any body op
                    args = [feval(i, env) for i in data_inputs(nd)]
                    env[b] = _MultiOut(
                        self._run_while(nd.attrs["_frame"], args, env))
                elif nd.op == "_WhileOut":
                    env[b] = feval(nd.inputs[0], env)
                elif nd.op in ("Switch", "RefSwitch"):
                    ins = data_inputs(nd)
                    if _base(ins[1]) == loop_cond:
                        # loop-skeleton switch: inside the body only the
                        # taken (:1) branch is live
                        env[b] = _MultiOut((_DEAD, feval(ins[0], env)))
                    else:
                        # tf.cond inside the body: both branch slots see
                        # the value; the join Merge selects by predicate
                        # (XLA-native vectorized conditional)
                        v = feval(ins[0], env)
                        env[b] = _MultiOut((v, v))
                elif nd.op in ("Merge", "RefMerge"):
                    # non-loop-var merge: the join of a body tf.cond
                    ins = data_inputs(nd)
                    if len(ins) != 2:
                        raise NotImplementedError(
                            f"while frame {frame['name']!r}: Merge "
                            f"{b!r} with {len(ins)} inputs is not a "
                            "recognized conditional join")
                    sl = [branch_slots(i, set()) for i in ins]
                    preds = {p for s in sl for p, _ in s}
                    if len(preds) != 1:
                        raise NotImplementedError(
                            f"while frame {frame['name']!r}: conditional "
                            f"join {b!r} controlled by {len(preds)} "
                            "predicates; only single-predicate tf.cond "
                            "bodies are supported")

                    slots = [{s for _, s in sli} for sli in sl]
                    if any(len(s) > 1 for s in slots):
                        raise NotImplementedError(
                            f"while frame {frame['name']!r}: conditional "
                            f"join {b!r} input consumes both Switch "
                            "branches")
                    # per-input identity: {1} = true branch, {0} = false
                    # branch, {} = constant (takes whatever side is left)
                    ids = [next(iter(s)) if s else None for s in slots]
                    if ids == [None, None] or (ids[0] is not None
                                               and ids[0] == ids[1]):
                        raise NotImplementedError(
                            f"while frame {frame['name']!r}: conditional "
                            f"join {b!r} branches are not a true/false "
                            f"pair (slots {ids})")
                    if ids[0] == 1 or ids[1] == 0:
                        i_true, i_false = 0, 1
                    else:
                        i_true, i_false = 1, 0
                    pv = jnp.reshape(feval(next(iter(preds)), env), ())
                    # genuine lax.cond over LAZY branch closures (not an
                    # eager both-eval + where): only the taken branch
                    # executes/differentiates, so a non-finite value on
                    # the untaken side (sqrt of a negative, ...) cannot
                    # leak 0*NaN=NaN into the gradients.  Each closure
                    # evaluates into a COPY of the memo so cond-trace
                    # tracers never escape into the outer env.
                    t_ref, f_ref = ins[i_true], ins[i_false]
                    val = lax.cond(
                        pv,
                        lambda _: jnp.asarray(feval(t_ref, dict(env))),
                        lambda _: jnp.asarray(feval(f_ref, dict(env))),
                        None)
                    env[b] = _MultiOut((
                        val,
                        jnp.where(pv, jnp.asarray(i_true, jnp.int32),
                                  jnp.asarray(i_false, jnp.int32))))
                elif nd.op in ("Exit", "RefExit"):
                    raise NotImplementedError(
                        f"while frame {frame['name']!r}: {nd.op} node "
                        f"{b!r} outside the recognized loop skeleton")
                else:
                    args = [feval(i, env) for i in data_inputs(nd)]
                    impl = _OP_IMPLS.get(nd.op)
                    if impl is None:
                        raise NotImplementedError(
                            f"TF op {nd.op!r} (node {b!r}) in while frame "
                            "not supported")
                    env[b] = impl(args, nd.attrs)
            v = env[b]
            base_name, _, slot = ref.partition(":")
            if isinstance(v, _MultiOut):
                return v[int(slot or 0)]
            return v

        init_env: Dict[str, object] = {}
        init = tuple(jnp.asarray(feval(enter_ref, init_env))
                     for _, enter_ref, _, _ in merge_info)

        def cond_fn(state):
            env: Dict[str, object] = {}
            for (m, _, _, _), s in zip(merge_info, state):
                env[m] = s
            return jnp.reshape(feval(frame["cond_ref"], env), ())

        def body_fn(state):
            env: Dict[str, object] = {}
            for (m, _, _, sw), s in zip(merge_info, state):
                env[m] = s
                if sw is not None:
                    # inside the body only the taken (:1) branch is live
                    env[sw] = _MultiOut((_DEAD, s))
            return tuple(
                jnp.asarray(feval(next_ref, env))
                for _, _, next_ref, _ in merge_info)

        if self.while_max_iters is None:
            return lax.while_loop(cond_fn, body_fn, init)
        # bounded differentiable lowering, shared with
        # nn.WhileLoop(max_iters=...)
        from ..nn.control_flow import bounded_while
        return bounded_while(cond_fn, body_fn, init, self.while_max_iters)


def load_tf_graph(path_or_bytes, inputs: Sequence[str],
                  outputs: Sequence[str],
                  while_max_iters=None) -> TFGraph:
    """≙ TensorflowLoader.load(graphPrototxt, inputs, outputs).

    ``while_max_iters=N`` lowers every imported while frame to a bounded
    differentiable scan (see :class:`TFGraph`) so the imported graph can
    TRAIN (≙ utils/tf/Session.scala:634 training over DynamicGraph)."""
    if isinstance(path_or_bytes, bytes):
        data = path_or_bytes
    else:
        with open(path_or_bytes, "rb") as f:
            data = f.read()
    return TFGraph(parse_graphdef(data), inputs, outputs,
                   while_max_iters=while_max_iters)


# --------------------------------------------------------------------- #
# export (TensorflowSaver subset)                                       #
# --------------------------------------------------------------------- #
def _enc_shape(dims) -> bytes:
    out = b""
    for d in dims:
        out += enc_bytes(2, proto.enc_int64(1, d))
    return out


def _enc_tensor(arr: np.ndarray) -> bytes:
    dt = _DT_REV[np.dtype(arr.dtype)]
    return (proto.enc_int64(1, dt) + enc_bytes(2, _enc_shape(arr.shape))
            + enc_bytes(4, np.ascontiguousarray(arr).tobytes()))


def _attr(key: str, value: bytes) -> bytes:
    return enc_bytes(5, enc_string(1, key) + enc_bytes(2, value))


def _node(name: str, op: str, inputs=(), attrs: Dict[str, bytes] = None) \
        -> bytes:
    body = enc_string(1, name) + enc_string(2, op)
    for i in inputs:
        body += enc_string(3, i)
    for k, v in (attrs or {}).items():
        body += _attr(k, v)
    return enc_bytes(1, body)


def save_tf_graph(model: Module, path: str, input_shape,
                  input_name: str = "input",
                  output_name: str = "output") -> List[str]:
    """Export a Sequential to a frozen GraphDef
    (≙ TensorflowSaver.saveGraph).  Covers Linear, activations, Reshape/
    View, SpatialConvolution (NCHW models: a single NHWC transpose pair
    brackets the conv stack, TF-style), max/avg pooling (explicit pads
    become Pad/PadV2(-inf) nodes + VALID ops), and BatchNormalization
    (inference form folded to Mul+Add consts).  Returns the node names.
    """
    from ..nn import (containers, linear as linear_mod, activation,
                      shape_ops, conv as conv_mod, pooling as pool_mod,
                      normalization as norm_mod)

    params = model.ensure_initialized()
    state = model._state or {}
    out = b""
    dt_float = proto.enc_int64(6, 1)  # type: DT_FLOAT attr value
    dt_int = proto.enc_int64(6, 3)
    out += _node(input_name, "Placeholder",
                 attrs={"dtype": dt_float,
                        "shape": enc_bytes(7, _enc_shape(input_shape))})
    cur = input_name
    names = [input_name]
    layout = "nchw" if len(tuple(input_shape)) == 4 else "flat"

    def emit(name, op, inputs, attrs=None):
        """Emit an op node with the required real-TF dtype attrs: every
        float op needs T, Transpose Tperm, Pad(V2) Tpaddings, Reshape
        Tshape (tf.import_graph_def rejects nodes missing them)."""
        nonlocal out
        at = dict(attrs or {})
        if op != "Const" and op != "Placeholder":
            at.setdefault("T", dt_float)
        if op == "Transpose":
            at.setdefault("Tperm", dt_int)
        if op in ("Pad", "PadV2"):
            at.setdefault("Tpaddings", dt_int)
        if op == "Reshape":
            at.setdefault("Tshape", dt_int)
        out += _node(name, op, inputs, at)
        names.append(name)

    def const(name, arr, dt=None):
        emit(name, "Const", (),
             {"dtype": dt or dt_float, "value": enc_bytes(8, _enc_tensor(arr))})
        return name

    def transpose(name, perm):
        nonlocal cur
        const(f"{name}/perm", np.asarray(perm, np.int32), dt_int)
        emit(name, "Transpose", [cur, f"{name}/perm"])
        cur = name

    def to_nhwc(lname):
        nonlocal layout
        if layout == "nchw":
            transpose(f"{lname}/to_nhwc", (0, 2, 3, 1))
            layout = "nhwc"

    def to_nchw(lname):
        nonlocal layout
        if layout == "nhwc":
            transpose(f"{lname}/to_nchw", (0, 3, 1, 2))
            layout = "nchw"

    def pad_explicit(lname, ph, pw, value=None):
        """Pad H/W of the NHWC tensor; value None = zeros, else PadV2."""
        nonlocal cur
        padv = np.asarray([[0, 0], [ph, ph], [pw, pw], [0, 0]], np.int32)
        const(f"{lname}/pads", padv, dt_int)
        if value is None:
            emit(f"{lname}/pad", "Pad", [cur, f"{lname}/pads"])
        else:
            const(f"{lname}/padval", np.asarray(value, np.float32))
            emit(f"{lname}/pad", "PadV2",
                 [cur, f"{lname}/pads", f"{lname}/padval"])
        cur = f"{lname}/pad"

    def spatial_attrs(sh, sw, kh=None, kw=None, padding="VALID"):
        at = {"strides": _ints_list_attr([1, sh, sw, 1]),
              "padding": enc_string(2, padding)}
        if kh is not None:
            at["ksize"] = _ints_list_attr([1, kh, kw, 1])
        return at

    def spatial_setup(layer, lname, pad_value=None):
        """Shared conv/pool geometry: NHWC transition, format/ceil guards,
        VALID/SAME/explicit-pad resolution.  Returns (kh,kw,sh,sw,padding)
        after emitting any needed Pad node."""
        if getattr(layer, "format", "NCHW") != "NCHW":
            raise NotImplementedError(
                f"save_tf_graph: {type(layer).__name__} with "
                f"format={layer.format!r} (exporter assumes NCHW models)")
        if getattr(layer, "ceil_mode", False):
            raise NotImplementedError(
                "save_tf_graph: ceil_mode pooling has no TF equivalent")
        to_nhwc(lname)
        kh, kw = layer.kernel
        sh, sw = layer.stride
        ph, pw = layer.pad
        padding = "VALID"
        if (ph, pw) == (-1, -1):
            padding = "SAME"
        elif (ph, pw) != (0, 0):
            pad_explicit(lname, ph, pw, value=pad_value)
        return kh, kw, sh, sw, padding

    layers = model.children() if hasattr(model, "children") else [model]
    idx = 0
    for layer in layers:
        lname = f"layer{idx}"
        if isinstance(layer, linear_mod.Linear):
            to_nchw(lname)
            w = np.asarray(params[layer.name]["weight"], np.float32)
            b = np.asarray(params[layer.name].get("bias"), np.float32) \
                if "bias" in params[layer.name] else None
            const(f"{lname}/weight", w.T)
            emit(f"{lname}/mm", "MatMul", [cur, f"{lname}/weight"])
            cur = f"{lname}/mm"
            if b is not None:
                const(f"{lname}/bias", b)
                emit(f"{lname}/add", "BiasAdd", [cur, f"{lname}/bias"])
                cur = f"{lname}/add"
        elif isinstance(layer, conv_mod.SpatialConvolution):
            if layer.n_group != 1:
                raise NotImplementedError(
                    "save_tf_graph: grouped convolution")
            kh, kw, sh, sw, padding = spatial_setup(layer, lname)
            w = np.asarray(params[layer.name]["weight"], np.float32)
            const(f"{lname}/kernel", w.transpose(2, 3, 1, 0))  # OIHW->HWIO
            emit(f"{lname}/conv", "Conv2D", [cur, f"{lname}/kernel"],
                 spatial_attrs(sh, sw, padding=padding))
            cur = f"{lname}/conv"
            if layer.with_bias:
                const(f"{lname}/bias",
                      np.asarray(params[layer.name]["bias"], np.float32))
                emit(f"{lname}/badd", "BiasAdd", [cur, f"{lname}/bias"])
                cur = f"{lname}/badd"
        elif isinstance(layer, pool_mod.SpatialMaxPooling):
            # explicit max-pool padding must not beat negative activations
            kh, kw, sh, sw, padding = spatial_setup(layer, lname,
                                                    pad_value=-3.4e38)
            emit(lname, "MaxPool", [cur],
                 spatial_attrs(sh, sw, kh, kw, padding))
            cur = lname
        elif isinstance(layer, pool_mod.SpatialAveragePooling):
            if layer.pad != (0, 0) and not layer.count_include_pad:
                raise NotImplementedError(
                    "save_tf_graph: avg pool with explicit pad and "
                    "count_include_pad=False")
            if layer.pad == (-1, -1) and layer.count_include_pad:
                # TF SAME avg divides by the in-bounds count; ours by the
                # full kernel area when count_include_pad — values differ
                raise NotImplementedError(
                    "save_tf_graph: SAME avg pool with "
                    "count_include_pad=True does not match TF semantics")
            kh, kw, sh, sw, padding = spatial_setup(layer, lname)
            emit(lname, "AvgPool", [cur],
                 spatial_attrs(sh, sw, kh, kw, padding))
            cur = lname
        elif isinstance(layer, (norm_mod.SpatialBatchNormalization,
                                norm_mod.BatchNormalization)):
            if layout == "nchw" and isinstance(
                    layer, norm_mod.SpatialBatchNormalization):
                to_nhwc(lname)
            st = state.get(layer.name, {})
            mean = np.asarray(st.get("running_mean",
                                     np.zeros(layer.n_output)), np.float32)
            var = np.asarray(st.get("running_var",
                                    np.ones(layer.n_output)), np.float32)
            p = params.get(layer.name, {})
            gamma = np.asarray(p.get("weight", np.ones(layer.n_output)),
                               np.float32)
            beta = np.asarray(p.get("bias", np.zeros(layer.n_output)),
                              np.float32)
            # inference BN folded to y = x*k + b (channel-last broadcast)
            k = gamma / np.sqrt(var + layer.eps)
            bb = beta - mean * k
            const(f"{lname}/scale", k.astype(np.float32))
            emit(f"{lname}/mul", "Mul", [cur, f"{lname}/scale"])
            const(f"{lname}/shift", bb.astype(np.float32))
            emit(f"{lname}/addb", "Add", [f"{lname}/mul", f"{lname}/shift"])
            cur = f"{lname}/addb"
        elif isinstance(layer, activation.ReLU):
            emit(lname, "Relu", [cur]); cur = lname
        elif isinstance(layer, activation.Tanh):
            emit(lname, "Tanh", [cur]); cur = lname
        elif isinstance(layer, activation.Sigmoid):
            emit(lname, "Sigmoid", [cur]); cur = lname
        elif isinstance(layer, activation.SoftMax):
            emit(lname, "Softmax", [cur]); cur = lname
        elif isinstance(layer, activation.LogSoftMax):
            emit(lname, "LogSoftmax", [cur]); cur = lname
        elif isinstance(layer, (shape_ops.Reshape, shape_ops.View)):
            to_nchw(lname)   # flatten order must match the NCHW weights
            size = layer.size if isinstance(layer, shape_ops.Reshape) \
                else layer.sizes
            tgt = np.asarray((-1,) + tuple(size), np.int32)
            const(f"{lname}/shape", tgt, dt_int)
            emit(lname, "Reshape", [cur, f"{lname}/shape"])
            cur = lname
            # a rank-4 target re-enters NCHW-image land (downstream convs
            # must transpose again); anything else is flat
            layout = "nchw" if tgt.size == 4 else "flat"
        else:
            raise NotImplementedError(
                f"save_tf_graph: unsupported layer {type(layer).__name__}")
        idx += 1
    to_nchw("final")
    emit(output_name, "Identity", [cur])
    with open(path, "wb") as f:
        f.write(out)
    return names


def _ints_list_attr(vals) -> bytes:
    """AttrValue list(int) for strides/ksize — ListValue.i is field 3,
    packed (attr_value.proto; field 2 is the strings list)."""
    payload = b"".join(proto._varint(v) for v in vals)
    return enc_bytes(1, enc_bytes(3, payload))
