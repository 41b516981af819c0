"""Reference-format `.bigdl` protobuf model reader/writer.

The reference persists models as a `BigDLModule` protobuf
(serialization/bigdl.proto; written/read by utils/serializer/
ModuleSerializer.scala:1, ModuleLoader.scala:48 loadFromFile,
ModulePersister).  Layout facts this module encodes against:

  * the file is the raw BigDLModule message (no magic/header);
  * tensor DATA lives once in the top-level attr map under
    "global_storage" (SerConst.GLOBAL_STORAGE) as a NameAttrList mapping
    tensorId -> AttrValue(tensorValue) whose TensorStorage carries the
    inline float data; parameter tensors elsewhere reference the same
    storage by id (ModuleLoader.scala:119 initTensorStorage);
  * each module's constructor args are attrs keyed by the Scala
    parameter name (ModuleSerializable.scala:214 doSerializeModule
    reflection), e.g. Linear(inputSize, outputSize, withBias);
  * weights ride `parameters` ([weight, bias] order) with
    hasParameters=true (ModuleSerializable.scala:364 copyFromBigDL);
    pre-0.5.0 files use the deprecated weight/bias fields instead
    (ModuleSerializable.scala:336 copyWeightAndBias) — both are read;
  * containers recurse through subModules
    (ModuleSerializable.scala:381 ContainerSerializable).

BatchNorm running stats ride the module's attr map as tensor attrs
``runningMean``/``runningVar`` (+ per-batch ``saveMean``/``saveStd``
temporaries) — nn/BatchNormalization.scala:323 doLoadModule reads all
four unconditionally, :346 doSerializeModule writes them.  Both
directions are handled here: load copies them into the model's BN
state; save emits them (saveMean/saveStd zeroed, as after resize).
"""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from . import proto
from .proto import iter_fields, enc_bytes, enc_string, enc_int64
from .. import nn

_NS = "com.intel.analytics.bigdl.nn."

# DataType enum (bigdl.proto)
_DT_FLOAT, _DT_DOUBLE, _DT_INT32, _DT_INT64, _DT_BOOL = 2, 3, 0, 1, 5
_DT_STRING = 4
_DT_TENSOR, _DT_ARRAY = 10, 15
_DT_NAME_ATTR_LIST = 14
_DT_MODULE = 13   # bigdl.proto DataType.MODULE (12 is INITMETHOD)


# --------------------------------------------------------------------- #
# wire decoding                                                          #
# --------------------------------------------------------------------- #
def _packed_varints(v, wire):
    if wire == 0:
        return [v]
    out, i = [], 0
    while i < len(v):
        n, i = proto._read_varint(v, i)
        out.append(n)
    return out


def _sint(v):
    return v if v < 1 << 62 else v - (1 << 64)


def _decode_storage(buf):
    """TensorStorage -> (np.ndarray | None, storage_id)."""
    dtype = np.float32
    data = None
    sid = 0
    for f, w, v in iter_fields(buf):
        if f == 1 and w == 0:
            dtype = {_DT_FLOAT: np.float32, _DT_DOUBLE: np.float64,
                     _DT_INT32: np.int32, _DT_INT64: np.int64,
                     _DT_BOOL: np.bool_}.get(v, np.float32)
        elif f == 2:  # float_data (packed fixed32 under proto3)
            if w == 2:
                data = np.frombuffer(v, "<f4").astype(np.float32)
            else:   # unpacked single float (iter_fields decodes fixed32)
                data = np.concatenate(
                    [data if data is not None else np.zeros(0, np.float32),
                     [v]]).astype(np.float32)
        elif f == 3 and w == 2:  # double_data
            data = np.frombuffer(v, "<f8")
        elif f == 6:  # int_data packed varints
            data = np.asarray(_packed_varints(v, w), np.int32)
        elif f == 7:  # long_data
            data = np.asarray([_sint(x) for x in _packed_varints(v, w)],
                              np.int64)
        elif f == 9 and w == 0:
            sid = v
    if data is not None:
        data = data.astype(dtype, copy=False)
    return data, sid


def _decode_tensor(buf, storages: Dict[int, np.ndarray]):
    """BigDLTensor -> np.ndarray (resolving shared storage by id)."""
    sizes: List[int] = []
    offset = 0
    tid = None
    data = None
    sid = None
    is_scalar = False
    for f, w, v in iter_fields(buf):
        if f == 2:
            sizes.extend(_packed_varints(v, w))
        elif f == 4 and w == 0:
            offset = v
        elif f == 7 and w == 0:
            is_scalar = bool(v)
        elif f == 8 and w == 2:
            data, sid = _decode_storage(v)
        elif f == 9 and w == 0:
            tid = v
    if data is None and sid is not None:
        data = storages.get(sid)
    if data is None:
        return None
    if sid is not None and sid not in storages:
        storages[sid] = data
    start = max(offset - 1, 0)   # reference storageOffset is 1-based
    n = int(np.prod(sizes)) if sizes else 1
    flat = np.asarray(data).reshape(-1)[start:start + n]
    if is_scalar or not sizes:
        return flat.reshape(())
    return flat.reshape(sizes)


def _decode_attr(buf, storages):
    """AttrValue -> python value (subset used by module files)."""
    dtype = None
    raw = {}
    for f, w, v in iter_fields(buf):
        raw.setdefault(f, []).append((w, v))
        if f == 1 and w == 0:
            dtype = v
    def first(f):
        return raw[f][0][1] if f in raw else None
    if 3 in raw:
        return _sint(first(3))
    if 4 in raw:
        return _sint(first(4))
    if 5 in raw:
        return float(first(5))    # iter_fields already decodes fixed32
    if 6 in raw:
        return float(first(6))    # ... and fixed64
    if 7 in raw:
        return first(7).decode("utf-8")
    if 8 in raw:
        return bool(first(8))
    if 10 in raw:
        return _decode_tensor(first(10), storages)
    if 13 in raw:  # nested BigDLModule (bigDLModuleValue)
        return _decode_module(first(13), storages)
    if 14 in raw:  # NameAttrList
        return _decode_name_attr_list(first(14), storages)
    if 15 in raw:  # ArrayValue
        return _decode_array(first(15), storages)
    if 16 in raw:  # DataFormat enum
        return "NHWC" if first(16) == 1 else "NCHW"
    if dtype is not None and dtype not in (_DT_TENSOR,):
        return None
    return None


def _decode_array(buf, storages):
    out = []
    for f, w, v in iter_fields(buf):
        if f == 3:
            out.extend(_sint(x) for x in _packed_varints(v, w))
        elif f == 4:
            out.extend(_sint(x) for x in _packed_varints(v, w))
        elif f == 5 and w == 2:
            out.extend(np.frombuffer(v, "<f4").tolist())
        elif f == 6 and w == 2:
            out.extend(np.frombuffer(v, "<f8").tolist())
        elif f == 7 and w == 2:
            out.append(v.decode("utf-8"))
        elif f == 8:
            out.extend(bool(x) for x in _packed_varints(v, w))
        elif f == 10 and w == 2:
            out.append(_decode_tensor(v, storages))
        elif f == 13 and w == 2:   # Array(BigDLModule)
            out.append(_decode_module(v, storages))
    return out


def _decode_name_attr_list(buf, storages):
    name = ""
    attrs = {}
    for f, w, v in iter_fields(buf):
        if f == 1 and w == 2:
            name = v.decode("utf-8")
        elif f == 2 and w == 2:
            k = val = None
            for f2, w2, v2 in iter_fields(v):
                if f2 == 1 and w2 == 2:
                    k = v2.decode("utf-8")
                elif f2 == 2 and w2 == 2:
                    val = _decode_attr(v2, storages)
            if k is not None:
                attrs[k] = val
    return {"name": name, "attr": attrs}


def _decode_module(buf, storages):
    m = {"name": "", "type": "", "subs": [], "attr": {}, "params": [],
         "pres": [], "weight": None, "bias": None, "has_params": False}
    # two passes: global_storage (attr map) must be registered before
    # parameter tensors that reference it — attrs can appear after
    # subModules on the wire, so collect first
    raw_attrs = []
    for f, w, v in iter_fields(buf):
        if f == 1 and w == 2:
            m["name"] = v.decode("utf-8")
        elif f == 7 and w == 2:
            m["type"] = v.decode("utf-8")
        elif f == 8 and w == 2:
            raw_attrs.append(v)
    # attr map: key=1, value=2
    pending = []
    for v in raw_attrs:
        k = raw = None
        for f2, w2, v2 in iter_fields(v):
            if f2 == 1 and w2 == 2:
                k = v2.decode("utf-8")
            elif f2 == 2 and w2 == 2:
                raw = v2
        if k == "global_storage" and raw is not None:
            m["attr"][k] = _decode_attr(raw, storages)  # registers storages
        elif k is not None:
            pending.append((k, raw))
    for k, raw in pending:
        m["attr"][k] = _decode_attr(raw, storages) if raw is not None \
            else None
    for f, w, v in iter_fields(buf):
        if f == 2 and w == 2:
            m["subs"].append(_decode_module(v, storages))
        elif f == 3 and w == 2:
            m["weight"] = _decode_tensor(v, storages)
        elif f == 4 and w == 2:
            m["bias"] = _decode_tensor(v, storages)
        elif f == 5 and w == 2:       # preModules (graph wiring)
            m["pres"].append(v.decode("utf-8"))
        elif f == 15 and w == 0:
            m["has_params"] = bool(v)
        elif f == 16 and w == 2:
            m["params"].append(_decode_tensor(v, storages))
    return m


# --------------------------------------------------------------------- #
# module factory (≙ ModuleSerializer's registered deserializers)         #
# --------------------------------------------------------------------- #
def _mk_linear(a):
    return nn.Linear(int(a["inputSize"]), int(a["outputSize"]),
                     with_bias=a.get("withBias", True))


def _mk_conv(a):
    return nn.SpatialConvolution(
        int(a["nInputPlane"]), int(a["nOutputPlane"]),
        int(a["kernelW"]), int(a["kernelH"]),
        int(a.get("strideW", 1)), int(a.get("strideH", 1)),
        int(a.get("padW", 0)), int(a.get("padH", 0)),
        n_group=int(a.get("nGroup", 1)),
        with_bias=a.get("withBias", True))


def _mk_maxpool(a):
    return nn.SpatialMaxPooling(
        int(a["kW"]), int(a["kH"]), int(a.get("dW", 1)), int(a.get("dH", 1)),
        int(a.get("padW", 0)), int(a.get("padH", 0)))


def _mk_avgpool(a):
    return nn.SpatialAveragePooling(
        int(a["kW"]), int(a["kH"]), int(a.get("dW", 1)), int(a.get("dH", 1)),
        int(a.get("padW", 0)), int(a.get("padH", 0)),
        count_include_pad=a.get("countIncludePad", True))


def _mk_bn(a):
    return nn.SpatialBatchNormalization(
        int(a["nOutput"]), eps=float(a.get("eps", 1e-5)),
        momentum=float(a.get("momentum", 0.1)),
        affine=a.get("affine", True))


def _mk_bn1d(a):
    return nn.BatchNormalization(
        int(a["nOutput"]), eps=float(a.get("eps", 1e-5)),
        momentum=float(a.get("momentum", 0.1)),
        affine=a.get("affine", True))


# --------------------------------------------------------------------- #
# recurrent modules — one-way READ transform.                            #
# nn/Recurrent.scala:604 serializes topology/preTopology as module       #
# attrs; cells go through Cell.scala:242 CellSerializer (ctor attrs +    #
# the internal Linear-graph under the "cell" attr + flat parameters).    #
# We rebuild our fused cells from the Linear weights instead of          #
# executing the reference graph.                                         #
# --------------------------------------------------------------------- #
_CELL_TYPES = {"LSTM", "GRU", "RnnCell"}


def _checked_cell_p(tree):
    """The cell's dropout p, raising for types whose p>0 wire layout
    (per-gate Linear graphs) the reader does not rebuild."""
    t = _short_type(tree["type"])
    p = float(tree["attr"].get("p") or 0.0)
    if p != 0.0 and t not in ("LSTM", "GRU"):
        raise ValueError(
            f".bigdl {t} with dropout p={p} serializes per-gate Linear "
            "graphs; only LSTM/GRU read the p>0 layout")
    return p


def _build_activation(tree, where):
    """Build a cell activation module; only stateless ones are usable
    inside our fused cells (a PReLU's weight would have no params slot)."""
    mod = _build(tree)
    import jax
    if mod.init(jax.random.PRNGKey(0)):
        raise ValueError(
            f".bigdl {where}: parameterized activation "
            f"{_short_type(tree['type'])} is not supported in fused cells")
    return mod


def _cell_activation(a, key, default_type, where):
    """Return the non-default activation module from attr `key`, or
    None when absent / the reference default (ctor fills defaults in,
    so the attr is present even for untouched cells)."""
    tr = a.get(key)
    if not isinstance(tr, dict) or _short_type(tr["type"]) == default_type:
        return None
    return _build_activation(tr, where)


def _build_cell(tree):
    t = _short_type(tree["type"])
    a = tree["attr"]
    cell_p = _checked_cell_p(tree)
    if t == "LSTM":
        cell = nn.LSTM(
            int(a["inputSize"]), int(a["hiddenSize"]), p=cell_p,
            activation=_cell_activation(a, "activation", "Tanh", t),
            inner_activation=_cell_activation(
                a, "innerActivation", "Sigmoid", t))
    elif t == "GRU":
        cell = nn.GRU(
            int(a["inputSize"]), int(a["outputSize"]), p=cell_p,
            activation=_cell_activation(a, "activation", "Tanh", t),
            inner_activation=_cell_activation(
                a, "innerActivation", "Sigmoid", t))
    elif t == "RnnCell":
        act_tree = a.get("activation")
        act = _build_activation(act_tree, t) \
            if isinstance(act_tree, dict) else None
        cell = nn.RnnCell(int(a["inputSize"]), int(a["hiddenSize"]),
                          activation=act)
    elif t == "MultiRNNCell":
        cells = a.get("cells") or []
        if not cells:
            raise ValueError(
                ".bigdl MultiRNNCell: missing or empty 'cells' attr")
        cell = nn.MultiRNNCell([_build_cell(c) for c in cells])
    else:
        raise ValueError(f"unsupported recurrent cell {tree['type']!r}")
    if tree["name"]:
        cell.set_name(tree["name"])
    return cell


def _hidden_shapes_ok(t, a, own):
    """Would `own` still satisfy the cell's hidden-weight shape scan?
    Used to validate the lead-match drop when includePreTopology is
    absent from the wire (older files)."""
    mats = [m for m in own if m.ndim == 2]
    if t == "LSTM":
        h = int(a["hiddenSize"])
        return any(m.shape[0] == 4 * h for m in mats)
    if t == "GRU":
        h = int(a["outputSize"])
        return (any(m.shape[0] == 2 * h for m in mats)
                and any(m.shape == (h, h) for m in mats))
    if t == "RnnCell":
        h = int(a["hiddenSize"])
        return any(m.shape == (h, h) for m in mats)
    return True


def _split_gate_linears(own, what):
    """Classify a p>0 cell's flat params into (input-Linear (w, b)
    pairs, hidden-Linear weights): with dropout the reference builds
    per-gate Sequential(Dropout, Linear) stacks where every
    input-to-gate Linear carries a bias and every hidden-to-gate Linear
    is withBias=false (LSTM.scala:88-116, GRU.scala:90-105) — the bias
    adjacency disambiguates even when inputSize == hiddenSize."""
    pairs, hmats = [], []
    i = 0
    while i < len(own):
        m = own[i]
        if m.ndim == 2 and i + 1 < len(own) and own[i + 1].ndim == 1 \
                and own[i + 1].shape == (m.shape[0],):
            pairs.append((m, own[i + 1]))
            i += 2
        elif m.ndim == 2:
            hmats.append(m)
            i += 1
        else:
            raise ValueError(
                f".bigdl {what} (p>0): unexpected rank-{m.ndim} entry "
                "in the cell's flat params")
    return pairs, hmats


def _cell_weights_dropout(tree, t, a):
    """p>0 wire layout (no preTopology; per-gate Linears in the cell's
    own flat params) -> our fused weight dicts."""
    own = [np.asarray(q, np.float32) for q in tree["params"]]
    pairs, hmats = _split_gate_linears(own, t)
    if t == "LSTM":
        h = int(a["hiddenSize"])
        if len(pairs) != 4 or len(hmats) != 4 \
                or any(w.shape[0] != h for w, _ in pairs) \
                or any(m.shape != (h, h) for m in hmats):
            raise ValueError(
                f".bigdl LSTM(p>0): expected 4 biased input Linears + "
                f"4 hidden mats of width {h}, got "
                f"{[w.shape for w, _ in pairs]} / "
                f"{[m.shape for m in hmats]}")
        # reference per-gate order is [i, g, f, o] (JoinTable of the
        # buildGates Linears); fused order is [i, f, g, o]
        perm = (0, 2, 1, 3)
        w_pre = np.concatenate([pairs[k][0] for k in perm], 0)
        bias = np.concatenate([pairs[k][1] for k in perm], 0)
        w_h = np.concatenate([hmats[k] for k in perm], 0)
        return tree["name"], {"weight_i": w_pre.T.copy(),
                              "weight_h": w_h.T.copy(), "bias": bias}
    # GRU: i2g [r, z] + candidate f2g carry biases; h2g [r, z] +
    # candidate linear2 don't (GRU.scala:90-105, :132-146)
    h = int(a["outputSize"])
    if len(pairs) != 3 or len(hmats) != 3 \
            or any(w.shape[0] != h for w, _ in pairs) \
            or any(m.shape != (h, h) for m in hmats):
        raise ValueError(
            f".bigdl GRU(p>0): expected 3 biased input Linears + 3 "
            f"hidden mats of width {h}, got "
            f"{[w.shape for w, _ in pairs]} / {[m.shape for m in hmats]}")
    (w_r, b_r), (w_z, b_z), (w_n, b_n) = pairs
    h_r, h_z, h_n = hmats
    return tree["name"], {
        "gates": {"weight_i": np.concatenate([w_r, w_z], 0).T.copy(),
                  "weight_h": np.concatenate([h_r, h_z], 0).T.copy(),
                  "bias": np.concatenate([b_r, b_z], 0)},
        "new": {"weight_i": w_n.T.copy(), "weight_h": h_n.T.copy(),
                "bias": b_n}}


def _pick_mat(mats, pred, what, t):
    for m in mats:
        if pred(m):
            return m
    raise ValueError(f".bigdl {t}: no {what} weight in cell parameters")


def _cell_weights(tree, split_pre_bias=False):
    """Reference cell wire tree -> (cell_name, our fused weight dict).

    The Linear weights live in two places: the input-to-gate Linear
    under the cell's "preTopology" module attr (LSTM.scala:77-81,
    GRU.scala:80-83, RNN.scala:62-67), and the hidden-to-gate Linears
    in the cell module's own flat parameter list (Cell.parameters() =
    the internal graph's Linears in topo order).  Reference Linear
    weights are (out, in); our fused layout is (in, out).

    ``split_pre_bias=True`` (the Recurrent(BatchNormParams) load path)
    keeps the preTopology Linear bias OUT of the fused step bias and
    returns a 4-tuple (name, weights, pre_bias, perm) instead — the
    pre-bias is applied BEFORE the BatchNorm (Recurrent.scala:119), and
    ``perm`` re-orders any per-feature vector of the projection (BN
    gamma/beta/running stats) from the reference's gate order onto our
    fused one.
    """
    t = _short_type(tree["type"])
    a = tree["attr"]
    if _checked_cell_p(tree) != 0.0:
        # dropout form: no preTopology, per-gate Linears in flat params
        if split_pre_bias:
            raise ValueError(
                f".bigdl {t}: BatchNormParams with p > 0 has no wire "
                "form (the reference's p > 0 cells have no preTopology)")
        return _cell_weights_dropout(tree, t, a)
    pre = a.get("preTopology")
    pre_params = (pre or {}).get("params") or []
    if not pre_params:
        raise ValueError(
            f".bigdl {t}: preTopology input Linear weights are missing")
    w_pre = np.asarray(pre_params[0], np.float32)
    b_pre = np.asarray(pre_params[1], np.float32) \
        if len(pre_params) > 1 else None
    # a cell with includePreTopology=true (RecurrentDecoder) carries the
    # preTopology Linear FIRST in its own flat params (Cell.parameters =
    # Sequential(pre, cell)) — drop them positionally so the shape-driven
    # hidden-weight scan can't pick the input Linear when input size ==
    # hidden size (the decoder's feedback case).  Keyed on the cell's
    # serialized includePreTopology attr (CellSerializer writes it).
    # When the attr is ABSENT (older files) the lead-match heuristic is
    # only trusted if the remaining params still carry the expected
    # hidden-weight shapes — a plain cell with genuinely tied input
    # weights (lead matches by value, but those ARE its hidden weights)
    # keeps its full list instead of being mis-dropped.
    own = [np.asarray(q, np.float32) for q in tree["params"]]
    n_pre = len(pre_params)
    inc = a.get("includePreTopology")
    lead_matches = (
        len(own) > n_pre
        and all(own[i].shape == np.shape(pre_params[i])
                for i in range(n_pre))
        and all(np.array_equal(own[i],
                               np.asarray(pre_params[i], np.float32))
                for i in range(n_pre)))
    if inc:
        if not lead_matches:
            raise ValueError(
                f".bigdl {t}: includePreTopology=true but the flat "
                "params do not lead with the preTopology weights")
        own = own[n_pre:]
    elif inc is None and lead_matches \
            and _hidden_shapes_ok(t, a, own[n_pre:]):
        own = own[n_pre:]
    if t == "LSTM":
        h = int(a["hiddenSize"])
        w_h = _pick_mat(own, lambda m: m.ndim == 2 and m.shape[0] == 4 * h,
                        "hidden-to-gate", t)
        # reference gate chunks are [i, g, f, o] (LSTM.scala:134-147
        # buildGates Select order); our fused order is [i, f, g, o]
        perm = (0, 2, 1, 3)

        def reorder(m):
            return np.concatenate([m[k * h:(k + 1) * h] for k in perm], 0)

        bias = reorder(b_pre) if b_pre is not None \
            else np.zeros(4 * h, np.float32)
        wd = {"weight_i": reorder(w_pre).T.copy(),
              "weight_h": reorder(w_h).T.copy(), "bias": bias}
        if split_pre_bias:
            wd["bias"] = np.zeros(4 * h, np.float32)
            return tree["name"], wd, bias, reorder
        return tree["name"], wd
    if t == "GRU":
        h = int(a["outputSize"])
        # pre chunks are [r, z, n] (GRU.scala:107 Narrow + :137 f2g)
        w_h2g = _pick_mat(own, lambda m: m.ndim == 2 and m.shape[0] == 2 * h,
                          "hidden-to-rz", t)
        w_new = _pick_mat(own, lambda m: m.ndim == 2 and m.shape == (h, h),
                          "hidden-to-new", t)
        bias = b_pre if b_pre is not None else np.zeros(3 * h, np.float32)
        wd = {
            "gates": {"weight_i": w_pre[:2 * h].T.copy(),
                      "weight_h": w_h2g.T.copy(), "bias": bias[:2 * h]},
            "new": {"weight_i": w_pre[2 * h:].T.copy(),
                    "weight_h": w_new.T.copy(), "bias": bias[2 * h:]}}
        if split_pre_bias:
            # projection order [r, z, n] == our [gates(2h), new(h)] concat
            wd["gates"]["bias"] = np.zeros(2 * h, np.float32)
            wd["new"]["bias"] = np.zeros(h, np.float32)
            return tree["name"], wd, bias, lambda v: v
        return tree["name"], wd
    if t == "RnnCell":
        h = int(a["hiddenSize"])
        w_h = _pick_mat(own, lambda m: m.ndim == 2 and m.shape == (h, h),
                        "hidden-to-hidden", t)
        # reference has separate input/hidden biases; ours is one sum
        b_h = next((m for m in own if m.ndim == 1 and m.shape == (h,)), None)
        wd = {"weight_i": w_pre.T.copy(), "weight_h": w_h.T.copy()}
        if split_pre_bias:
            wd["bias"] = b_h if b_h is not None else np.zeros(h, np.float32)
            pre = b_pre if b_pre is not None else np.zeros(h, np.float32)
            return tree["name"], wd, pre, lambda v: v
        bias = np.zeros(h, np.float32)
        if b_pre is not None:
            bias = bias + b_pre
        if b_h is not None:
            bias = bias + b_h
        wd["bias"] = bias
        return tree["name"], wd
    raise ValueError(f"unsupported recurrent cell {tree['type']!r}")


def _build_recurrent_decoder(tree):
    a = tree["attr"]
    if a.get("bnorm"):
        raise ValueError(
            ".bigdl RecurrentDecoder(BatchNormParams) is not supported")
    topo = a.get("topology")
    if not isinstance(topo, dict):
        raise ValueError(".bigdl RecurrentDecoder: missing topology attr")
    dec = nn.RecurrentDecoder(int(a["seqLength"]), _build_cell(topo))
    if tree["name"]:
        dec.set_name(tree["name"])
    return dec


def _bn_params_from_attrs(a):
    """Recurrent/BiRecurrent bnorm attrs -> nn.BatchNormParams
    (Recurrent.scala:738-768 doLoadModule reads bnormEps/bnormMomentum/
    bnormAffine; gamma/beta come from the serialized BN module itself,
    so init_weight/init_bias are not needed here)."""
    eps = a.get("bnormEps")
    mom = a.get("bnormMomentum")
    aff = a.get("bnormAffine")
    # None-checks, not `or`: momentum=0.0 (frozen running stats) and
    # affine=False are legitimate serialized values
    return nn.BatchNormParams(
        eps=1e-5 if eps is None else float(eps),
        momentum=0.1 if mom is None else float(mom),
        affine=True if aff is None else bool(aff))


def _recurrent_bn_tree(rec_tree):
    """Find the BatchNormalization module tree under a bnorm=true
    Recurrent's preTopology attr (Recurrent.scala:111-119 wraps it as
    Sequential[TimeDistributed(pre), TimeDistributed(BN)])."""
    stack = [rec_tree["attr"].get("preTopology")]
    while stack:
        t = stack.pop()
        if not isinstance(t, dict):
            continue
        st = _short_type(t["type"])
        if st in ("BatchNormalization", "SpatialBatchNormalization"):
            return t
        inner = t["attr"].get("layer") if st == "TimeDistributed" else None
        if inner is not None:
            stack.append(inner)
        stack.extend(t.get("subs") or [])
    raise ValueError(
        ".bigdl Recurrent(bnorm): no BatchNormalization module found "
        "under the preTopology attr")


def _build_recurrent(tree):
    a = tree["attr"]
    topo = a.get("topology")
    if not isinstance(topo, dict):
        raise ValueError(".bigdl Recurrent: missing topology cell attr")
    bn = _bn_params_from_attrs(a) if a.get("bnorm") else None
    rec = nn.Recurrent(_build_cell(topo), batch_norm_params=bn,
                       mask_zero=bool(a.get("maskZero")))
    if tree["name"]:
        rec.set_name(tree["name"])
    return rec


def _birnn_recurrents(birnn):
    """BiRecurrent's internal Sequential (BiRecurrent.scala:48-66):
    [input-fanout, ParallelTable[fwd Recurrent, Sequential[Reverse,
    rev Recurrent, Reverse]], merge] -> (fwd tree, rev tree)."""
    for sub in birnn.get("subs", []):
        if _short_type(sub["type"]) == "ParallelTable" \
                and len(sub["subs"]) == 2:
            fwd = sub["subs"][0]
            rev = next((x for x in sub["subs"][1].get("subs", [])
                        if _short_type(x["type"]) == "Recurrent"), None)
            if _short_type(fwd["type"]) == "Recurrent" and rev is not None:
                return fwd, rev
    raise ValueError(
        ".bigdl BiRecurrent: unrecognized birnn layout (expected "
        "ParallelTable of forward Recurrent + Reverse/Recurrent/Reverse)")


def _build_birecurrent(tree):
    a = tree["attr"]
    birnn = a.get("birnn")
    if not isinstance(birnn, dict):
        raise ValueError(".bigdl BiRecurrent: missing birnn attr")
    fwd_t, _ = _birnn_recurrents(birnn)
    subs = birnn.get("subs", [])
    merge_t = subs[-1] if subs else None
    merge = None
    if merge_t is not None and _short_type(merge_t["type"]) not in (
            "CAddTable",):
        merge = _build(merge_t)
    # isSplitInput rides the ctor attr when present; older files show it
    # structurally as a leading BifurcateSplitTable (BiRecurrent.scala:50)
    split = bool(a.get("isSplitInput")) or any(
        _short_type(s["type"]) == "BifurcateSplitTable"
        for s in subs[:1])
    # bnorm: each direction's internal Recurrent carries its own
    # BatchNorm (BiRecurrent.scala:45-46); config attrs ride the
    # BiRecurrent node (bnormEps/bnormMomentum, BiRecurrent.scala:178-193)
    bn = _bn_params_from_attrs(a) if a.get("bnorm") else None
    m = nn.BiRecurrent(merge=merge, cell=_build_cell(
        fwd_t["attr"]["topology"]), is_split_input=split,
        batch_norm_params=bn)
    if tree["name"]:
        m.set_name(tree["name"])
    return m


def _assign_cell_weights(params, cell_tree, target=None,
                         target_tree=None):
    """Assign a serialized cell's weights into `params`.  `target`
    renames the destination slot (BiRecurrent's backward cell is a
    "<fwd>_bwd" rename of the forward one); for a MultiRNNCell the
    renames apply per sub-cell, so `target_tree` carries the FORWARD
    topology whose sub-cell names the built model used."""
    import jax
    if _short_type(cell_tree["type"]) == "MultiRNNCell":
        subs = cell_tree["attr"].get("cells") or []
        if target is None:
            for sub in subs:
                _assign_cell_weights(params, sub)
            return
        fwd_subs = (target_tree or {}).get("attr", {}).get("cells") or []
        if len(fwd_subs) != len(subs):
            raise ValueError(
                ".bigdl BiRecurrent over MultiRNNCell: forward/backward "
                f"stacks differ ({len(fwd_subs)} vs {len(subs)} cells)")
        for sub, fsub in zip(subs, fwd_subs):
            _assign_cell_weights(params, sub,
                                 target=f"{fsub['name']}_bwd")
        return
    cname, wd = _cell_weights(cell_tree)
    if target is not None:
        cname = target
    if cname not in params:
        raise ValueError(
            f".bigdl recurrent cell {cname!r} has no params slot in the "
            "built model")
    want = jax.tree_util.tree_map(np.shape, params[cname])
    got = jax.tree_util.tree_map(np.shape, wd)
    if want != got:
        raise ValueError(
            f".bigdl cell {cname!r}: weight shapes {got} do not match "
            f"the built cell {want}")
    params[cname] = wd


def _assign_recurrent_bn(params, state, rec_tree, rec_slot,
                         cell_slot=None):
    """bnorm=true Recurrent tree -> cell weights (preTopology bias split
    OUT of the fused step bias: it applies BEFORE the BatchNorm,
    Recurrent.scala:119), the built Recurrent's own ``bias_pre``, and
    the BN's gamma/beta + running stats — all per-feature vectors of the
    projection permuted from the reference's gate order onto our fused
    one.  ``rec_slot`` names the built Recurrent's own params slot
    (BiRecurrent runners are '<bi>_f'/'<bi>_b'); ``cell_slot`` renames
    the cell slot (the backward direction's '<fwd>_bwd')."""
    import jax
    topo = rec_tree["attr"]["topology"]
    cname, wd, pre_bias, perm = _cell_weights(topo, split_pre_bias=True)
    if cell_slot is not None:
        cname = cell_slot
    for slot in (cname, rec_slot):
        if slot not in params:
            raise ValueError(
                f".bigdl Recurrent(bnorm): no params slot {slot!r} in "
                "the built model")
    want = jax.tree_util.tree_map(np.shape, params[cname])
    got = jax.tree_util.tree_map(np.shape, wd)
    if want != got:
        raise ValueError(
            f".bigdl cell {cname!r}: weight shapes {got} do not match "
            f"the built cell {want}")
    params[cname] = wd
    own = dict(params[rec_slot])
    own["bias_pre"] = np.asarray(pre_bias, np.float32).reshape(
        np.shape(own["bias_pre"]))
    params[rec_slot] = own
    bn_tree = _recurrent_bn_tree(rec_tree)
    bn_slot = f"{rec_slot}_bn"
    arrs = bn_tree["params"] if bn_tree["has_params"] else \
        [t for t in (bn_tree["weight"], bn_tree["bias"]) if t is not None]
    if arrs and bn_slot in params:
        own_bn = dict(params[bn_slot])
        keys = nn.Module._weights_order(own_bn)
        for k, arr in zip(keys, arrs):
            own_bn[k] = perm(np.asarray(arr, np.float32).reshape(
                np.shape(own_bn[k])))
        params[bn_slot] = own_bn
    st = state.get(bn_slot)
    if isinstance(st, dict):
        st = dict(st)
        for ak, sk in (("runningMean", "running_mean"),
                       ("runningVar", "running_var")):
            val = bn_tree["attr"].get(ak)
            if val is not None and sk in st:
                st[sk] = perm(np.asarray(val, np.float32).reshape(
                    np.shape(st[sk])))
        state[bn_slot] = st


_FACTORY = {
    "Linear": _mk_linear,
    "SpatialConvolution": _mk_conv,
    "SpatialMaxPooling": _mk_maxpool,
    "SpatialAveragePooling": _mk_avgpool,
    "SpatialBatchNormalization": _mk_bn,
    "BatchNormalization": _mk_bn1d,
    "TimeDistributed": lambda a: nn.TimeDistributed(
        _build(a["layer"]), mask_zero=bool(a.get("maskZero"))),
    "LookupTable": lambda a: nn.LookupTable(
        int(a["nIndex"]), int(a["nOutput"]),
        padding_value=float(a.get("paddingValue", 0.0) or 0.0),
        # reference reflection always writes maxNorm; its default is
        # Double.MaxValue == "no renorm" — map to None or every forward
        # pays a useless per-row norm
        max_norm=(None if a.get("maxNorm") is None
                  or float(a["maxNorm"]) >= 1e300 else
                  float(a["maxNorm"])),
        norm_type=float(a.get("normType") or 2.0),
        mask_zero=bool(a.get("maskZero", False))),
    "SpatialFullConvolution": lambda a: nn.SpatialFullConvolution(
        int(a["nInputPlane"]), int(a["nOutputPlane"]),
        int(a["kW"]), int(a["kH"]),
        int(a.get("dW", 1)), int(a.get("dH", 1)),
        int(a.get("padW", 0)), int(a.get("padH", 0)),
        int(a.get("adjW", 0)), int(a.get("adjH", 0)),
        n_group=int(a.get("nGroup", 1)),
        no_bias=bool(a.get("noBias", False))),
    "SpatialDilatedConvolution": lambda a: nn.SpatialDilatedConvolution(
        int(a["nInputPlane"]), int(a["nOutputPlane"]),
        int(a["kW"]), int(a["kH"]),
        int(a.get("dW", 1)), int(a.get("dH", 1)),
        int(a.get("padW", 0)), int(a.get("padH", 0)),
        int(a.get("dilationW", 1)), int(a.get("dilationH", 1))),
    "TemporalConvolution": lambda a: nn.TemporalConvolution(
        int(a["inputFrameSize"]), int(a["outputFrameSize"]),
        int(a["kernelW"]), int(a.get("strideW", 1))),
    "SpatialZeroPadding": lambda a: nn.SpatialZeroPadding(
        int(a.get("padLeft", 0)), int(a.get("padRight", 0)),
        int(a.get("padTop", 0)), int(a.get("padBottom", 0))),
    "Padding": lambda a: (
        (_ for _ in ()).throw(ValueError(
            ".bigdl Padding with nIndex != 1 is not supported"))
        if int(a.get("nIndex", 1) or 1) != 1 else nn.Padding(
            int(a["dim"]), int(a["pad"]), int(a.get("nInputDim", 0)),
            float(a.get("value", 0.0) or 0.0))),
    "SpatialCrossMapLRN": lambda a: nn.SpatialCrossMapLRN(
        int(a.get("size", 5)), float(a.get("alpha", 1.0)),
        float(a.get("beta", 0.75)), float(a.get("k", 1.0))),
    "ReLU": lambda a: nn.ReLU(),
    "Tanh": lambda a: nn.Tanh(),
    "Sigmoid": lambda a: nn.Sigmoid(),
    "SoftMax": lambda a: nn.SoftMax(),
    "LogSoftMax": lambda a: nn.LogSoftMax(),
    "Identity": lambda a: nn.Identity(),
    "Dropout": lambda a: nn.Dropout(float(a.get("initP", 0.5))),
    "Reshape": lambda a: nn.Reshape(
        [int(s) for s in a.get("size", [])],
        batch_mode=a.get("batchMode")),
    "View": lambda a: nn.View([int(s) for s in a.get("sizes", [])]),
    "JoinTable": lambda a: nn.JoinTable(
        int(a.get("dimension", 1)), int(a.get("nInputDims", -1))),
    "CAddTable": lambda a: nn.CAddTable(),
    "CMulTable": lambda a: nn.CMulTable(),
    "ELU": lambda a: nn.ELU(float(a.get("alpha", 1.0))),
    "PReLU": lambda a: nn.PReLU(int(a.get("nOutputPlane", 0))),
    "Abs": lambda a: nn.Abs(),
    "Power": lambda a: nn.Power(float(a.get("power", 1.0)),
                                float(a.get("scale", 1.0)),
                                float(a.get("shift", 0.0))),
    "Exp": lambda a: nn.Exp(),
    "Log": lambda a: nn.Log(),
    "HardTanh": lambda a: nn.HardTanh(float(a.get("minValue", -1.0)),
                                      float(a.get("maxValue", 1.0))),
    "Clamp": lambda a: nn.Clamp(float(a.get("min", -1.0)),
                                float(a.get("max", 1.0))),
    "SoftPlus": lambda a: nn.SoftPlus(float(a.get("beta", 1.0))),
    "SoftSign": lambda a: nn.SoftSign(),
    "LeakyReLU": lambda a: nn.LeakyReLU(float(a.get("negval", 0.01))),
    "ReLU6": lambda a: nn.ReLU6(),
    "Threshold": lambda a: nn.Threshold(float(a.get("th", 1e-6)),
                                        float(a.get("v", 0.0))),
    "MulConstant": lambda a: nn.MulConstant(float(a.get("scalar", 1.0))),
    "AddConstant": lambda a: nn.AddConstant(
        float(a.get("constant_scalar", 0.0))),
    "Squeeze": lambda a: nn.Squeeze(a.get("dim")),
    "Unsqueeze": lambda a: nn.Unsqueeze(int(a.get("pos", 1))),
    "Select": lambda a: nn.Select(int(a.get("dimension", a.get("dim", 1))),
                                  int(a.get("index", 1))),
    "Narrow": lambda a: nn.Narrow(int(a.get("dimension", 1)),
                                  int(a.get("offset", 1)),
                                  int(a.get("length", 1))),
    "Mean": lambda a: nn.Mean(int(a.get("dimension", 1)),
                              int(a.get("nInputDims", -1)),
                              a.get("squeeze", True)),
    "CMul": lambda a: nn.CMul([int(s) for s in a.get("size", [])]),
    "CAdd": lambda a: nn.CAdd([int(s) for s in a.get("size", [])]),
    "Mul": lambda a: nn.Mul(),
    "Normalize": lambda a: nn.Normalize(float(a.get("p", 2.0)),
                                        float(a.get("eps", 1e-10))),
    "GaussianDropout": lambda a: nn.GaussianDropout(
        float(a.get("rate", 0.5))),
    "GaussianNoise": lambda a: nn.GaussianNoise(
        float(a.get("stddev", 1.0))),
    "SoftMin": lambda a: nn.SoftMin(),
    "LogSigmoid": lambda a: nn.LogSigmoid(),
    "HardSigmoid": lambda a: nn.HardSigmoid(),
    "Echo": lambda a: nn.Echo(),
    "FlattenTable": lambda a: nn.FlattenTable(),
    "SelectTable": lambda a: nn.SelectTable(int(a.get("index", 1))),
    "NarrowTable": lambda a: nn.NarrowTable(int(a.get("offset", 1)),
                                            int(a.get("length", 1))),
    "MaskedSelect": lambda a: nn.MaskedSelect(),
    "Index": lambda a: nn.Index(int(a.get("dimension", 1))),
    "Sequential": lambda a: nn.Sequential(),
    "ConcatTable": lambda a: nn.ConcatTable(),
    "ParallelTable": lambda a: nn.ParallelTable(),
    "Concat": lambda a: nn.Concat(int(a.get("dimension", 1))),
}

_CONTAINERS = {"Sequential", "ConcatTable", "ParallelTable", "Concat"}


_GRAPHS = {"StaticGraph", "Graph", "DynamicGraph"}


def _short_type(full: str) -> str:
    return full.rsplit(".", 1)[-1]


def _build_graph(tree):
    """DAG module (nn/Graph.scala GraphSerializable: subModules carry
    preModules wiring; inputNames/outputNames attrs name the
    endpoints)."""
    from ..nn.graph import Graph as NNGraph, Node

    by_name = {sub["name"]: sub for sub in tree["subs"]}
    if len(by_name) != len(tree["subs"]):
        raise ValueError(
            ".bigdl graph: duplicate node names (shared-module graphs "
            "are not supported)")
    nodes = {}
    visiting = set()

    def node_of(nm):
        if nm in nodes:
            return nodes[nm]
        if nm in visiting:
            raise ValueError(f".bigdl graph: wiring cycle through {nm!r}")
        visiting.add(nm)
        sub = by_name[nm]
        pres = [node_of(p) for p in sub["pres"] if p in by_name]
        if _short_type(sub["type"]) == "Input":
            nodes[nm] = Node(None, [])
        else:
            nodes[nm] = Node(_build(sub), pres)
        visiting.discard(nm)
        return nodes[nm]

    for sub in tree["subs"]:
        node_of(sub["name"])
    in_names = tree["attr"].get("inputNames") or []
    out_names = tree["attr"].get("outputNames") or []
    if not in_names or not out_names:
        raise ValueError(".bigdl graph: missing inputNames/outputNames")
    g = NNGraph([nodes[n] for n in in_names],
                [nodes[n] for n in out_names])
    if tree["name"]:
        g.set_name(tree["name"])
    return g


def _fix_temporal_conv(mod, arrs):
    """Reference TemporalConvolution weight is (out, in*kW) with column
    k*inputFrameSize + i (TemporalConvolution.scala:63 unfold layout);
    ours is (out, in, kW)."""
    out = []
    for a in arrs:
        a = np.asarray(a, np.float32)
        if a.ndim == 2:         # the weight; bias passes through
            a = a.reshape(mod.output_frame_size, mod.kernel_w,
                          mod.input_frame_size).transpose(0, 2, 1)
        out.append(a)
    return out


def _unfix_temporal_conv(mod, arrs):
    """Inverse of :func:`_fix_temporal_conv` for the writer: our
    (out, in, kW) -> reference (out, in*kW) with column k*fin + i."""
    out = []
    for a in arrs:
        a = np.asarray(a, np.float32)
        if a.ndim == 3:
            a = a.transpose(0, 2, 1).reshape(a.shape[0], -1)
        out.append(a)
    return out


_WEIGHT_FIX = {"TemporalConvolution": _fix_temporal_conv}
_WEIGHT_UNFIX = {"TemporalConvolution": _unfix_temporal_conv}


def _build(tree):
    t = _short_type(tree["type"])
    if t in _GRAPHS:
        return _build_graph(tree)
    if t == "Recurrent":
        return _build_recurrent(tree)
    if t == "RecurrentDecoder":
        return _build_recurrent_decoder(tree)
    if t == "BiRecurrent":
        return _build_birecurrent(tree)
    if t in _CELL_TYPES or t == "MultiRNNCell":
        return _build_cell(tree)
    fac = _FACTORY.get(t)
    if fac is None:
        raise ValueError(
            f".bigdl module type {tree['type']!r} is not mapped; "
            f"supported: {sorted(_FACTORY) + sorted(_GRAPHS)}")
    mod = fac(tree["attr"])
    if tree["name"]:
        mod.set_name(tree["name"])
    if t in _CONTAINERS:
        for sub in tree["subs"]:
            mod.add(_build(sub))
    return mod


def _leaf_modules(tree):
    t = _short_type(tree["type"])
    if t in _CONTAINERS or t in _GRAPHS:
        for s in tree["subs"]:
            yield from _leaf_modules(s)
    elif t != "Input":
        yield tree


def load_bigdl(path: str):
    """Read a reference `.bigdl` model file into a bigdl_tpu Module
    (≙ Module.loadModule / ModuleLoader.loadFromFile)."""
    with open(path, "rb") as f:
        data = f.read()
    storages: Dict[int, np.ndarray] = {}
    tree = _decode_module(data, storages)
    model = _build(tree)
    params, state = model.init_params(0)
    # assign by MODULE NAME (params are keyed by it, and _build preserved
    # every serialized name) — robust to container vs graph traversal order
    _by_name = {m.name: m for m in model.modules()}

    def assign_leaf(sub):
        st = _short_type(sub["type"])
        if st in ("Recurrent", "RecurrentDecoder"):
            if sub["attr"].get("bnorm") and st == "Recurrent":
                _assign_recurrent_bn(params, state, sub,
                                     rec_slot=sub["name"])
                return
            # cell weights come from the topology attr's Linear layout,
            # not the Recurrent's own flat parameter list
            _assign_cell_weights(params, sub["attr"]["topology"])
            return

        if st == "BiRecurrent":
            fwd_t, rev_t = _birnn_recurrents(sub["attr"]["birnn"])
            fwd_name = fwd_t["attr"]["topology"]["name"]
            if sub["attr"].get("bnorm"):
                # per-direction BN: the runners' slots are
                # '<bi>_f'/'<bi>_b' (nn/recurrent.py BiRecurrent._runners)
                bi = sub["name"]
                _assign_recurrent_bn(params, state, fwd_t,
                                     rec_slot=f"{bi}_f")
                _assign_recurrent_bn(params, state, rev_t,
                                     rec_slot=f"{bi}_b",
                                     cell_slot=f"{fwd_name}_bwd")
                return
            _assign_cell_weights(params, fwd_t["attr"]["topology"])
            # the built model's backward cell is a rename of the forward
            # one ("<fwd>_bwd", nn/recurrent.py BiRecurrent._ensure_bwd);
            # the reference's reverse topology has its own name — assign
            # with the same shape/structure validation as the fwd cell
            _assign_cell_weights(params, rev_t["attr"]["topology"],
                                 target=f"{fwd_name}_bwd",
                                 target_tree=fwd_t["attr"]["topology"])
            return
        if st in _CELL_TYPES or st == "MultiRNNCell":
            _assign_cell_weights(params, sub)
            return
        if st == "TimeDistributed":
            # the weights belong to the wrapped layer (the "layer"
            # module attr); the TimeDistributed node's own flat list
            # mirrors them
            for inner in _leaf_modules(sub["attr"]["layer"]):
                assign_leaf(inner)
            return
        arrs = sub["params"] if sub["has_params"] else \
            [t for t in (sub["weight"], sub["bias"]) if t is not None]
        if not arrs:
            return
        name = sub["name"]
        if name not in params:
            raise ValueError(
                f".bigdl layer {name!r} carries parameters but the built "
                "model has no params under that name")
        own = dict(params[name])
        keys = [k for k in nn.Module._weights_order(own)]
        if len(arrs) > len(keys):
            raise ValueError(
                f"{name}: {len(arrs)} serialized parameters, module "
                f"has {len(keys)}")
        built = _by_name.get(name)
        fix = _WEIGHT_FIX.get(type(built).__name__) \
            if built is not None else None
        if fix is not None:
            arrs = fix(built, arrs)
        for k, arr in zip(keys, arrs):
            want = np.shape(own[k])
            own[k] = np.asarray(arr, np.float32).reshape(want)
        params[name] = own

    for sub in _leaf_modules(tree):
        assign_leaf(sub)
    # BN running statistics: tensor attrs on the BN module
    # (nn/BatchNormalization.scala:323 doLoadModule); descend through
    # TimeDistributed wrappers — their BN rides the 'layer' attr
    def _bn_trees(subtree):
        for leaf in _leaf_modules(subtree):
            if _short_type(leaf["type"]) == "TimeDistributed":
                yield from _bn_trees(leaf["attr"]["layer"])
            else:
                yield leaf

    for sub in _bn_trees(tree):
        if _short_type(sub["type"]) not in (
                "SpatialBatchNormalization", "BatchNormalization"):
            continue
        own_st = state.get(sub["name"])
        if not isinstance(own_st, dict):
            continue
        own_st = dict(own_st)
        for attr_key, st_key in (("runningMean", "running_mean"),
                                 ("runningVar", "running_var")):
            val = sub["attr"].get(attr_key)
            if val is not None and st_key in own_st:
                own_st[st_key] = np.asarray(val, np.float32).reshape(
                    np.shape(own_st[st_key]))
        state[sub["name"]] = own_st
    model.set_params(params, state)
    return model


# --------------------------------------------------------------------- #
# writer (≙ ModulePersister.saveToFile with ProtoStorageType)            #
# --------------------------------------------------------------------- #
def _enc_storage(arr: np.ndarray, sid: int) -> bytes:
    body = enc_int64(1, _DT_FLOAT)
    body += enc_bytes(2, np.ascontiguousarray(arr, "<f4").tobytes())
    body += enc_int64(9, sid)
    return body


def _enc_tensor_msg(arr: np.ndarray, tid: int, sid: int,
                    inline: bool) -> bytes:
    body = enc_int64(1, _DT_FLOAT)
    sizes = b"".join(enc_int64(2, d) for d in arr.shape)
    body += sizes
    body += enc_int64(4, 1)                  # storageOffset (1-based)
    body += enc_int64(5, arr.ndim)
    body += enc_int64(6, arr.size)
    st = _enc_storage(arr, sid) if inline else (
        enc_int64(1, _DT_FLOAT) + enc_int64(9, sid))
    body += enc_bytes(8, st)
    body += enc_int64(9, tid)
    return body


def _attr_entry(key: str, attr_body: bytes) -> bytes:
    return enc_bytes(8, enc_string(1, key) + enc_bytes(2, attr_body))


def _attr_int(v: int) -> bytes:
    return enc_int64(1, _DT_INT32) + enc_int64(3, v & ((1 << 64) - 1))


def _alloc_tensor(arr, counter, global_entries) -> bytes:
    """Allocate tensor+storage ids, stash inline data in global_storage,
    return the non-inline (storage-referencing) tensor message."""
    arr = np.asarray(arr, np.float32)
    counter[0] += 1
    tid = counter[0]
    counter[0] += 1
    sid = counter[0]
    global_entries[str(tid)] = _enc_tensor_msg(arr, tid, sid, inline=True)
    return _enc_tensor_msg(arr, tid, sid, inline=False)


def _attr_tensor(arr, counter, global_entries) -> bytes:
    """Tensor AttrValue; data rides global_storage like parameters do."""
    return enc_int64(1, _DT_TENSOR) + enc_bytes(
        10, _alloc_tensor(arr, counter, global_entries))


def _attr_double(v: float) -> bytes:
    return enc_int64(1, _DT_DOUBLE) + proto.enc_double(6, v)


def _attr_bool(v: bool) -> bytes:
    return enc_int64(1, _DT_BOOL) + enc_int64(8, 1 if v else 0)


def _attr_int_array(vals) -> bytes:
    arr = enc_int64(1, len(list(vals))) + enc_int64(2, _DT_INT32)
    for v in vals:
        arr += enc_int64(3, v & ((1 << 64) - 1))
    return enc_int64(1, _DT_ARRAY) + enc_bytes(15, arr)


def _attr_str_array(vals) -> bytes:
    vals = list(vals)
    arr = enc_int64(1, len(vals)) + enc_int64(2, _DT_STRING)
    for v in vals:
        arr += enc_string(7, v)
    return enc_int64(1, _DT_ARRAY) + enc_bytes(15, arr)


def _module_attrs(mod) -> Dict[str, bytes]:
    if isinstance(mod, nn.Linear):
        return {"inputSize": _attr_int(mod.input_size),
                "outputSize": _attr_int(mod.output_size),
                "withBias": _attr_bool(mod.with_bias)}
    if isinstance(mod, nn.SpatialConvolution):
        kh, kw = mod.kernel
        sh, sw = mod.stride
        ph, pw = mod.pad
        return {"nInputPlane": _attr_int(mod.n_input_plane),
                "nOutputPlane": _attr_int(mod.n_output_plane),
                "kernelW": _attr_int(kw), "kernelH": _attr_int(kh),
                "strideW": _attr_int(sw), "strideH": _attr_int(sh),
                "padW": _attr_int(pw), "padH": _attr_int(ph),
                "nGroup": _attr_int(mod.n_group),
                "withBias": _attr_bool(mod.with_bias)}
    if isinstance(mod, (nn.SpatialMaxPooling, nn.SpatialAveragePooling)):
        kh, kw = mod.kernel
        sh, sw = mod.stride
        ph, pw = mod.pad
        return {"kW": _attr_int(kw), "kH": _attr_int(kh),
                "dW": _attr_int(sw), "dH": _attr_int(sh),
                "padW": _attr_int(pw), "padH": _attr_int(ph)}
    if isinstance(mod, (nn.SpatialBatchNormalization,
                        nn.BatchNormalization)):
        return {"nOutput": _attr_int(mod.n_output),
                "eps": _attr_double(mod.eps),
                "momentum": _attr_double(mod.momentum),
                "affine": _attr_bool(mod.affine)}
    if isinstance(mod, nn.LookupTable):
        return {"nIndex": _attr_int(mod.n_index),
                "nOutput": _attr_int(mod.n_output),
                "paddingValue": _attr_double(mod.padding_value or 0.0),
                # reference "no renorm" sentinel is Double.MaxValue
                "maxNorm": _attr_double(
                    1.7976931348623157e308 if mod.max_norm is None
                    else float(mod.max_norm)),
                "normType": _attr_double(float(mod.norm_type or 2.0)),
                "shouldScaleGradByFreq": _attr_bool(False),
                "maskZero": _attr_bool(bool(getattr(mod, "mask_zero",
                                                    False)))}
    if isinstance(mod, nn.SpatialFullConvolution):
        if getattr(mod, "format", "NCHW") != "NCHW":
            raise ValueError(
                "save_bigdl: SpatialFullConvolution(format='NHWC') has "
                "no reference wire form")
        kh, kw = mod.kernel
        sh, sw = mod.stride
        ph, pw = mod.pad
        ah, aw = mod.adj
        return {"nInputPlane": _attr_int(mod.n_input_plane),
                "nOutputPlane": _attr_int(mod.n_output_plane),
                "kW": _attr_int(kw), "kH": _attr_int(kh),
                "dW": _attr_int(sw), "dH": _attr_int(sh),
                "padW": _attr_int(pw), "padH": _attr_int(ph),
                "adjW": _attr_int(aw), "adjH": _attr_int(ah),
                "nGroup": _attr_int(mod.n_group),
                "noBias": _attr_bool(not mod.with_bias)}
    if isinstance(mod, nn.SpatialDilatedConvolution):
        kh, kw = mod.kernel
        sh, sw = mod.stride
        ph, pw = mod.pad
        dh, dw = mod.dilation
        return {"nInputPlane": _attr_int(mod.n_input_plane),
                "nOutputPlane": _attr_int(mod.n_output_plane),
                "kW": _attr_int(kw), "kH": _attr_int(kh),
                "dW": _attr_int(sw), "dH": _attr_int(sh),
                "padW": _attr_int(pw), "padH": _attr_int(ph),
                "dilationW": _attr_int(dw), "dilationH": _attr_int(dh)}
    if isinstance(mod, nn.TemporalConvolution):
        return {"inputFrameSize": _attr_int(mod.input_frame_size),
                "outputFrameSize": _attr_int(mod.output_frame_size),
                "kernelW": _attr_int(mod.kernel_w),
                "strideW": _attr_int(mod.stride_w)}
    if isinstance(mod, nn.SpatialZeroPadding):
        if getattr(mod, "format", "NCHW") != "NCHW":
            raise ValueError(
                "save_bigdl: SpatialZeroPadding(format='NHWC') has no "
                "reference wire form")
        pl, pr, pt, pb = mod.pads
        return {"padLeft": _attr_int(pl), "padRight": _attr_int(pr),
                "padTop": _attr_int(pt), "padBottom": _attr_int(pb)}
    if isinstance(mod, nn.Padding):
        return {"dim": _attr_int(mod.dim), "pad": _attr_int(mod.pad),
                "nInputDim": _attr_int(mod.n_input_dim),
                "value": _attr_double(mod.value),
                "nIndex": _attr_int(1)}
    if isinstance(mod, nn.Dropout):
        return {"initP": _attr_double(mod.p)}
    if isinstance(mod, nn.Reshape):
        return {"size": _attr_int_array(mod.size)}
    if isinstance(mod, nn.JoinTable):
        return {"dimension": _attr_int(mod.dimension),
                "nInputDims": _attr_int(mod.n_input_dims)}
    if isinstance(mod, nn.Concat):
        return {"dimension": _attr_int(mod.dimension)}
    if isinstance(mod, nn.SpatialCrossMapLRN):
        return {"size": _attr_int(mod.size),
                "alpha": _attr_double(mod.alpha),
                "beta": _attr_double(mod.beta),
                "k": _attr_double(mod.k)}
    if isinstance(mod, nn.PReLU):
        return {"nOutputPlane": _attr_int(mod.n_output_plane)}
    if isinstance(mod, nn.ELU):
        return {"alpha": _attr_double(mod.alpha)}
    if isinstance(mod, nn.Power):
        return {"power": _attr_double(mod.power),
                "scale": _attr_double(mod.scale),
                "shift": _attr_double(mod.shift)}
    if isinstance(mod, nn.View):
        return {"sizes": _attr_int_array(mod.sizes)}
    if isinstance(mod, nn.Clamp) or isinstance(mod, nn.HardTanh):
        # Clamp subclasses HardTanh; reference Clamp ctor is (min: Int,
        # max: Int) while HardTanh takes doubles
        if type(mod).__name__ == "Clamp":
            return {"min": _attr_int(int(mod.min_value)),
                    "max": _attr_int(int(mod.max_value))}
        return {"minValue": _attr_double(mod.min_value),
                "maxValue": _attr_double(mod.max_value)}
    if isinstance(mod, nn.SoftPlus):
        return {"beta": _attr_double(mod.beta)}
    if isinstance(mod, nn.LeakyReLU):
        return {"negval": _attr_double(mod.negval)}
    if isinstance(mod, nn.Threshold):
        return {"th": _attr_double(mod.th), "v": _attr_double(mod.v)}
    if isinstance(mod, nn.MulConstant):
        return {"scalar": _attr_double(mod.scalar)}
    if isinstance(mod, nn.AddConstant):
        return {"constant_scalar": _attr_double(mod.constant)}
    if isinstance(mod, nn.Squeeze):
        if isinstance(mod.dim, (tuple, list)) or mod.batch_mode:
            raise ValueError(
                "save_bigdl: Squeeze with multiple dims or batch_mode "
                "has no reference wire form")
        return {} if mod.dim is None else {"dim": _attr_int(mod.dim)}
    if isinstance(mod, nn.Unsqueeze):
        return {"pos": _attr_int(mod.pos)}
    if isinstance(mod, nn.Select):
        return {"dimension": _attr_int(mod.dim),
                "index": _attr_int(mod.index)}
    if isinstance(mod, nn.Narrow):
        return {"dimension": _attr_int(mod.dimension),
                "offset": _attr_int(mod.offset),
                "length": _attr_int(mod.length)}
    if isinstance(mod, nn.Mean):
        return {"dimension": _attr_int(mod.dimension),
                "nInputDims": _attr_int(getattr(mod, "n_input_dims", -1)),
                "squeeze": _attr_bool(mod.squeeze)}
    if isinstance(mod, (nn.CMul, nn.CAdd)):
        return {"size": _attr_int_array(mod.size)}
    if isinstance(mod, nn.Normalize):
        return {"p": _attr_double(mod.p), "eps": _attr_double(mod.eps)}
    if isinstance(mod, nn.GaussianDropout):
        return {"rate": _attr_double(mod.rate)}
    if isinstance(mod, nn.GaussianNoise):
        return {"stddev": _attr_double(mod.stddev)}
    if isinstance(mod, nn.SelectTable):
        return {"index": _attr_int(mod.index)}
    if isinstance(mod, nn.NarrowTable):
        return {"offset": _attr_int(mod.offset),
                "length": _attr_int(mod.length)}
    if isinstance(mod, nn.Index):
        return {"dimension": _attr_int(mod.dimension)}
    return {}


_TYPE_NAMES = {}
for _short, _fac in _FACTORY.items():
    _TYPE_NAMES[_short] = _NS + _short


def _enc_graph(mod, params, state, counter, global_entries) -> bytes:
    """nn.Graph -> StaticGraph wire form: subModules with preModules
    wiring, inputNames/outputNames attrs, per-node edges maps
    (≙ nn/Graph.scala GraphSerializable doSerializeModule)."""
    body = enc_string(1, mod.name)
    body += enc_string(7, _NS + "StaticGraph")
    # every node the file references: the DFS-from-outputs topo PLUS any
    # declared input node that no output path reaches
    all_nodes = list(mod._topo)
    seen_ids = {id(n) for n in all_nodes}
    for n in mod.input_nodes:
        if id(n) not in seen_ids:
            all_nodes.insert(0, n)
            seen_ids.add(id(n))
    names_of = {}
    used_names = set()
    n_in = 0
    for node in all_nodes:
        if node.module is None:
            nm = f"{mod.name}.input{n_in}"
            n_in += 1
        else:
            nm = node.module.name
        if nm in used_names:
            # the wire format keys nodes by module name; one module
            # instance at two graph positions would collapse on load
            raise NotImplementedError(
                f"save_bigdl: module {nm!r} appears at multiple graph "
                "nodes (shared-module graphs are not supported)")
        used_names.add(nm)
        names_of[id(node)] = nm
    for node in all_nodes:
        nm = names_of[id(node)]
        pres = [names_of[id(p)] for p in node.prev_nodes]
        if node.module is None:
            sub = enc_string(1, nm) + enc_string(7, _NS + "Input")
        else:
            sub = _enc_module(node.module, params, state, counter,
                              global_entries)
        for p in pres:
            sub += enc_string(5, p)      # preModules
        body += enc_bytes(2, sub)
    # per-node edges maps: the reference loader unconditionally reads
    # "<name>_edges" (Graph.scala prepareLoadModule), so they must exist;
    # -1 encodes the default Edge() (no tuple index).  Our own loader
    # wires by preModules and ignores these.
    for node in all_nodes:
        nm = names_of[id(node)]
        inner = enc_string(1, nm)
        for p in (names_of[id(q)] for q in node.prev_nodes):
            av = enc_int64(1, _DT_INT32) \
                + enc_int64(3, (-1) & ((1 << 64) - 1))
            inner = inner + enc_bytes(2, enc_string(1, p)
                                      + enc_bytes(2, av))
        outer = enc_string(1, f"{nm}_edges") + enc_bytes(
            2, enc_string(1, nm)
            + enc_bytes(2, enc_int64(1, _DT_NAME_ATTR_LIST)
                        + enc_bytes(14, inner)))
        body += _attr_entry(f"{nm}_edges",
                            enc_int64(1, _DT_NAME_ATTR_LIST)
                            + enc_bytes(14, outer))
    body += _attr_entry("inputNames", _attr_str_array(
        names_of[id(n)] for n in mod.input_nodes))
    body += _attr_entry("outputNames", _attr_str_array(
        names_of[id(n)] for n in mod.output_nodes))
    return body


def _enc_module(mod, params, state, counter, global_entries) -> bytes:
    from ..nn.graph import Graph as _NNGraph
    if isinstance(mod, _NNGraph):
        return _enc_graph(mod, params, state, counter, global_entries)
    cls = type(mod).__name__
    if cls not in _TYPE_NAMES:
        raise ValueError(f"save_bigdl: unsupported layer {cls}")
    body = enc_string(1, mod.name)
    body += enc_string(7, _TYPE_NAMES[cls])
    if isinstance(mod, nn.TimeDistributed):
        # reference form: the wrapped module rides the 'layer' attr
        # (ctor reflection), NOT subModules; the TD node's flat params
        # mirror the layer's (TimeDistributed.parameters)
        inner = params.get(mod.layer.name, {})
        keys = nn.Module._weights_order(inner)
        if keys:
            body += enc_int64(15, 1)
            for k in keys:
                body += enc_bytes(16, _alloc_tensor(inner[k], counter,
                                                    global_entries))
        layer_bytes = _enc_module(mod.layer, params, state, counter,
                                  global_entries)
        body += _attr_entry("layer", enc_int64(1, _DT_MODULE)
                            + enc_bytes(13, layer_bytes))
        body += _attr_entry("maskZero", _attr_bool(
            bool(getattr(mod, "mask_zero", False))))
        return body
    if mod.children():
        for sub in mod.children():
            body += enc_bytes(2, _enc_module(sub, params, state, counter,
                                             global_entries))
    else:
        own = params.get(mod.name, {})
        keys = nn.Module._weights_order(own)
        if keys:
            body += enc_int64(15, 1)   # hasParameters
            arrs = [own[k] for k in keys]
            unfix = _WEIGHT_UNFIX.get(cls)
            if unfix is not None:
                arrs = unfix(mod, arrs)
            for arr in arrs:
                # data lives once in global_storage; the parameter slot
                # references the storage id (ModuleLoader.scala:119)
                body += enc_bytes(16, _alloc_tensor(arr, counter,
                                                    global_entries))
    for k, v in _module_attrs(mod).items():
        body += _attr_entry(k, v)
    if isinstance(mod, (nn.SpatialBatchNormalization,
                        nn.BatchNormalization)) and not mod.children():
        # nn/BatchNormalization.scala:346 doSerializeModule writes all
        # four tensor attrs; :323 doLoadModule reads them unconditionally
        own_st = (state or {}).get(mod.name) or {}
        rm = np.asarray(own_st.get(
            "running_mean", np.zeros(mod.n_output)), np.float32)
        rv = np.asarray(own_st.get(
            "running_var", np.ones(mod.n_output)), np.float32)
        for key, arr in (("runningMean", rm), ("runningVar", rv),
                         ("saveMean", np.zeros_like(rm)),
                         ("saveStd", np.zeros_like(rm))):
            body += _attr_entry(
                key, _attr_tensor(arr, counter, global_entries))
    return body


def save_bigdl(model, path: str):
    """Write `model` as a reference-format `.bigdl` file
    (≙ Module.saveModule / ModulePersister.saveToFile)."""
    params = model.ensure_initialized()
    state = getattr(model, "_state", None) or {}
    counter = [0]
    global_entries: Dict[str, bytes] = {}
    body = _enc_module(model, params, state, counter, global_entries)
    # top-level global_storage attr: NameAttrList{ name, attr{tid->tensor} }
    nal = enc_string(1, "global_storage")
    for tid, tensor_body in global_entries.items():
        attr_val = enc_int64(1, _DT_TENSOR) + enc_bytes(10, tensor_body)
        nal += enc_bytes(2, enc_string(1, tid) + enc_bytes(2, attr_val))
    gs_attr = enc_int64(1, 14) + enc_bytes(14, nal)   # NAME_ATTR_LIST
    body += _attr_entry("global_storage", gs_attr)
    # tmp + os.replace: same crash-safety contract as serializer.py's
    # _write_payload_zip — never corrupt an existing file mid-write
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(body)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path
