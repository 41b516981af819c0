"""Core module abstraction.

Reference: nn/abstractnn/AbstractModule.scala — stateful Torch-style modules
with hand-written ``updateOutput`` / ``updateGradInput`` / ``accGradParameters``.

TPU-native redesign: every module is a *functional core* plus a *Torch shell*.

Functional core (what XLA sees):
  - ``init(rng) -> params``: build this module's (and children's) parameters
    as a flat dict keyed by globally-unique module name -> {'weight': ..., ...}.
  - ``apply(params, x, ctx) -> y``: pure function of the full flat param dict
    and the input activity.  Mutable extras (batch-norm running stats, dropout
    RNG) ride on ``ctx``: persistent state is read from ``ctx.state`` and
    written to ``ctx.new_state``; per-module RNG keys are derived by folding
    the module's uid into ``ctx.rng_key``.  Because state flows through the
    ctx dicts (trace-time python mutation of traced values), the whole model —
    containers included — stays a pure, jittable function
    ``(params, state, rng, x) -> (y, new_state)`` via :meth:`run`.

Torch shell (API parity with the reference):
  - ``forward(x)`` lazily initializes parameters and caches ``self.output``.
  - ``backward(x, grad_output)`` uses ``jax.vjp`` w.r.t. (params, input),
    accumulating into ``self.grad_params`` and returning ``grad_input`` —
    replacing the reference's hand-written backward passes with JAX AD.

There is no hand-scheduled kernel work here: convs/matmuls lower to the MXU
through ``lax``; XLA fuses the elementwise neighbourhoods.
"""
from __future__ import annotations

import functools
import inspect
import itertools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_uid_counter = itertools.count()


def _fresh_uid():
    return next(_uid_counter)


def _capture_config(cls):
    """Wrap ``cls.__init__`` so constructing an instance records the bound
    constructor arguments on ``self._serde`` (outermost class wins).

    This is what makes module serialization *topology-as-data* (≙ the
    reference's utils/serializer/ModuleSerializer.scala SerializeContext,
    which persists each layer as class name + attribute protobuf): a saved
    model is "class + config + children", re-buildable by calling the
    constructor — never a pickle of the live object graph.
    """
    orig = cls.__init__
    if getattr(orig, "_captures_config", False):
        return
    try:
        sig = inspect.signature(orig)
    except (ValueError, TypeError):  # C-level or exotic signature
        return
    varargs = next((p.name for p in sig.parameters.values()
                    if p.kind is p.VAR_POSITIONAL), None)

    @functools.wraps(orig)
    def __init__(self, *args, **kwargs):
        if not hasattr(self, "_serde"):
            rec = {"class": type(self), "varargs": varargs, "config": None}
            self._serde = rec
            try:
                bound = sig.bind(self, *args, **kwargs)
                bound.apply_defaults()
                cfg = {}
                for pname, p in sig.parameters.items():
                    if pname == "self" or pname not in bound.arguments:
                        continue
                    v = bound.arguments[pname]
                    if p.kind is p.VAR_POSITIONAL:
                        cfg[pname] = list(v)
                    elif p.kind is p.VAR_KEYWORD:
                        cfg.update(v)
                    else:
                        cfg[pname] = v
                rec["config"] = cfg
            except TypeError:
                pass
        orig(self, *args, **kwargs)

    __init__._captures_config = True
    cls.__init__ = __init__


def migrate_legacy_names(tree, module):
    """Rename dict keys written before auto-names were zero-padded
    ('Linear_12' -> 'Linear_00000012') wherever the padded form matches one
    of `module`'s expected param/state names.  Cheap no-op when every key is
    already in the current format."""
    import re

    def has_legacy(t):
        if isinstance(t, dict):
            return any(re.fullmatch(r".*_\d{1,7}", k) or has_legacy(v)
                       for k, v in t.items())
        if isinstance(t, (list, tuple)):
            return any(has_legacy(v) for v in t)
        return False

    if not has_legacy(tree):
        return tree

    expected = set()

    def collect(t):
        if isinstance(t, dict):
            expected.update(t.keys())
            for v in t.values():
                collect(v)
    collect(jax.eval_shape(module.init, jax.random.PRNGKey(0)))
    collect(module.initial_state())

    def pad(k):
        m = re.fullmatch(r"(.*_)(\d{1,7})", k)
        return f"{m.group(1)}{int(m.group(2)):08d}" if m else k

    def migrate(t):
        if isinstance(t, dict):
            return {k if k in expected or pad(k) not in expected
                    else pad(k): migrate(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            return type(t)(migrate(v) for v in t)
        return t

    return migrate(tree)


class Ctx:
    """Per-call context threaded through ``apply``.

    Carries the training flag, the base RNG key, persistent state in/out
    dicts, and a scratch list for side losses (e.g. ActivityRegularization).
    """

    __slots__ = ("training", "rng_key", "state", "new_state",
                 "side_losses", "step_rng", "token_mask", "counters")

    def __init__(self, state=None, training=False, rng_key=None):
        self.training = training
        self.rng_key = rng_key
        self.state = state or {}
        self.new_state: Dict[str, Any] = {}
        self.side_losses = []
        # per-timestep key a Recurrent scan threads through its carry so
        # stochastic cells (LSTM/GRU p>0) draw fresh masks each step
        self.step_rng = None
        # which tokens of the call are real (bool, one a token; None:
        # all): a serving step's padding and dead slots are not
        self.token_mask = None
        # sums a layer counts while it computes, by counter name: traced
        # values, returned by the program that made the Ctx
        self.counters: Dict[str, Any] = {}

    def rng(self, module) -> jax.Array:
        if self.rng_key is None:
            raise ValueError(
                f"{module.name}: this module needs an RNG key in training mode; "
                "pass rng= to run()/forward()")
        return jax.random.fold_in(self.rng_key, module._uid % (2 ** 31))

    def get_state(self, module):
        return self.state.get(module.name)

    def put_state(self, module, value):
        self.new_state[module.name] = value

    def add_loss(self, value):
        self.side_losses.append(value)

    def count(self, name, value):
        self.counters[name] = self.counters.get(name, 0) + value


class Module:
    """Base class of all layers and containers."""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "__init__" in cls.__dict__:
            _capture_config(cls)

    def __init__(self, name: Optional[str] = None):
        self._uid = _fresh_uid()
        # zero-pad so lexicographic dict-key order (JAX pytree flatten order)
        # matches creation order even across uid digit-count boundaries
        self.name = name or f"{type(self).__name__}_{self._uid:08d}"
        # Torch-shell mutable state
        self.output = None
        self.grad_input = None
        self._params: Optional[Dict[str, Any]] = None
        self._state: Dict[str, Any] = {}
        self.grad_params: Optional[Dict[str, Any]] = None
        self.train_mode = False
        self._forward_rng = np.random.randint(0, 2 ** 31)
        # init-method overrides (nn/abstractnn/Initializable.scala)
        self.weight_init = None
        self.bias_init = None
        # per-layer regularizers (optim/Regularizer.scala)
        self.w_regularizer = None
        self.b_regularizer = None
        self.scale_w = 1.0  # gradient scale factors (AbstractModule.setScaleW)
        self.scale_b = 1.0

    # ------------------------------------------------------------------ #
    # functional core — subclasses override these two                    #
    # ------------------------------------------------------------------ #
    def init(self, rng) -> Dict[str, Any]:
        """Return the flat params dict for this module (and children)."""
        return {}

    def apply(self, params: Dict[str, Any], x, ctx: Ctx):
        """Pure forward. Subclasses must implement."""
        raise NotImplementedError(type(self).__name__)

    def initial_state(self) -> Dict[str, Any]:
        """Flat dict of persistent non-trainable state (e.g. BN stats)."""
        return {}

    # convenience for leaf layers
    def own(self, params):
        return params.get(self.name, {})

    # ------------------------------------------------------------------ #
    # functional entry point                                             #
    # ------------------------------------------------------------------ #
    def run(self, params, x, state=None, training=False, rng=None):
        """(params, x[, state, rng]) -> (y, new_state). Pure; safe under jit."""
        ctx = Ctx(state=state, training=training, rng_key=rng)
        y = self.apply(params, x, ctx)
        out_state = dict(state or {})
        out_state.update(ctx.new_state)
        return y, out_state

    def init_params(self, seed: int = 0):
        """Initialize and return (params, state)."""
        rng = jax.random.PRNGKey(seed)
        return self.init(rng), self.initial_state()

    # ------------------------------------------------------------------ #
    # Torch shell — API parity with the reference AbstractModule         #
    # ------------------------------------------------------------------ #
    def ensure_initialized(self, seed: int = 0):
        if self._params is None:
            self._params, self._state = self.init_params(
                getattr(self, "_init_seed", seed))
        return self._params

    @property
    def parameters_(self):
        return self.ensure_initialized()

    def forward(self, x, rng=None):
        self.ensure_initialized()
        if rng is None:
            self._forward_rng += 1
            rng = jax.random.PRNGKey(self._forward_rng)
        self._last_rng = rng  # backward must replay the same stochastic pass
        y, new_state = self.run(self._params, x, state=self._state,
                                training=self.train_mode, rng=rng)
        if self.train_mode:
            self._state = new_state
        self.output = y
        return y

    def __call__(self, x, rng=None):
        return self.forward(x, rng=rng)

    def backward(self, x, grad_output, rng=None):
        """grad_input via jax.vjp; accumulates param grads into grad_params."""
        self.ensure_initialized()
        if rng is None:
            rng = getattr(self, "_last_rng", None)
            if rng is None:
                rng = jax.random.PRNGKey(self._forward_rng)

        def f(params, inp):
            y, _ = self.run(params, inp, state=self._state,
                            training=self.train_mode, rng=rng)
            return y

        y, vjp_fn = jax.vjp(f, self._params, x)
        gparams, ginput = vjp_fn(grad_output)
        if self.grad_params is None:
            self.grad_params = gparams
        else:
            self.grad_params = jax.tree_util.tree_map(
                jnp.add, self.grad_params, gparams)
        self.grad_input = ginput
        self.output = y
        return ginput

    def update_output(self, x):
        return self.forward(x)

    def update_grad_input(self, x, grad_output):
        return self.backward(x, grad_output)

    def zero_grad_parameters(self):
        self.grad_params = None

    def update_parameters(self, learning_rate):
        """One manual SGD step from the Torch shell's accumulated
        grad_params; frozen modules stay untouched
        (≙ Layer.update_parameters)."""
        if self.grad_params is None:
            raise ValueError("no accumulated gradients; call backward first")
        frozen = self.frozen_param_names()
        self._params = {
            name: (sub if name in frozen else jax.tree_util.tree_map(
                lambda p, g: p - learning_rate * g, sub,
                self.grad_params[name]))
            for name, sub in self._params.items()}
        return self

    def get_parameters(self):
        """Return (params, grad_params) flat dicts (≙ reference getParameters)."""
        self.ensure_initialized()
        if self.grad_params is None:
            self.grad_params = jax.tree_util.tree_map(
                jnp.zeros_like, self._params)
        return self._params, self.grad_params

    def set_params(self, params, state=None):
        self._params = params
        if state is not None:
            self._state = state
        return self

    # -- pyspark Layer-method parity (bigdl/nn/layer.py) ---------------- #
    @staticmethod
    def _weights_order(sub):
        """Per-module key order for get/set_weights: weight* first, bias*
        second, the rest alphabetically — matching the reference
        Layer.get_weights [weight, bias] convention."""
        def rank(k):
            if k.startswith("weight"):
                return (0, k)
            if k.startswith("bias"):
                return (1, k)
            return (2, k)
        return sorted(sub, key=rank)

    def get_weights(self):
        """Flat list of this model's weight arrays, module-traversal order
        with per-module keys weight-first (≙ Layer.get_weights)."""
        self.ensure_initialized()
        out = []
        for m in self.modules():
            sub = self._params.get(m.name)
            if sub:
                for k in self._weights_order(sub):
                    out.append(np.asarray(sub[k]))
        return out

    def set_weights(self, weights):
        """Inverse of :meth:`get_weights`; shapes are validated."""
        self.ensure_initialized()
        ws = list(weights)
        new = dict(self._params)
        i = 0
        for m in self.modules():
            sub = self._params.get(m.name)
            if not sub:
                continue
            cur = {}
            for k in self._weights_order(sub):
                if i >= len(ws):
                    raise ValueError(
                        f"set_weights: {len(ws)} arrays given, more needed "
                        f"(stopped at {m.name}.{k})")
                arr = jnp.asarray(ws[i])
                i += 1
                if tuple(arr.shape) != tuple(np.shape(sub[k])):
                    raise ValueError(
                        f"set_weights: {m.name}.{k} expects "
                        f"{np.shape(sub[k])}, got {arr.shape}")
                cur[k] = arr
            new[m.name] = cur
        if i != len(ws):
            raise ValueError(f"set_weights: {len(ws)} arrays given, "
                             f"only {i} consumed")
        self._params = new
        return self

    def parameters(self):
        """{module_name: {param_name: ndarray}} (≙ Layer.parameters)."""
        self.ensure_initialized()
        return {name: {k: np.asarray(v) for k, v in sub.items()}
                for name, sub in self._params.items()}

    def freeze(self, names=None):
        """Mark this module — or the named submodules — non-trainable;
        training drivers zero their gradients (≙ Layer.freeze, the
        fine-tuning workflow).  Per-layer regularizers are masked with
        the gradients; an OptimMethod-level ``weight_decay`` still
        applies to every parameter, so prefer layer regularizers when
        freezing."""
        if names is None:
            for m in self.modules():
                m._frozen = True
        else:
            wanted = set(names)
            hit = set()
            for m in self.modules():
                if m.name in wanted:
                    hit.add(m.name)
                    for sub in m.modules():
                        sub._frozen = True
            missing = wanted - hit
            if missing:
                raise ValueError(f"freeze: no submodule named {missing}")
        return self

    def unfreeze(self, names=None):
        """Undo :meth:`freeze` (≙ Layer.unfreeze)."""
        if names is None:
            for m in self.modules():
                m._frozen = False
        else:
            for m in self.modules():
                if m.name in set(names):
                    for sub in m.modules():
                        sub._frozen = False
        return self

    def frozen_param_names(self):
        """Names of modules whose params must not update."""
        return {m.name for m in self.modules()
                if getattr(m, "_frozen", False)}

    def quantize(self, calibration_data=None):
        """Post-training int8 rewrite (≙ Layer.quantize);
        ``calibration_data`` bakes static activation scales."""
        from ..quantized import quantize as _q
        return _q(self, calibration_data=calibration_data)

    def _predictor(self, batch_size):
        # one long-lived Predictor per batch size: its jitted eval step
        # must be reused across predict calls, not recompiled each time
        cache = getattr(self, "_predictors", None)
        if cache is None:
            cache = self._predictors = {}
        if batch_size not in cache:
            from ..optim.predictor import Predictor
            cache[batch_size] = Predictor(self, batch_size=batch_size)
        return cache[batch_size]

    def predict(self, x, batch_size=128):
        """Batched jitted inference (≙ Layer.predict_local)."""
        return self._predictor(batch_size).predict(x)

    def predict_class(self, x, batch_size=128):
        """1-based class predictions (≙ Layer.predict_class)."""
        return self._predictor(batch_size).predict_class(x)

    # pyspark layer.py spellings (predict_distributed ≙ mesh-sharded
    # evaluation — route through DistriOptimizer/Predictor for that)
    predict_local = predict
    predict_class_local = predict_class

    def is_with_weights(self):
        """≙ Layer.is_with_weights: does this module (or any descendant —
        the reference's parameters() aggregates children) carry weights?"""
        p = self.ensure_initialized()
        return any(p.get(m.name) for m in self.modules())

    def set_seed(self, seed=123):
        """Seed FUTURE lazy parameter init (≙ Layer.set_seed).  Never
        re-initializes an already-built module — trained or loaded
        weights must not be silently destroyed; call
        ``reset(seed)`` explicitly for a fresh init."""
        self._init_seed = int(seed)
        return self

    def setWRegularizer(self, w_regularizer):              # noqa: N802
        """≙ Layer.setWRegularizer."""
        self.w_regularizer = w_regularizer
        return self

    def setBRegularizer(self, b_regularizer):              # noqa: N802
        """≙ Layer.setBRegularizer."""
        self.b_regularizer = b_regularizer
        return self

    def _sub_model_to(self, output_layer):
        """Model that ends at the named submodule — Sequential prefix or
        Graph re-outputting at that node (predict_image output_layer)."""
        from .graph import Graph as _Graph
        if type(self).__name__ == "Sequential":
            kids = self.children()
            for i, m in enumerate(kids):
                if m.name == output_layer:
                    from .containers import Sequential as _Seq
                    sub = _Seq(*kids[:i + 1])
                    return sub
            raise ValueError(f"no child named {output_layer!r}")
        if isinstance(self, _Graph):
            for node in self._topo:
                if node.module is not None \
                        and node.module.name == output_layer:
                    return _Graph(self.input_nodes, [node])
            raise ValueError(f"no graph node named {output_layer!r}")
        raise ValueError(
            "output_layer= needs a Sequential or Graph model")

    def predict_image(self, image_frame, output_layer=None,
                      share_buffer=False, batch_per_partition=4,
                      predict_key="predict"):
        """Predict every image of an ImageFrame, storing each result
        under ``predict_key`` on its ImageFeature (≙ Layer.predict_image
        / images/Utils.scala modelPredictImage).  Uses the prepared
        ``sample`` feature when a to-sample transform ran, else the raw
        CHW image.  ``share_buffer=True`` skips the defensive copy."""
        import numpy as np
        from ..data.imageframe import ImageFeature
        self.ensure_initialized()
        model = self
        if output_layer is not None:
            # cache sub-models per output layer: each owns a jitted
            # Predictor that must be reused, not recompiled per call
            cache = getattr(self, "_sub_models", None)
            if cache is None:
                cache = self._sub_models = {}
            if output_layer not in cache:
                cache[output_layer] = self._sub_model_to(output_layer)
            model = cache[output_layer]
            # re-sync EVERY call, not once at cache fill: set_weights /
            # load_weights / a training loop replace self._params, and a
            # one-time snapshot would keep predicting with stale weights
            model._params, model._state = self._params, self._state
        feats = list(image_frame)
        xs = []
        for f in feats:
            if ImageFeature.SAMPLE in f:
                xs.append(np.asarray(f[ImageFeature.SAMPLE].feature()))
            else:
                img = np.asarray(f[ImageFeature.IMAGE], np.float32)
                if img.ndim == 2:          # grayscale HW -> (1, H, W)
                    img = img[None]
                else:                      # HWC -> CHW
                    img = np.transpose(img, (2, 0, 1))
                xs.append(img)
        shapes = {x.shape for x in xs}
        if len(shapes) > 1:
            raise ValueError(
                f"predict_image: images have mixed shapes {sorted(shapes)} "
                "— add a Resize / to-sample transform to the ImageFrame "
                "first (≙ the reference's transform-before-predict "
                "pipeline)")
        preds = np.asarray(model.predict(np.stack(xs),
                                         batch_size=max(1,
                                                        batch_per_partition)))
        for f, p in zip(feats, preds):
            f[predict_key] = p if share_buffer else np.array(p, copy=True)
        return image_frame

    def saveModel(self, path, over_write=True):          # noqa: N802
        """pyspark spelling of :meth:`save`."""
        return self.save(path, overwrite=over_write)

    def save_caffe(self, prototxt_path, model_path, **kw):
        """≙ Layer.save_caffe (utils/caffe.save_caffe)."""
        from ..utils.caffe import save_caffe as _sc
        return _sc(self, prototxt_path, model_path, **kw)

    def save_tensorflow(self, path, input_shape, **kw):
        """≙ Layer.save_tensorflow (utils/tf_import.save_tf_graph)."""
        from ..utils.tf_import import save_tf_graph as _stf
        return _stf(self, path, input_shape, **kw)

    def set_running_mean(self, mean):
        """Overwrite this module's BN running mean (≙ Layer.set_running_mean).
        For a BN layer inside a container, call
        ``model.set_running_stats(bn_name, mean=...)`` on the model that
        owns the state instead."""
        return self._set_running(self.name, "running_mean", mean)

    def set_running_std(self, std):
        """Overwrite this module's BN running variance
        (≙ Layer.set_running_std; the reference stores variance).  See
        :meth:`set_running_mean` for layers inside containers."""
        return self._set_running(self.name, "running_var", std)

    def set_running_stats(self, module_name, mean=None, std=None):
        """Overwrite a named submodule's BN running statistics in THIS
        model's state (the container owns its children's state — calling
        set_running_mean on the child would touch a private copy)."""
        if mean is not None:
            self._set_running(module_name, "running_mean", mean)
        if std is not None:
            self._set_running(module_name, "running_var", std)
        return self

    def _set_running(self, module_name, key, value):
        if self._state is None and module_name != self.name:
            raise ValueError(
                "model state not initialized; run forward/init first")
        self.ensure_initialized()
        own = self._state.get(module_name)
        if not isinstance(own, dict) or key not in own:
            if module_name == self.name:
                raise ValueError(
                    f"{type(self).__name__} has no {key} state (not a "
                    "batch-normalization layer, or inside a container — "
                    "use model.set_running_stats(name, ...) there)")
            raise ValueError(f"no submodule state {module_name!r} with "
                             f"{key} in this model")
        value = jnp.asarray(value)
        if value.shape != own[key].shape:
            raise ValueError(f"{key} expects shape {own[key].shape}, "
                             f"got {value.shape}")
        new_state = dict(self._state)
        new_state[module_name] = dict(own, **{key: value})
        self._state = new_state
        return self

    def training(self):
        self.train_mode = True
        for m in self.children():
            m.training()
        return self

    def evaluate(self, *args):
        """No arguments: switch to eval mode (returns self).

        ``evaluate(dataset, batch_size, val_methods)``: benchmark model
        quality — the pyspark 3-arg form (`bigdl/nn/layer.py
        Layer.evaluate`); returns ``[(method, result), ...]`` like
        `optim.Evaluator.test`."""
        if args:
            if len(args) != 3:
                raise TypeError(
                    "evaluate() takes either no arguments (set eval "
                    "mode) or (dataset, batch_size, val_methods)")
            dataset, batch_size, val_methods = args
            from ..optim.predictor import Evaluator
            # cache the Evaluator (its jitted eval step) per batch size:
            # a per-epoch validation loop must not retrace every call
            cached = getattr(self, "_evaluator_cache", None)
            if cached is None or cached[0] != batch_size:
                cached = (batch_size, Evaluator(self,
                                                batch_size=batch_size))
                self._evaluator_cache = cached
            return cached[1].test(dataset, val_methods)
        self.train_mode = False
        for m in self.children():
            m.evaluate()
        return self

    def is_training(self):
        return self.train_mode

    # ------------------------------------------------------------------ #
    # structure & introspection                                          #
    # ------------------------------------------------------------------ #
    def children(self):
        return []

    # -- serde hooks (utils/serializer.py v2 format) -------------------- #
    # extra instance attributes to persist alongside the ctor config
    _serde_extra_attrs = ()

    def _serde_children(self):
        """Children to persist (None entries allowed as placeholders)."""
        return self.children()

    def _serde_restore_children(self, children):
        """Re-attach deserialized children after config reconstruction.

        Default: no-op — right for leaf modules and for modules whose
        constructor deterministically rebuilds its children from the
        replayed config (their persisted children list is then redundant).
        Classes that accept children post-construction (``add``/attribute
        assignment) must override this, or a reloaded model silently loses
        the added children.
        """

    def _serde_config(self):
        """Ctor config to persist; None = 'not reconstructible from
        config' (the class must then override ``_serde_build``)."""
        serde = getattr(self, "_serde", None)
        return dict(serde["config"]) if serde and serde.get("config") \
            is not None else None

    @classmethod
    def _serde_build(cls, config, children):
        """Construct from decoded config+children when plain ctor replay
        can't work.  Return None to use ctor replay (the default)."""
        return None

    def modules(self):
        """Depth-first list of this module and all descendants."""
        out = [self]
        for c in self.children():
            out.extend(c.modules())
        return out

    def named_modules(self):
        return {m.name: m for m in self.modules()}

    def set_name(self, name):
        self.name = name
        return self

    def get_name(self):
        return self.name

    def set_init_method(self, weight_init=None, bias_init=None):
        self.weight_init = weight_init
        self.bias_init = bias_init
        return self

    def set_scale_w(self, s):
        self.scale_w = s
        return self

    def set_scale_b(self, s):
        self.scale_b = s
        return self

    def parameter_count(self):
        params = self.ensure_initialized()
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    def get_output_shape(self, input_shape, dtype=jnp.float32):
        """Shape inference via jax.eval_shape (≙ nn/abstractnn/InferShape.scala)."""
        params, state = self.init_params(0)
        if isinstance(input_shape[0], (tuple, list)):
            x = [jax.ShapeDtypeStruct(tuple(s), dtype) for s in input_shape]
        else:
            x = jax.ShapeDtypeStruct(tuple(input_shape), dtype)
        out = jax.eval_shape(
            lambda p, i: self.run(p, i, state=state,
                                  rng=jax.random.PRNGKey(0))[0], params, x)
        return jax.tree_util.tree_map(lambda s: s.shape, out)

    # regularization support: collect per-layer penalties over a params dict
    def regularization_loss(self, params):
        loss = 0.0
        for m in self.modules():
            p = params.get(m.name)
            if not p:
                continue
            if m.w_regularizer is not None and "weight" in p:
                loss = loss + m.w_regularizer(p["weight"])
            if m.b_regularizer is not None and "bias" in p:
                loss = loss + m.b_regularizer(p["bias"])
        return loss

    # ------------------------------------------------------------------ #
    # persistence (≙ AbstractModule.save / Module.load)                  #
    # ------------------------------------------------------------------ #
    def save(self, path, overwrite=True):
        from ..utils import serializer
        serializer.save_module(self, path, overwrite=overwrite)
        return self

    @staticmethod
    def load(path):
        from ..utils import serializer
        return serializer.load_module(path)

    def save_weights(self, path, overwrite=True):
        from ..utils import serializer
        self.ensure_initialized()
        serializer.save_weights_file(self, path)
        return self

    def load_weights(self, path):
        from ..utils import serializer
        params, state = serializer.load_weights_file(path)
        params, state = migrate_legacy_names((params, state), self)
        # jnp.array(copy=True), NOT jnp.asarray: asarray can zero-copy
        # ADOPT an aligned np.load buffer, and a later donated train
        # step would scribble over memory numpy still owns (GL001, the
        # PR-3 restore corruption shape)
        own = lambda v: jnp.array(v, copy=True)
        self._params = jax.tree_util.tree_map(own, params)
        self._state = jax.tree_util.tree_map(own, state)
        return self

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"

    # reference API aliases -------------------------------------------- #
    def reset(self, seed: int = 0):
        self._params, self._state = self.init_params(seed)
        return self

    def clear_state(self):
        self.output = None
        self.grad_input = None
        return self


# classes that don't define their own __init__ fall through to the base
# ctor; wrap it too so every instance gets its ctor config captured
_capture_config(Module)


class Criterion:
    """Base of all losses (nn/abstractnn/AbstractCriterion.scala):
    ``loss(output, target)`` pure fn + Torch-style forward/backward shell."""
    """Base loss (≙ nn/abstractnn/AbstractCriterion.scala).

    Subclasses implement ``loss(output, target) -> scalar``.  ``forward``
    caches the value; ``backward`` returns d loss / d output via JAX AD,
    replacing the reference's hand-written updateGradInput.
    """

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "__init__" in cls.__dict__:
            _capture_config(cls)

    def __init__(self, name: Optional[str] = None):
        self._uid = _fresh_uid()
        # zero-pad so lexicographic dict-key order (JAX pytree flatten order)
        # matches creation order even across uid digit-count boundaries
        self.name = name or f"{type(self).__name__}_{self._uid:08d}"
        self.output = None
        self.grad_input = None

    def loss(self, output, target):
        raise NotImplementedError

    def forward(self, output, target):
        self.output = self.loss(output, target)
        return self.output

    def __call__(self, output, target):
        return self.forward(output, target)

    def backward(self, output, target):
        self.grad_input = jax.grad(lambda o: self.loss(o, target))(output)
        return self.grad_input

    def __repr__(self):
        return f"{type(self).__name__}()"


_capture_config(Criterion)
