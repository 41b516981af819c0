"""Training driver (≙ optim/Optimizer.scala, LocalOptimizer.scala).

The reference LocalOptimizer splits each MiniBatch across Engine threads,
runs per-clone fwd/bwd, sums gradients, then applies the OptimMethod.  On
TPU the whole thing is ONE jitted XLA program per iteration:

    (params, opt_state, model_state, x, y, rng)
        -> fwd -> loss -> bwd (AD) -> optimizer update

with buffers donated (in-place HBM update, no copies) and optional bf16
compute (master weights stay fp32; layers cast weights to the input dtype,
so feeding bf16 inputs runs matmuls/convs on the MXU in bf16).

Host-side, the Optimizer drives epochs/iterations, fires Triggers for
validation / checkpoint / summaries, and supports checkpoint-resume — the
failure-recovery analogue of DistriOptimizer's retry-from-cache
(DistriOptimizer.scala optimize() retry loop).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..nn.module import Ctx, Module, migrate_legacy_names
from ..data.dataset import DataSet
from ..data.minibatch import MiniBatch
from ..observability import DivergenceError, Recorder
from ..observability.host import TelemetryHost
from .optim_method import OptimMethod, SGD
from .trigger import Trigger
from .validation import ValidationMethod


@dataclass
class TrainingState:
    epoch: int = 1
    iteration: int = 0
    loss: Optional[float] = None
    score: Optional[float] = None
    epoch_finished: bool = False
    batch_in_epoch: int = 0      # completed batches within current epoch


class Metrics:
    """Per-iteration timing/throughput (≙ optim/Metrics.scala: the
    reference tracks data-fetch / compute / aggregate timers per
    iteration).  `trace()` additionally captures an XLA device profile
    viewable in TensorBoard / Perfetto (the TPU analogue of the
    reference's driver-side metric dump)."""

    def __init__(self):
        self.values: Dict[str, List[float]] = {}

    def add(self, key, value):
        self.values.setdefault(key, []).append(value)

    def mean(self, key):
        v = self.values.get(key, [])
        return sum(v) / len(v) if v else 0.0

    def summary(self):
        return {k: self.mean(k) for k in self.values}

    @staticmethod
    def trace(log_dir):
        """Context manager: profile device execution into `log_dir`
        (jax.profiler trace; open with TensorBoard's profile plugin)."""
        return jax.profiler.trace(log_dir)

    @staticmethod
    def annotation(name):
        """Label a host-side region so it shows up on the trace timeline."""
        return jax.profiler.TraceAnnotation(name)


def _tree_sq(tree, axis_name=None, sharded_mask=None):
    """Global sum of squares over a pytree's float leaves.  Under FSDP
    (``axis_name`` + ``sharded_mask``) the dim-0-sharded contributions
    are psum'ed so every shard sees the GLOBAL value (same semantics as
    :class:`_ClippedOptim`)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if axis_name is not None and sharded_mask is not None:
        mask = jax.tree_util.tree_leaves(sharded_mask)
        sq_sh = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                    for g, m in zip(leaves, mask) if m) + 0.0
        sq_rep = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                     for g, m in zip(leaves, mask) if not m) + 0.0
        return jax.lax.psum(sq_sh, axis_name) + sq_rep
    return sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves) + 0.0


def _tree_nonfinite(tree, axis_name=None, sharded_mask=None):
    """Global count of non-finite elements over a pytree's leaves (same
    FSDP psum semantics as :func:`_tree_sq`)."""
    def cnt(g):
        return jnp.sum(~jnp.isfinite(g.astype(jnp.float32))
                       ).astype(jnp.float32)
    leaves = jax.tree_util.tree_leaves(tree)
    if axis_name is not None and sharded_mask is not None:
        mask = jax.tree_util.tree_leaves(sharded_mask)
        c_sh = sum(cnt(g) for g, m in zip(leaves, mask) if m) + 0.0
        c_rep = sum(cnt(g) for g, m in zip(leaves, mask) if not m) + 0.0
        return jax.lax.psum(c_sh, axis_name) + c_rep
    return sum(cnt(g) for g in leaves) + 0.0


def health_scalars(grads, old_params, new_params, axis_name=None,
                   sharded_mask=None):
    """Training-health scalars computed ON DEVICE inside the step (a few
    reductions — negligible next to the backward): gradient global-norm,
    post-update parameter norm, update norm, the update/param ratio
    (the classic 1e-3-ish learning-rate sanity signal), and the
    non-finite gradient-element count the NaN/Inf sentinel reads —
    folded into the jitted step so health checking adds no host sync
    beyond the one telemetry already pays."""
    gn = jnp.sqrt(_tree_sq(grads, axis_name, sharded_mask))
    pn = jnp.sqrt(_tree_sq(new_params, axis_name, sharded_mask))
    diff = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new_params, old_params)
    un = jnp.sqrt(_tree_sq(diff, axis_name, sharded_mask))
    return {"grad_norm": gn, "param_norm": pn, "update_norm": un,
            "update_ratio": un / jnp.maximum(pn, 1e-12),
            "nonfinite_grads": _tree_nonfinite(grads, axis_name,
                                               sharded_mask)}


def mask_frozen_grads(model: Module, grads):
    """Zero gradients of modules frozen via Module.freeze (evaluated at
    step-build time, so the compiled program bakes the mask in)."""
    frozen = model.frozen_param_names()
    if not frozen:
        return grads
    return {name: (jax.tree_util.tree_map(jnp.zeros_like, sub)
                   if name in frozen else sub)
            for name, sub in grads.items()}


def apply_device_augment(augment, x, rng, training=True):
    """Run a device-side augmentation (``data.device_augment``-style
    callable) INSIDE the jitted step: the host ships raw uint8 and the
    crop/flip/normalize math fuses into the step's XLA program.  Returns
    ``(x, rng)`` — the augmentation key is split off the step's traced
    rng (recompile-safe: no host clock or host RNG enters the trace),
    so every step (and every resumed step, whose rng comes from the
    checkpoint) sees its own deterministic stream."""
    if augment is None:
        return x, rng
    rng, sub = jax.random.split(rng)
    return augment(x, sub, training=training), rng


def make_train_step(model: Module, criterion, optim_method: OptimMethod,
                    mixed_precision=False, extra_loss_fn=None,
                    telemetry=False, device_augment=None):
    """Build the pure fused train step; caller jits (and shard_maps) it.

    ``telemetry=True`` appends a dict of training-health device scalars
    (:func:`health_scalars`) to the return tuple.  ``device_augment``
    folds a device-side augmentation into the step (uint8 on the wire;
    see :func:`apply_device_augment`)."""

    def step(params, opt_state, model_state, x, y, rng):
        x, rng = apply_device_augment(device_augment, x, rng)
        if mixed_precision:
            x = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, x)

        def loss_fn(p):
            ctx = Ctx(state=model_state, training=True, rng_key=rng)
            out = model.apply(p, x, ctx)
            out32 = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
                else a, out)
            loss = criterion.loss(out32, y)
            for sl in ctx.side_losses:
                loss = loss + sl
            loss = loss + model.regularization_loss(p)
            if extra_loss_fn is not None:
                loss = loss + extra_loss_fn(p)
            return loss, ctx.new_state

        (loss, state_updates), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = mask_frozen_grads(model, grads)
        new_params, new_opt_state = optim_method.update(grads, params,
                                                        opt_state)
        merged = dict(model_state)
        merged.update(state_updates)
        if telemetry:
            return (new_params, new_opt_state, merged, loss,
                    health_scalars(grads, params, new_params))
        return new_params, new_opt_state, merged, loss

    return step


def make_accum_grads(loss_fn, n_accum: int, weight_fn=None):
    """Microbatch gradient accumulation shared by Local/Distri/Spmd steps.

    ``loss_fn(params, model_state, x, y, rng) -> (loss, new_state)``.
    Returns ``grads_fn(params, model_state, x, y, rng) ->
    ((mean_loss, merged_state), mean_grads)`` that scans ``n_accum``
    microbatches (BN state threaded in order, per-microbatch RNG via
    fold_in); ``n_accum < 2`` degenerates to one value_and_grad.

    ``weight_fn(x, y) -> scalar`` weights each microbatch's loss/grads
    (final result divided by the total weight).  Needed when ``loss_fn``
    is a *masked* mean — e.g. token cross-entropy with padding, where the
    valid-token count varies per microbatch and equal weighting would
    silently optimize a different objective.  Default: equal weights
    (exact for per-sample-mean criteria, since microbatches are equal
    sized).
    """
    if n_accum < 2:
        def direct(params, model_state, x, y, rng):
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, model_state, x, y, rng)
        return direct

    def grads_fn(params, model_state, x, y, rng):
        def split(a):
            b = a.shape[0]
            if b % n_accum:
                raise ValueError(
                    f"(per-shard) batch {b} not divisible by "
                    f"n_accum={n_accum}; on a mesh the global batch is "
                    "first split over dp shards")
            # strided split (microbatch i = rows {j*n+i}): dim 0 of each
            # microbatch keeps the original batch-dim sharding, so under
            # GSPMD no cross-device resharding is inserted per scan step
            a2 = a.reshape((b // n_accum, n_accum) + a.shape[1:])
            return jnp.moveaxis(a2, 1, 0)

        xs = jax.tree_util.tree_map(split, x)
        ys = jax.tree_util.tree_map(split, y)

        def body(carry, mb):
            g_acc, loss_acc, w_acc, mstate, i = carry
            xi, yi = mb
            w = (jnp.float32(1.0) if weight_fn is None
                 else weight_fn(xi, yi).astype(jnp.float32))
            (loss, upd), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                    params, mstate, xi, yi, jax.random.fold_in(rng, i))
            merged = dict(mstate)
            merged.update(upd)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + w * g, g_acc, grads)
            return (g_acc, loss_acc + w * loss, w_acc + w, merged,
                    i + 1), None

        # zeros_like (vs jnp.zeros(shape)) lets GSPMD propagate the
        # operand's sharding into the gradient carry
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
        (g_sum, loss_sum, w_sum, merged, _), _ = lax.scan(
            body, (zeros, jnp.float32(0), jnp.float32(0),
                   dict(model_state), jnp.int32(0)), (xs, ys))
        w_sum = jnp.maximum(w_sum, 1e-8)
        grads = jax.tree_util.tree_map(lambda g: g / w_sum, g_sum)
        return (loss_sum / w_sum, merged), grads

    return grads_fn


def make_accum_train_step(model: Module, criterion,
                          optim_method: OptimMethod, n_accum: int,
                          mixed_precision=False, extra_loss_fn=None,
                          telemetry=False, device_augment=None):
    """Gradient-accumulation variant of make_train_step: the batch is
    split into ``n_accum`` microbatches, a ``lax.scan`` accumulates the
    mean gradient (and threads BN state through in order), and the
    optimizer applies ONE update — a large effective batch in bounded
    activation memory on a single chip.  (Beyond the reference's surface;
    its analogue is the Spark executors' subbatch loop in
    optim/LocalOptimizer.scala.)
    """
    if n_accum < 2:
        return make_train_step(model, criterion, optim_method,
                               mixed_precision, extra_loss_fn,
                               telemetry=telemetry,
                               device_augment=device_augment)

    def micro_loss(params, model_state, x, y, rng):
        x, rng = apply_device_augment(device_augment, x, rng)
        if mixed_precision:
            x = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, x)
        ctx = Ctx(state=model_state, training=True, rng_key=rng)
        out = model.apply(params, x, ctx)
        out32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
            else a, out)
        loss = criterion.loss(out32, y)
        for sl in ctx.side_losses:
            loss = loss + sl
        if extra_loss_fn is not None:
            loss = loss + extra_loss_fn(params)
        return loss, ctx.new_state

    grads_fn = make_accum_grads(micro_loss, n_accum)

    def step(params, opt_state, model_state, x, y, rng):
        (mean_loss, merged), grads = grads_fn(params, model_state, x, y,
                                              rng)
        # regularization is batch-independent: add its loss and gradient
        # once (a regularizer-free model contributes zeros, which XLA
        # folds away); keeps the reported loss identical to the
        # non-accumulated step's
        reg_loss = model.regularization_loss(params)
        reg_grads = jax.grad(model.regularization_loss)(params)
        grads = jax.tree_util.tree_map(jnp.add, grads, reg_grads)
        grads = mask_frozen_grads(model, grads)
        new_params, new_opt_state = optim_method.update(grads, params,
                                                        opt_state)
        if telemetry:
            return (new_params, new_opt_state, merged, mean_loss + reg_loss,
                    health_scalars(grads, params, new_params))
        return new_params, new_opt_state, merged, mean_loss + reg_loss

    return step


def make_eval_step(model: Module, device_augment=None):
    def step(params, model_state, x):
        if device_augment is not None:
            # eval-mode augmentation (center crop + normalize): rng is
            # None positionally, honoring the documented
            # (x, rng, training) -> x callable contract
            x = device_augment(x, None, training=False)
        ctx = Ctx(state=model_state, training=False, rng_key=None)
        return model.apply(params, x, ctx)
    return step


class Optimizer(TelemetryHost):
    """Base training driver; factory returns Local or Distri optimizer
    (≙ optim/Optimizer.scala apply); ``set_telemetry`` / ``set_health``
    / ``serve_metrics`` come from :class:`TelemetryHost` (≙
    optim/Metrics.scala, grown into a first-class subsystem)."""

    def __init__(self, model: Module, training_set, criterion,
                 batch_size: Optional[int] = None, seed: int = 0):
        if isinstance(training_set, tuple):
            x, y = training_set
            if batch_size is None:
                raise ValueError("batch_size required for array data")
            training_set = DataSet.minibatch_arrays(x, y, batch_size)
        self.model = model
        self.dataset: DataSet = training_set
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.seed = seed
        # validation
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset: Optional[DataSet] = None
        self.val_methods: Optional[List[ValidationMethod]] = None
        # checkpoint (bigdl_tpu.checkpoint subsystem)
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self._ckpt_mgr = None
        self._preemption = None
        # summaries
        self.train_summary = None
        self.val_summary = None
        self.metrics = Metrics()
        self.state = TrainingState()
        self.mixed_precision = False
        self._grad_accum = 1
        self._grad_clip_norm = None
        self._grad_clip_const = None
        # failure recovery (≙ DistriOptimizer.scala optimize() retry loop:
        # failed iterations restart from the cached model state)
        self.max_retries = 0
        self._resume_skip = 0        # batches to skip after mid-epoch resume
        self._resume_rng = None      # loop rng restored from checkpoint
        # a restored data cursor positions the dataset itself; an empty
        # first epoch then means "resumed at the boundary", not "no data"
        self._cursor_resumed = False
        self.prefetch_depth = 0
        # device-side augmentation compiled into the train step (the
        # uint8-wire path: data/device_augment.DeviceAugment or any
        # (x, rng, training) -> x callable)
        self._device_augment = None
        self._retry_cache = None
        TelemetryHost.__init__(self)

    # -- fluent config, reference API ----------------------------------- #
    def set_optim_method(self, method):
        self.optim_method = method
        return self

    def set_end_when(self, trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger, dataset, methods, batch_size=None):
        self.val_trigger = trigger
        if isinstance(dataset, tuple):
            x, y = dataset
            dataset = DataSet.minibatch_arrays(x, y, batch_size or 128,
                                               shuffle=False, drop_last=False)
        self.val_dataset = dataset
        self.val_methods = list(methods)
        return self

    def set_checkpoint(self, path, trigger=None, layout="manifest",
                       async_write=True, keep_last=None,
                       keep_every_epochs=None, handle_preemption=False):
        """Checkpoint into ``path`` whenever ``trigger`` fires (default:
        every epoch), via the :mod:`bigdl_tpu.checkpoint` subsystem:
        sharded CRC32C-verified files committed by an atomic manifest,
        written by a background thread (``async_write``) so only the
        device→host copy blocks the step loop.  ``keep_last`` /
        ``keep_every_epochs`` configure retention GC (default: keep
        everything).  ``layout="file"`` keeps the legacy single-file
        format (still with an atomic ``latest`` pointer, and resume
        tolerates a dangling/corrupt pointer by scanning).
        ``handle_preemption`` installs a SIGTERM handler: a preempted
        run finishes the in-flight write, emits a final checkpoint, and
        ``optimize()`` returns cleanly."""
        from ..checkpoint import CheckpointManager, PreemptionHandler
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger or Trigger.every_epoch()
        os.makedirs(path, exist_ok=True)
        self._ckpt_mgr = CheckpointManager(
            path, layout=layout, async_write=async_write,
            keep_last=keep_last, keep_every_epochs=keep_every_epochs,
            recorder_fn=self._rec)
        if handle_preemption:
            self._preemption = PreemptionHandler().install()
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_weight_stream(self, publisher):
        """Attach a live train→serve weight stream
        (:class:`~bigdl_tpu.serving.WeightStreamPublisher`): its
        trigger is evaluated per iteration and, on fire, the current
        params are snapshotted (owning copies — the next step donates
        the live buffers) and published to the serving target through
        the canary gate.  ``None`` detaches."""
        self._weight_stream = publisher
        return self

    def set_val_summary(self, summary):
        self.val_summary = summary
        return self

    def set_gradient_accumulation(self, n_accum: int):
        """Split each batch into ``n_accum`` microbatches and apply one
        optimizer update on the averaged gradient — a large effective
        batch in bounded activation memory (single chip or per shard)."""
        if n_accum < 1:
            raise ValueError("n_accum must be >= 1")
        self._grad_accum = int(n_accum)
        return self

    def set_mixed_precision(self, enabled=True):
        self.mixed_precision = enabled
        return self

    def set_prefetch(self, depth=2):
        """Stage minibatches to the device from a background thread,
        `depth` batches ahead (double buffering at the default; ≙ the
        reference Engine's prefetching iterators).  Self-staging
        datasets (``data.sharded.ShardedRecordDataSet``) already
        prefetch and place internally — they are never double-wrapped,
        because a loader reading ahead of training would break the
        exactly-once data cursor."""
        self.prefetch_depth = depth
        return self

    def set_device_augment(self, augment):
        """Compile a device-side augmentation into the train step
        (``data.device_augment.DeviceAugment`` or any
        ``(x, rng, training) -> x`` callable): the host ships raw uint8
        batches (4× smaller on the wire than fp32) and crop / flip /
        normalize fuse into the step's XLA program.  The augmentation
        key is split off the step's traced rng — recompile-safe, and a
        resumed run (rng restored from the checkpoint) replays the
        identical stream.  Takes effect at the next step build; call
        before ``optimize()``."""
        self._device_augment = augment
        # the cached eval program baked the OLD augmentation in; a
        # stale one would feed validation un-augmented (wrong shapes
        # or silently wrong metrics)
        self._eval_step = None
        return self

    def set_trace_every(self, n_steps: int, log_dir: str):
        """Capture a jax.profiler trace of every n-th step into
        ``log_dir`` (TensorBoard profile plugin / Perfetto).  Creates a
        sink-less Recorder if none is attached yet — trace-only, so no
        health norms are compiled into the step."""
        if self._recorder is None:
            self.set_telemetry(Recorder(), health=False)
        self._recorder.trace_every(n_steps, log_dir)
        return self

    def _wd_suspended(self):
        """Suspend the stall watchdog around legitimate between-step
        work (validation, checkpoint commit) — a long pass there is not
        a wedged step loop."""
        if self._watchdog is None:
            from contextlib import nullcontext
            return nullcontext()
        return self._watchdog.suspended()

    def set_auto_retry(self, max_retries):
        """Retry a failed epoch from the last end-of-epoch state snapshot
        (≙ DistriOptimizer's retryNum/cache recovery)."""
        self.max_retries = max_retries
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm):
        self._grad_clip_norm = clip_norm
        return self

    def set_constant_gradient_clipping(self, min_v, max_v):
        self._grad_clip_const = (min_v, max_v)
        return self

    # -- checkpointing (≙ Optimizer.saveCheckpoint / resume; the heavy
    # lifting lives in bigdl_tpu.checkpoint) ----------------------------- #
    def _ckpt_manager(self):
        if self._ckpt_mgr is None:
            from ..checkpoint import CheckpointManager
            self._ckpt_mgr = CheckpointManager(self.checkpoint_path,
                                               recorder_fn=self._rec)
        return self._ckpt_mgr

    @staticmethod
    def _ckpt_shards(host):
        """Split (params, opt_state, model_state) into named shards —
        params per top-level module, so shard files stay bounded and a
        torn write can only tear one file."""
        params, opt_state, model_state = host
        shards = {"opt_state": opt_state, "model_state": model_state}
        if isinstance(params, dict) and params:
            for mod, sub in params.items():
                shards[f"params/{mod}"] = sub
        else:
            shards["params"] = params
        return shards

    @staticmethod
    def _ckpt_unshard(trees):
        if "params" in trees:
            params = trees["params"]
        else:
            params = {k[len("params/"):]: v for k, v in trees.items()
                      if k.startswith("params/")}
        return (params, trees.get("opt_state"), trees.get("model_state"))

    def save_checkpoint(self, params, opt_state, model_state, tag=None,
                        sync=False, epoch_boundary=False):
        if self.checkpoint_path is None:
            return
        from ..checkpoint.manager import host_snapshot
        mgr = self._ckpt_manager()
        tag = tag or f"iter_{self.state.iteration}"
        # the only work on the step loop: an OWNING device→host copy of
        # the live state (serialize + CRC + write + commit run on the
        # writer thread; `checkpoint/*` counters and the in-flight gauge
        # track it).  host_snapshot, not a view: the step loop donates
        # these buffers and would mutate a lazy copy mid-write.
        with self._wd_suspended(), self._rec().span("checkpoint.blocking"):
            host = host_snapshot((params, opt_state, model_state))
        # iterator position + loop rng make mid-epoch resume EXACT: the
        # epoch-seeded shuffle reproduces the order, batch_in_epoch says
        # where to skip to, rng reproduces the per-step dropout keys
        # (≙ DistriOptimizer.scala:878-914's cached-state retry)
        meta = {"epoch": self.state.epoch, "iteration": self.state.iteration,
                "batch_in_epoch": self.state.batch_in_epoch,
                "rng": None if getattr(self, "_loop_rng", None) is None
                else np.asarray(self._loop_rng).tolist(),
                "epoch_boundary": bool(epoch_boundary)}
        # deterministic data cursor (data/sharded.py): the exact read
        # position of the last CONSUMED batch rides in the manifest, so
        # resume re-positions the stream instead of replaying the epoch
        # head — no sample re-seen, none skipped
        if callable(getattr(self.dataset, "state", None)):
            meta["data_cursor"] = self.dataset.state()
        payload = self._ckpt_shards(host) if mgr.layout == "manifest" \
            else host
        with self._wd_suspended():      # sync commits block the loop
            mgr.save(payload, meta, tag, sync=sync,
                     trace_ctx=self._trace_ctx.child()
                     if self._trace_ctx is not None else None)

    def load_checkpoint(self):
        """Restore the newest INTACT checkpoint (manifest or legacy file
        layout): manifests are CRC-verified, a torn newest checkpoint
        falls back to the previous intact one, and a dangling/corrupt
        ``latest`` pointer degrades to a directory scan."""
        restored = self._ckpt_manager().restore_latest()
        if restored is None:
            return None
        kind, payload, meta = restored
        state = self._ckpt_unshard(payload) if kind == "manifest" \
            else payload
        self.state.epoch = meta["epoch"]
        self.state.iteration = meta["iteration"]
        self.state.batch_in_epoch = meta.get("batch_in_epoch", 0)
        self._resume_skip = self.state.batch_in_epoch
        cursor = meta.get("data_cursor")
        if cursor is not None and callable(getattr(self.dataset,
                                                   "restore", None)):
            # the dataset re-positions ITSELF — skipping batches on top
            # of the restored cursor would double-skip
            self.dataset.restore(cursor)
            self._resume_skip = 0
            self._cursor_resumed = True
        rng_saved = meta.get("rng")
        # owning copy (GL001): jnp.asarray could zero-copy adopt the
        # host buffer, and the step donates the rng key — same hazard
        # the comment below fixes for the state leaves
        self._resume_rng = None if rng_saved is None else \
            jnp.array(np.asarray(rng_saved, np.uint32), copy=True)
        restored = migrate_legacy_names(state, self.model)
        # jnp.array(copy=True), NOT jnp.asarray: asarray can zero-copy an
        # ALIGNED numpy buffer (alignment of np.load output varies with
        # the zip layout), and the first train step DONATES these leaves —
        # donating a buffer jax doesn't own lets XLA scribble over it and
        # corrupts the resumed state (seen as 1e9-garbage Adam moments)
        return jax.tree_util.tree_map(
            lambda v: jnp.array(v, copy=True)
            if isinstance(v, (np.ndarray, np.generic, jax.Array))
            else v, restored)

    # -- validation ------------------------------------------------------ #
    def _validate(self, params, model_state):
        if self.val_dataset is None or not self.val_methods:
            return None
        with self._wd_suspended(), self._rec().span("validation"):
            return self._validate_inner(params, model_state)

    def _validate_inner(self, params, model_state):
        # jit once per optimizer: rebuilding the closure each call would
        # recompile the full eval program at every validation trigger
        if not hasattr(self, "_eval_step") or self._eval_step is None:
            self._eval_step = jax.jit(make_eval_step(
                self.model, self._device_augment))
        eval_step = self._eval_step
        results = [None] * len(self.val_methods)
        for mb in self.val_dataset.data(train=False):
            x, y = _mb_to_arrays(mb)
            out = eval_step(params, model_state, x)
            for i, method in enumerate(self.val_methods):
                r = method(out, y)
                results[i] = r if results[i] is None else results[i] + r
        named = list(zip(self.val_methods, results))
        for method, res in named:
            print(f"  [validation] {method}: {res}")
            if self.val_summary is not None and res is not None:
                v, _ = res.result()
                self.val_summary.add_scalar(method.name, v,
                                            self.state.iteration)
        if named and named[0][1] is not None:
            self.state.score = named[0][1].result()[0]
        return named

    def _write_train_summary(self, params, opt_state):
        """Per-iteration scalars + trigger-gated Parameters histograms
        (≙ DistriOptimizer saveSummary; histograms pull params to host so
        they are gated by an explicit trigger)."""
        ts = self.train_summary
        it = self.state.iteration

        def fires(tag):
            trig = getattr(ts, "get_summary_trigger", lambda _t: None)(tag)
            return trig is None or trig(self.state)

        if fires("Loss"):
            ts.add_scalar("Loss", float(self.state.loss), it)
        if fires("LearningRate"):
            lr = self.optim_method.get_learning_rate(opt_state)
            ts.add_scalar("LearningRate", float(lr), it)
        ptrig = getattr(ts, "get_summary_trigger", lambda _t: None)(
            "Parameters")
        if ptrig is not None and ptrig(self.state):
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
                name = "/".join(str(getattr(p, "key", p)) for p in path)
                ts.add_histogram(name, np.asarray(leaf), it)

    # -- hooks overridden by DistriOptimizer ----------------------------- #
    def _wrap_optim(self, params):
        """Apply gradient-clipping wrapper around the user's OptimMethod."""
        optim = self.optim_method
        if self._grad_clip_norm or self._grad_clip_const:
            optim = _ClippedOptim(optim, self._grad_clip_norm,
                                  self._grad_clip_const)
        return optim

    def _make_step_builder(self, params_template, optim):
        def build_step():
            n_accum = self._grad_accum
            telemetry = self._begin_step_build()
            if n_accum > 1:
                fn = make_accum_train_step(self.model, self.criterion,
                                           optim, n_accum,
                                           self.mixed_precision,
                                           telemetry=telemetry,
                                           device_augment=self._device_augment)
            else:
                fn = make_train_step(self.model, self.criterion, optim,
                                     self.mixed_precision,
                                     telemetry=telemetry,
                                     device_augment=self._device_augment)
            return jax.jit(self._accounted(fn), donate_argnums=(0, 1, 2))
        return build_step

    def _layout_params(self, params):
        """Place initial params on devices (FSDP shards them)."""
        return params

    def _place_batch(self, x, y):
        return x, y

    def _params_for_eval(self, params):
        return params

    def _banner_suffix(self):
        return ""

    # -- main loop (shared by Local and Distri optimizers) --------------- #
    def optimize(self) -> Module:
        params, model_state = self.model.init_params(self.seed)
        if self.model._params is not None:
            params, model_state = self.model._params, self.model._state
        optim = self._wrap_optim(params)
        build_step = self._make_step_builder(params, optim)
        params = self._layout_params(params)
        opt_state = optim.init_state(params)
        if self.checkpoint_path:
            restored = self.load_checkpoint()
            if restored is not None:
                params, opt_state, model_state = restored

        step_fn = build_step()
        rng = jax.random.PRNGKey(self.seed + 13)
        if self._resume_rng is not None:
            rng = self._resume_rng
        self._loop_rng = rng
        if self._watchdog is not None:
            self._watchdog.start()      # no-op when already polling

        try:
            return self._optimize_loop(params, opt_state, model_state,
                                       rng, step_fn, build_step)
        finally:
            if self._watchdog is not None:
                # even when the loop raises (divergence, exhausted
                # retries): a dead loop is not a stalled one, and the
                # daemon must not pin /healthz at 503 forever
                self._watchdog.stop()

    def _optimize_loop(self, params, opt_state, model_state, rng,
                       step_fn, build_step) -> Module:
        stop = False
        retries = 0
        while not stop:
            if self.max_retries:
                # end-of-epoch snapshot for failure recovery (OWNING host
                # copies: device buffers may be donated/invalid after a
                # fault, and np.asarray views would be scribbled over by
                # the donating step loop — see checkpoint.host_snapshot)
                from ..checkpoint.manager import host_snapshot
                self._retry_cache = (
                    host_snapshot((params, opt_state, model_state)),
                    self.state.epoch, self.state.iteration, rng)
            try:
                params, opt_state, model_state, rng, step_fn, stop = \
                    self._run_epoch(params, opt_state, model_state, rng,
                                    step_fn, build_step)
            except DivergenceError as e:
                # sentinel-raised: never routed into the generic retry —
                # rollback restores the last COMMITTED checkpoint (the
                # flight dump already happened at raise time)
                mon = self._health_monitor
                if (mon is None or mon.policy != "rollback"
                        or mon.rollbacks >= self._max_rollbacks
                        or self.checkpoint_path is None):
                    raise
                if self._ckpt_mgr is not None:
                    self._ckpt_mgr.wait()   # an in-flight write may be
                    # the newest intact checkpoint — let it commit
                restored = self.load_checkpoint()
                if restored is None:
                    raise
                mon.rollbacks += 1
                mon.reset_statistics()
                mon.mark_recovered()
                print(f"[health] rollback {mon.rollbacks}/"
                      f"{self._max_rollbacks}: {e}; resumed from "
                      f"iteration {self.state.iteration}", flush=True)
                params, opt_state, model_state = restored
                if self._resume_rng is not None:
                    rng = self._resume_rng
            except Exception as e:
                if retries >= self.max_retries or self._retry_cache is None:
                    if self._flight is not None:
                        # leave a post-mortem before propagating (keyed:
                        # the chained excepthook won't dump it twice)
                        self._flight._dump_quietly(
                            f"exception:{type(e).__name__}",
                            {"error": repr(e)}, key=id(e))
                    raise
                retries += 1
                host, epoch, iteration, rng = self._retry_cache
                # prefer the newest mid-epoch checkpoint over the
                # epoch-start cache: finer-grained restart point
                restored = None
                if self.checkpoint_path:
                    try:
                        restored = self.load_checkpoint()
                    except Exception:
                        restored = None
                if restored is not None and self.state.iteration >= iteration:
                    print(f"[retry {retries}/{self.max_retries}] iteration "
                          f"{self.state.iteration} failed ({e!r}); resuming "
                          "from last checkpoint")
                    params, opt_state, model_state = restored
                    if self._resume_rng is not None:
                        rng = self._resume_rng
                else:
                    print(f"[retry {retries}/{self.max_retries}] epoch "
                          f"{self.state.epoch} failed ({e!r}); restoring "
                          "cached state")
                    # jax-owned copies: the next step donates these (see
                    # load_checkpoint's zero-copy/donation note)
                    params, opt_state, model_state = jax.tree_util.tree_map(
                        lambda v: jnp.array(v, copy=True)
                        if isinstance(v, (np.ndarray, np.generic))
                        else v, host)
                    self.state.epoch = epoch
                    self.state.iteration = iteration
                    self.state.batch_in_epoch = 0
                    self._resume_skip = 0

        self.model.set_params(self._params_for_eval(params), model_state)
        rec = self._rec()
        if self._ckpt_mgr is not None:
            # drain the async writer: when optimize() returns, every
            # triggered checkpoint is committed and durable
            self._ckpt_mgr.wait()
            # commits that landed after the last step record was cut
            # would otherwise be invisible to the sinks
            ck = {k: v for k, v in rec.snapshot()["counters"].items()
                  if k.startswith("checkpoint/")}
            if ck:
                rec.emit_record("checkpoint_summary", counters=ck)
        rec.flush()
        return self.model

    def _run_epoch(self, params, opt_state, model_state, rng, step_fn,
                   build_step):
        """One epoch of the shared loop; returns updated carry + stop."""
        stop = False
        self.state.epoch_finished = False
        epoch_start = time.time()
        n_seen = 0
        skip = self._resume_skip
        self._resume_skip = 0
        cursor_resumed = self._cursor_resumed
        self._cursor_resumed = False
        self.state.batch_in_epoch = skip

        rec = self._rec()

        self_staging = bool(getattr(self.dataset, "self_staging", False))
        pipeline_places = self_staging and callable(
            getattr(self.dataset, "set_place_fn", None))
        if pipeline_places:
            # the pipeline's staging thread runs the device placement
            # `staging_depth` batches ahead — h2d overlaps the step
            # without an extra loader layer
            self.dataset.set_place_fn(lambda b: self._place_batch(*b))

        def staged():
            try:
                it = self.dataset.data(train=True, epoch=self.state.epoch)
            except TypeError:   # dataset without epoch-seeded shuffling
                it = self.dataset.data(train=True)
            for _ in range(skip):      # resume: already-processed batches
                if next(it, None) is None:
                    return
            for mb in it:
                x, y = _mb_to_arrays(mb)
                if isinstance(mb, MiniBatch):
                    size = mb.size()
                else:       # (x, y) tuple, e.g. a streaming pipeline
                    size = int(jnp.shape(
                        jax.tree_util.tree_leaves(x)[0])[0])
                if pipeline_places:
                    # already placed on the pipeline's staging thread;
                    # re-placing here would add a per-batch tree_map
                    # and book a meaningless ~0 h2d span
                    yield (size, x, y)
                    continue
                # under prefetch this runs on the producer thread: the
                # h2d span for batch N+1 overlaps step N by design
                with rec.span("h2d"):
                    placed = self._place_batch(x, y)
                yield (size,) + tuple(placed)

        batches = staged()
        if self.prefetch_depth and not self_staging:
            # self-staging pipelines already prefetch + place internally;
            # another read-ahead layer would advance their cursor past
            # what training consumed and break exactly-once resume
            from ..data.device_loader import DeviceLoader
            batches = iter(DeviceLoader(batches, self.prefetch_depth,
                                        recorder=self._recorder))

        def fetch_timed(src):
            """Open the step record BEFORE fetching so data-fetch time is
            inside the step; preserves the for/else epoch-end path."""
            synchronous = not self.prefetch_depth
            while True:
                rec.start_step(self.state.iteration + 1)
                h2d0 = rec.span_value("h2d") if synchronous else 0.0
                t0 = time.time()
                item = next(src, None)
                wait = time.time() - t0
                if item is None:
                    rec.abort_step()
                    return
                if synchronous:
                    # without prefetch, staged()'s h2d span ran inside
                    # this fetch window: subtract it so the two spans
                    # stay disjoint in the step-time breakdown
                    wait = max(0.0, wait - (rec.span_value("h2d") - h2d0))
                rec.add_span("data_fetch", wait)
                yield wait, item

        for wait, (size, x, y) in fetch_timed(iter(batches)):
            rng, sub = jax.random.split(rng)
            t0 = time.time()
            self._loop_rng = rng
            (params, opt_state, model_state, loss), health = self._dispatch(
                step_fn, (params, opt_state, model_state, x, y, sub), (x, y))
            # keep `loss` on device: float()ing here would sync the host
            # with the accelerator every step and stall the input pipeline
            # (telemetry syncs it in end_step — the price of a loss curve)
            dispatch = time.time() - t0
            self.state.iteration += 1
            self.state.batch_in_epoch += 1
            self.state.loss = loss
            n_seen += size
            self.metrics.add("data wait time", wait)
            self.metrics.add("dispatch time", dispatch)
            if self.train_summary is not None:
                self._write_train_summary(params, opt_state)
            # step record (and its health-sentinel check) BEFORE the
            # iteration triggers: a diverged step must raise before the
            # checkpoint trigger can commit its poisoned params — a
            # rollback that restores NaN weights is no rollback.  (Spans
            # from a mid-epoch checkpoint/validation now fold into the
            # NEXT step's record, same as epoch-boundary ones always did.)
            if rec.enabled:
                # the record floats the loss, which waits for the step:
                # on the profiler's timeline that wait has this name
                with rec.span("step_record"):
                    self._emit_step_record(rec, size, loss, opt_state,
                                           health)
            fired_stop = self._fire_mid_epoch(params, opt_state, model_state)
            if fired_stop:
                stop = True
                break
        else:
            self.state.epoch_finished = True
            if n_seen == 0:
                if skip == 0 and not cursor_resumed:
                    raise ValueError(
                        "dataset produced no batches (batch_size larger "
                        "than the dataset with drop_last, or empty data)")
                # resumed exactly at an epoch boundary: the epoch's work —
                # including its validation/checkpoint — already happened
                # before the crash; just advance
                self.state.epoch += 1
                self.state.batch_in_epoch = 0
                return (params, opt_state, model_state, rng, step_fn,
                        self.end_when(self.state))
            self.state.loss = float(self.state.loss)
            dur = time.time() - epoch_start
            thru = n_seen / max(dur, 1e-9)
            self.metrics.add("throughput", thru)
            if self.train_summary is not None:
                self.train_summary.add_scalar("Throughput", thru,
                                              self.state.iteration)
            print(f"[epoch {self.state.epoch}] loss={self.state.loss:.4f} "
                  f"({n_seen} samples in {dur:.1f}s, {thru:.1f}/s"
                  f"{self._banner_suffix()})")
            if self.val_trigger is not None and self.val_trigger(self.state):
                self._validate(self._params_for_eval(params), model_state)
            if (self.checkpoint_trigger is not None
                    and self.checkpoint_trigger(self.state)):
                self.save_checkpoint(params, opt_state, model_state,
                                     tag=f"epoch_{self.state.epoch}",
                                     epoch_boundary=True)
            # metric-driven schedules (Plateau): factor changes are host
            # state baked into the trace, so a change forces a re-jit
            sched = getattr(self.optim_method, "schedule", None)
            if sched is not None and hasattr(sched, "on_epoch_end"):
                before = sched.current_factor
                metric = self.state.score if self.state.score is not None \
                    else self.state.loss
                if metric is not None:
                    sched.on_epoch_end(float(metric))
                if sched.current_factor != before:
                    step_fn = build_step()
            self.state.epoch += 1
            self.state.batch_in_epoch = 0
            if self.end_when(self.state):
                stop = True

        return params, opt_state, model_state, rng, step_fn, stop

    def _emit_step_record(self, rec: Recorder, size, loss, opt_state,
                          health):
        """Fold this iteration's telemetry into one step record."""
        if (not rec.sinks and self._health_monitor is None
                and rec.series is None):
            # trace-only recorder: keep the step/trace cadence but skip
            # the scalars — recording `loss` would host-sync the device
            # every step for a record nobody consumes (an attached
            # health monitor or keep_series= store IS a consumer: both
            # need the floats)
            rec.end_step(self.state.iteration)
            return
        extra = {}
        try:
            extra["learning_rate"] = float(
                self.optim_method.get_learning_rate(opt_state))
        except Exception:
            pass    # custom OptimMethods without a readable lr
        self._record_step(self.state.iteration, size, loss, health, extra)

    def _fire_mid_epoch(self, params, opt_state, model_state) -> bool:
        """iteration-level triggers; returns True if training should end."""
        st = self.state
        if (self._preemption is not None and self._preemption.requested
                and self.checkpoint_path is not None):
            # SIGTERM: finish any in-flight async write, commit a final
            # checkpoint synchronously, and stop the loop cleanly
            self.save_checkpoint(params, opt_state, model_state,
                                 tag=f"preempt_iter_{st.iteration}",
                                 sync=True)
            if self._flight is not None:
                # post-commit dump rides alongside the preemption
                # checkpoint: its counters show the final commit
                self._flight._dump_quietly("preemption")
            print(f"[preemption] final checkpoint at iteration "
                  f"{st.iteration} committed; stopping cleanly", flush=True)
            return True
        if self.val_trigger is not None and not isinstance(
                self.val_trigger, type(Trigger.every_epoch())) \
                and self.val_trigger(st):
            self._validate(self._params_for_eval(params), model_state)
        if (self.checkpoint_trigger is not None
                and not isinstance(self.checkpoint_trigger,
                                   type(Trigger.every_epoch()))
                and self.checkpoint_trigger(st)):
            self.save_checkpoint(params, opt_state, model_state)
        stream = getattr(self, "_weight_stream", None)
        if stream is not None:
            # snapshot happens synchronously inside (owning copies);
            # the publish itself rides the stream's worker thread
            stream.maybe_publish(params, state=st)
        return (not isinstance(self.end_when, type(Trigger.max_epoch(1)))
                and self.end_when(st))


class LocalOptimizer(Optimizer):
    """Single-chip training (≙ optim/LocalOptimizer.scala). The reference's
    multi-threaded subbatching is replaced by one fused XLA step."""


class _ClippedOptim(OptimMethod):
    """Gradient clipping wrapper (≙ Optimizer.setGradientClipping*).

    `sum_axis` is set when gradients are sharded across a mesh axis (FSDP):
    the local sum of squares is psum'ed so every shard clips by the GLOBAL
    L2 norm, matching the replicated-gradient semantics.
    """

    def __init__(self, inner, clip_norm=None, clip_const=None, sum_axis=None,
                 sharded_mask=None):
        super().__init__()
        self.inner = inner
        self.clip_norm = clip_norm
        self.clip_const = clip_const
        self.sum_axis = sum_axis
        # bool pytree: which grad leaves are dim-0 shards (summed via psum)
        # vs fully replicated (counted once)
        self.sharded_mask = sharded_mask

    def init_state(self, params):
        return self.inner.init_state(params)

    def get_learning_rate(self, state):
        return self.inner.get_learning_rate(state)

    def update(self, grads, params, state):
        if self.clip_const is not None:
            lo, hi = self.clip_const
            grads = jax.tree_util.tree_map(
                lambda g: jnp.clip(g, lo, hi), grads)
        if self.clip_norm is not None:
            if self.sum_axis is not None and self.sharded_mask is not None:
                leaves = jax.tree_util.tree_leaves(grads)
                mask = jax.tree_util.tree_leaves(self.sharded_mask)
                sq_sh = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                            for g, m in zip(leaves, mask) if m) + 0.0
                sq_rep = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                             for g, m in zip(leaves, mask) if not m) + 0.0
                sq = jax.lax.psum(sq_sh, self.sum_axis) + sq_rep
            else:
                sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads))
                if self.sum_axis is not None:
                    sq = jax.lax.psum(sq, self.sum_axis)
            total = jnp.sqrt(sq)
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(total, 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        return self.inner.update(grads, params, state)


def _mb_to_arrays(mb):
    if isinstance(mb, MiniBatch):
        return mb.get_input(), mb.get_target()
    if isinstance(mb, tuple) and len(mb) == 2:
        return mb
    raise TypeError(f"unsupported batch type {type(mb)}")
