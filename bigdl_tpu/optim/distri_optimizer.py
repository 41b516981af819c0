"""Distributed synchronous-SGD driver (≙ optim/DistriOptimizer.scala +
parameters/AllReduceParameter.scala).

Reference architecture: Spark tasks hold model replicas; each iteration
zips a data partition with the model cache, runs local fwd/bwd, slices the
gradient into partitions on the block manager, every partition aggregates
its slice, applies the OptimMethod there, and replicas fetch updated weight
slices (a partitioned parameter server over TCP).

TPU-native architecture: ONE jitted SPMD program per iteration via
``jax.shard_map`` over a `Mesh`:

  * dp (replicated params):   local fwd/bwd -> psum(grads, 'dp') -> update
                              — all-reduce rides ICI/DCN collectives.
  * fsdp (sharded params):    params + optimizer state sharded on dim 0;
                              all_gather(params) -> fwd/bwd ->
                              psum_scatter(grads) -> sharded update
                              — comm-equivalent to the reference's
                              partitioned parameter server, memory scales
                              1/N per chip.
  * gradient compression:     bf16/fp16 cast pre-reduce
                              (≙ FP16CompressedTensor).

The host loop (triggers, validation, checkpoints, summaries, metrics) is
shared with LocalOptimizer.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.module import Ctx
from ..parallel import mesh as mesh_lib
from ..parallel.allreduce import (allreduce_gradients,
                                  reduce_scatter_gradients, allgather_params,
                                  shardable_mask_dim0)
from ..parallel.bucketer import GradBucketer
from ..parallel.zero import Zero1Layout, Zero1Optim
from .optim_method import LAMB, LARS
from .optimizer import (Optimizer, _mb_to_arrays, _ClippedOptim,
                        health_scalars, make_accum_grads,
                        mask_frozen_grads)
from .trigger import Trigger


def fsdp_opt_state_specs(params_template, shardable, optim,
                         spec: P = P("dp")):
    """PartitionSpecs for an OptimMethod's state under FSDP.

    Optimizer-state moment trees mirror the param tree structure (every
    OptimMethod stores them as ``{"m": <params-shaped tree>, …}``), so
    shardings are derived by TREE-PATH correspondence: an opt-state leaf
    whose path suffix names an existing param (and matches its shape)
    inherits that param's spec; everything else (step counters, scalars,
    non-moment buffers) stays replicated.  Matching on (shape, dtype)
    alone would wrongly dim-0-shard state belonging to a replicated
    param that happens to share shape+dtype with a sharded one.

    ``spec`` is the PartitionSpec a *sharded* moment leaf takes —
    ``P("dp")`` for the flat fsdp/zero1 paths, ``P(("pp", "dp"))`` for
    the composed pipeline path where the shard space is additionally
    stage-stacked on dim 0.
    """
    opt_state_template = jax.eval_shape(optim.init_state, params_template)
    p_paths, _ = jax.tree_util.tree_flatten_with_path(params_template)
    s_flat = jax.tree_util.tree_leaves(shardable)
    by_path = {tuple(path): (tuple(leaf.shape), bool(s))
               for (path, leaf), s in zip(p_paths, s_flat)}

    def spec_for_opt_leaf(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        for i in range(len(path)):
            hit = by_path.get(tuple(path[i:]))
            if hit is not None and hit[0] == shape:
                return spec if hit[1] else P()
        return P()

    return jax.tree_util.tree_map_with_path(spec_for_opt_leaf,
                                            opt_state_template)


class DistriOptimizer(Optimizer):
    def __init__(self, model, training_set, criterion, batch_size=None,
                 mesh: Optional[Mesh] = None, compress: Optional[str] = None,
                 fsdp: bool = False, seed: int = 0, zero1: bool = False,
                 bucket_bytes: Optional[int] = None,
                 fused_optim: bool = False):
        """Step-time knobs beyond the reference surface (all default-off;
        the plain replicated dp step stays the default until a config's
        parity suite passes — see docs/performance.md):

        ``zero1``        ZeRO-1 sharded weight update: reduce-scatter
                         grads, update only this replica's 1/N shard of
                         params + optimizer state (moments live sharded,
                         1/N memory), all-gather the updated params.
                         Elementwise optimizers only; mutually exclusive
                         with ``fsdp``.
        ``bucket_bytes`` exchange gradients in flat buckets of this many
                         bytes (per-bucket collectives the async
                         scheduler overlaps with the tail of backward)
                         instead of one monolithic all-reduce; with
                         ``zero1`` it sizes the flat buckets of the
                         non-dim0-shardable leaves.
        ``fused_optim``  route the update through the single-pass Pallas
                         kernels (``bigdl_tpu.kernels``) when the
                         OptimMethod supports ``fused`` (SGD/Adam/AdamW).
        """
        super().__init__(model, training_set, criterion,
                         batch_size=batch_size, seed=seed)
        self.mesh = mesh or mesh_lib.get_mesh()
        if "dp" not in self.mesh.axis_names:
            raise ValueError("DistriOptimizer mesh needs a 'dp' axis")
        if zero1 and fsdp:
            raise ValueError(
                "zero1 and fsdp are mutually exclusive: fsdp already "
                "shards params AND optimizer state (ZeRO-3); zero1 "
                "shards only the update/optimizer state")
        self.compress = compress
        self.fsdp = fsdp
        self.zero1 = bool(zero1)
        self.bucket_bytes = bucket_bytes
        self.fused_optim = bool(fused_optim)
        self._z1: Optional[Zero1Layout] = None

    # ------------------------------------------------------------------ #
    def _build_step(self, params_template, optim, telemetry=False):
        model, criterion = self.model, self.criterion
        mixed = self.mixed_precision
        compress = self.compress
        n_dp = self.mesh.shape["dp"]

        n_accum = self._grad_accum

        augment = self._device_augment

        def local_loss(p, model_state, x, y, rng):
            # device-side augmentation on THIS shard's slice of the
            # batch: per-shard rng is already folded by axis_index, so
            # every image gets its own crop/flip stream (uint8 wire)
            from .optimizer import apply_device_augment
            x, rng = apply_device_augment(augment, x, rng)
            if mixed:
                x = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.bfloat16)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, x)
            ctx = Ctx(state=model_state, training=True, rng_key=rng)
            out = model.apply(p, x, ctx)
            out = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
                else a, out)
            loss = criterion.loss(out, y)
            for sl in ctx.side_losses:
                loss = loss + sl
            loss = loss + model.regularization_loss(p)
            return loss, ctx.new_state

        # per-shard gradient accumulation: each shard scans its own
        # microbatches BEFORE the psum, so collective traffic is one op
        # regardless of n_accum (reg term stays inside local_loss: counted
        # n times then divided by n, i.e. added once)
        local_grads = make_accum_grads(local_loss, n_accum)

        if self.zero1:
            return self._build_step_zero1(params_template, optim,
                                          local_grads, telemetry)

        if not self.fsdp:
            bucketer = GradBucketer(params_template,
                                    bucket_bytes=self.bucket_bytes) \
                if self.bucket_bytes else None

            def step(params, opt_state, model_state, x, y, rng):
                rng = jax.random.fold_in(rng, lax.axis_index("dp"))
                (loss, upd), grads = local_grads(params, model_state,
                                                 x, y, rng)
                grads = mask_frozen_grads(model, grads)
                if bucketer is not None:
                    # per-bucket collectives: XLA's async scheduler can
                    # start each bucket's exchange before backward ends
                    grads = bucketer.allreduce(grads, "dp",
                                               compress=compress)
                else:
                    grads = allreduce_gradients(grads, "dp",
                                                compress=compress)
                new_params, new_opt = optim.update(grads, params, opt_state)
                merged = dict(model_state)
                merged.update(upd)
                merged = lax.pmean(merged, "dp")  # keep BN stats replicated
                out = (new_params, new_opt, merged, lax.pmean(loss, "dp"))
                if telemetry:
                    # grads/params are replicated post-allreduce: norms
                    # need no extra collective
                    out += (health_scalars(grads, params, new_params),)
                return out

            specs_in = (P(), P(), P(), P("dp"), P("dp"), P())
            specs_out = (P(), P(), P(), P()) + ((P(),) if telemetry else ())
            return jax.jit(
                jax.shard_map(self._accounted(step), mesh=self.mesh,
                              in_specs=specs_in, out_specs=specs_out,
                              check_vma=False),
                donate_argnums=(0, 1, 2)), None

        # ---- FSDP: params sharded on dim 0 where divisible -------------- #
        shardable = shardable_mask_dim0(params_template, n_dp)

        def step(params_sh, opt_state, model_state, x, y, rng):
            rng = jax.random.fold_in(rng, lax.axis_index("dp"))
            full = allgather_params(params_sh, "dp", mask=shardable)
            (loss, upd), grads = local_grads(full, model_state, x, y, rng)
            grads = mask_frozen_grads(model, grads)
            g_sh = reduce_scatter_gradients(grads, "dp", mask=shardable)
            new_params_sh, new_opt = optim.update(g_sh, params_sh, opt_state)
            merged = dict(model_state)
            merged.update(upd)
            merged = lax.pmean(merged, "dp")
            out = (new_params_sh, new_opt, merged, lax.pmean(loss, "dp"))
            if telemetry:
                # shard norms psum'ed to the GLOBAL value on every shard
                out += (health_scalars(g_sh, params_sh, new_params_sh,
                                       axis_name="dp",
                                       sharded_mask=shardable),)
            return out

        p_specs = jax.tree_util.tree_map(
            lambda s: P("dp") if s else P(), shardable,
            is_leaf=lambda v: isinstance(v, bool))
        o_specs = fsdp_opt_state_specs(params_template, shardable, optim)
        specs_in = (p_specs, o_specs, P(), P("dp"), P("dp"), P())
        specs_out = (p_specs, o_specs, P(), P()) \
            + ((P(),) if telemetry else ())
        return jax.jit(
            jax.shard_map(self._accounted(step), mesh=self.mesh,
                          in_specs=specs_in, out_specs=specs_out,
                          check_vma=False),
            donate_argnums=(0, 1, 2)), shardable

    # ---- ZeRO-1: replicated params, sharded update + optimizer state -- #
    def _build_step_zero1(self, params_template, optim, local_grads,
                          telemetry):
        """One shard_map'ped step: local fwd/bwd on REPLICATED params ->
        reduce-scatter grads into shard space -> each replica updates
        only its 1/N param shard with its 1/N optimizer-state shard ->
        all-gather the updated params (arXiv:2004.13336).  Collective
        volume equals the all-reduce (S·(n−1)/n each way); update FLOPs
        and optimizer-state memory drop to 1/N."""
        model = self.model
        compress = self.compress
        z1 = self._z1

        def step(params, opt_state, model_state, x, y, rng):
            rng = jax.random.fold_in(rng, lax.axis_index("dp"))
            (loss, upd), grads = local_grads(params, model_state, x, y, rng)
            grads = mask_frozen_grads(model, grads)
            idx = lax.axis_index("dp")
            g_sh = z1.scatter_grads(grads, "dp", compress=compress)
            p_sh = z1.local_shard(params, idx)
            new_p_sh, new_opt = optim.update(g_sh, p_sh, opt_state)
            new_params = z1.gather_params(new_p_sh, "dp")
            merged = dict(model_state)
            merged.update(upd)
            merged = lax.pmean(merged, "dp")
            out = (new_params, new_opt, merged, lax.pmean(loss, "dp"))
            if telemetry:
                # every shard-space leaf holds 1/N of a global tensor:
                # psum the shard norms so all replicas see global values
                mask_sh = jax.tree_util.tree_map(lambda _: True, g_sh)
                out += (health_scalars(g_sh, p_sh, new_p_sh,
                                       axis_name="dp",
                                       sharded_mask=mask_sh),)
            return out

        # optimizer state mirrors the shard space; derive its P("dp")
        # specs by tree-path correspondence against the global shard
        # space (every entry dim-0-sharded, scalars replicated)
        sst = jax.eval_shape(z1.global_shard_space, params_template)
        all_sharded = jax.tree_util.tree_map(lambda _: True, sst)
        o_specs = fsdp_opt_state_specs(sst, all_sharded, optim.inner)
        specs_in = (P(), o_specs, P(), P("dp"), P("dp"), P())
        specs_out = (P(), o_specs, P(), P()) \
            + ((P(),) if telemetry else ())
        return jax.jit(
            jax.shard_map(self._accounted(step), mesh=self.mesh,
                          in_specs=specs_in, out_specs=specs_out,
                          check_vma=False),
            donate_argnums=(0, 1, 2)), None

    def _shard_params_host(self, params, shardable):
        """Slice host params to this shard layout for FSDP init (global view:
        jit handles placement; we just reshape logically sharded leaves)."""
        return params  # global arrays; jit shards via in_shardings

    # ------------------------------------------------------------------ #
    # -- hook overrides: the epoch loop itself lives in Optimizer -------- #
    def _wrap_optim(self, params):
        optim = self.optim_method
        if self.fused_optim:
            if not hasattr(optim, "fused"):
                raise ValueError(
                    f"fused_optim=True: {type(optim).__name__} has no "
                    "fused kernel (supported: SGD, Adam, AdamW)")
            # shallow copy, never mutate the user's instance: the same
            # OptimMethod reused in another optimizer WITHOUT the flag
            # must keep the default (unfused) path
            import copy
            optim = copy.copy(optim)
            optim.fused = True
        if self.zero1 and isinstance(optim, (LARS, LAMB)):
            raise ValueError(
                f"zero1 cannot shard {type(optim).__name__}: its "
                "per-TENSOR trust ratios need whole-tensor norms, and a "
                "dim-0 shard's norm is not the tensor's norm.  Use fsdp "
                "(whole tensors stay visible to the update) or an "
                "elementwise optimizer (SGD/Adam/AdamW/...)")
        if self._grad_clip_norm or self._grad_clip_const:
            if self.fsdp:
                # gradients inside shard_map are dim-0 shards: the L2 norm
                # must psum shard contributions to be global & consistent
                n_dp = self.mesh.shape["dp"]
                mask = shardable_mask_dim0(params, n_dp)
                optim = _ClippedOptim(optim, self._grad_clip_norm,
                                      self._grad_clip_const, sum_axis="dp",
                                      sharded_mask=mask)
            elif self.zero1:
                # EVERY shard-space leaf holds 1/N of a global tensor:
                # psum of all shard sums-of-squares IS the global norm
                optim = _ClippedOptim(optim, self._grad_clip_norm,
                                      self._grad_clip_const, sum_axis="dp")
            else:
                optim = _ClippedOptim(optim, self._grad_clip_norm,
                                      self._grad_clip_const)
        if self.zero1:
            self._z1 = Zero1Layout(params, self.mesh.shape["dp"],
                                   bucket_bytes=self.bucket_bytes)
            optim = Zero1Optim(optim, self._z1)
        return optim

    def _make_step_builder(self, params_template, optim):
        def build_step():
            return self._build_step(
                params_template, optim,
                telemetry=self._begin_step_build())[0]
        return build_step

    def _layout_params(self, params):
        if not self.fsdp:
            return params
        mask = shardable_mask_dim0(params, self.mesh.shape["dp"])
        return jax.tree_util.tree_map(
            lambda p, s: jax.device_put(
                p, NamedSharding(self.mesh, P("dp") if s else P())),
            params, mask)

    def _place_batch(self, x, y):
        sharding = NamedSharding(self.mesh, P("dp"))
        put = lambda a: jax.device_put(a, sharding)
        x = jax.tree_util.tree_map(put, x)
        if y is not None:
            y = jax.tree_util.tree_map(put, y)
        return x, y

    def _params_for_eval(self, params):
        if not self.fsdp:
            return params
        # params are globally-shaped jax.Arrays sharded over dp;
        # re-replicate for single-program eval / the local model
        return jax.tree_util.tree_map(
            lambda p: jax.device_put(p, NamedSharding(self.mesh, P())),
            params)

    def _banner_suffix(self):
        return (f", dp={self.mesh.shape['dp']}"
                + (", fsdp" if self.fsdp else "")
                + (", zero1" if self.zero1 else "")
                + (f", buckets={self.bucket_bytes}" if self.bucket_bytes
                   else "")
                + (", fused" if self.fused_optim else ""))
