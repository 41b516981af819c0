"""Pipeline parallelism over the ``pp`` mesh axis.

GSPMD does not partition by *layer*; pipelining is inherently a manual
schedule, so this is a shard_map program: each pp rank holds one stage's
parameters (stacked layer params sharded on their leading axis), and a
``lax.scan`` runs the GPipe schedule — microbatches enter stage 0, flow
stage-to-stage via ``lax.ppermute`` (one ICI hop per tick), and leave from
the last stage.  With M microbatches and S stages the scan runs M + S - 1
ticks; every tick all stages compute concurrently (the bubble is the usual
(S-1)/(M+S-1)).

AD: ppermute transposes to the reverse rotation and the scan transposes to
the reverse schedule, so ``jax.grad`` through :func:`pipeline_run` is the
standard 1F1B-equivalent backward pipeline — no hand-written backward.

The reference has nothing comparable (Spark tasks parallelise over *data*
only); this is part of going beyond its scale (SURVEY §2 #30).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..observability.host import TelemetryHost


def pipeline_run(stage_fn: Callable, stage_params, microbatches,
                 axis_name: str = "pp"):
    """Run the GPipe schedule. Call inside shard_map.

    stage_fn: (params_of_my_stage, x) -> y   (x, y same shape)
    stage_params: this rank's stage parameters (device-varying pytree)
    microbatches: (M, mb, ...) — the full microbatched input, replicated;
                  only stage 0 reads it.
    Returns (M, mb, ...) outputs, valid on the *last* stage (zeros
    elsewhere); weight per-stage reductions with :func:`last_stage_mask`.
    """
    n_stages = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    # shift-down (no wraparound): stage i -> i+1; stage 0 receives zeros
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    is_first = (idx == 0)
    is_last = (idx == n_stages - 1)

    def tick(carry, t):
        state, outputs = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        feed = lax.dynamic_index_in_dim(microbatches, mb_idx, 0,
                                        keepdims=False)
        x = jnp.where(is_first, feed, state)
        y = stage_fn(stage_params, x)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        write = is_last & (t >= n_stages - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, cur), out_idx, 0)
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    init = (jnp.zeros(mb_shape, microbatches.dtype),
            jnp.zeros((n_micro,) + mb_shape, microbatches.dtype))
    (_, outputs), _ = lax.scan(tick, init, jnp.arange(n_micro + n_stages - 1))
    return outputs


def last_stage_mask(axis_name: str = "pp"):
    """1.0 on the last pp rank, 0.0 elsewhere — multiply the loss by this
    and psum over pp so earlier stages contribute zero."""
    idx = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)
    return (idx == n - 1).astype(jnp.float32)


def pipelined(stage_fn: Callable, mesh: Mesh, n_microbatches: int,
              axis_name: str = "pp"):
    """Wrap a stage function into a global-array pipelined forward.

    Returns ``f(stacked_params, x)`` where stacked_params leaves have a
    leading n_stages axis (sharded over pp) and x is (batch, ...);
    the result is the full-model output (batch, ...), replicated.
    """
    def global_fn(stacked_params, x):
        def local(params_stack, xs):
            # my slice of the stacked layer params: leading dim 1 -> squeeze
            my = jax.tree_util.tree_map(lambda p: p[0], params_stack)
            mbs = xs.reshape((n_microbatches, -1) + xs.shape[1:])
            outs = pipeline_run(stage_fn, my, mbs, axis_name)
            outs = outs.reshape(xs.shape)
            # broadcast the last stage's result to every rank
            outs = lax.psum(outs * last_stage_mask(axis_name), axis_name)
            return outs

        in_specs = (jax.tree_util.tree_map(lambda _: P(axis_name),
                                           stacked_params), P())
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=P(),
                             check_vma=False)(stacked_params, x)

    return global_fn


# --------------------------------------------------------------------- #
# transformer pipeline trainer                                          #
# --------------------------------------------------------------------- #
class PipelineLMTrainer(TelemetryHost):
    """GPipe training for TransformerLM over a 'pp' mesh axis (x optional
    'dp', 'tp', 'sp'): each pp rank owns n_layers/n_stages blocks (params
    stacked on a leading layer axis, sharded over pp); microbatches flow
    through pipeline_run's ppermute schedule; embedding feeds stage 0 and
    the LM head + loss run on the last stage (loss is masked+psum'd, so
    AD routes every gradient to the stage that owns it).  tp and sp are
    AUTO (GSPMD) axes inside the manual pp/dp shard_map: tensor parallel
    via the megatron pspecs, sequence parallel by sharding the sequence
    dim of the token batch.

    By default the optimizer update happens on the global (sharded)
    arrays outside the shard_map — GSPMD keeps the pp layout for block
    params/moments.  The composed-mesh roofline knobs (all default-off,
    same semantics as ``DistriOptimizer``; see docs/distributed.md §
    Composed parallelism):

    ``zero1``        ZeRO-1 over the **dp axis of the pp(/tp)-sharded
                     model** (arXiv:2004.13336 composed with GPipe):
                     grads reduce-scatter into each stage's shard space
                     over dp, each (stage, dp-rank) updates only its
                     1/dp slice with its 1/dp moment shard — optimizer
                     state lives ``P(("pp", "dp"))``, 1/(pp·dp) per
                     device by sharding metadata — and updated params
                     ride an all-gather back.  Elementwise optimizers
                     only; grad-clip/health norms psum over the right
                     axis groups (rest over dp, blocks over dp×pp).
    ``bucket_bytes`` exchange dp-group gradients in flat single-dtype
                     buckets (one collective per bucket — the dp bucket
                     stream, accounted ``comm/group.dp.*``); with
                     ``zero1`` it sizes the flat shard-space buckets.
    ``compress``     "fp16"/"bf16" dp-group wire compression (the mean
                     travels, pre-scaled in fp32 — fp16-sum-safe).
    ``fused_optim``  route the update through the Pallas kernels
                     (``bigdl_tpu.kernels``) when the OptimMethod
                     supports ``fused``.
    ``overlap_grad_chunks``
                     split the microbatch train into this many gradient
                     chunks: each chunk runs its own GPipe schedule and
                     issues its dp-group collectives as soon as its
                     backward finishes — **under the next chunk's
                     pipeline bubble** instead of after the last
                     microbatch (XLA's async collectives overlap them
                     with the next chunk's compute).  Must divide
                     ``n_microbatches``.  Chunked accumulation
                     reassociates the token-mean (documented-ulp class,
                     see docs/checkpointing.md taxonomy).
    ``clip_norm``    global-L2 gradient clipping, axis-group-scoped on
                     the zero1 path (shard sums-of-squares psum'd over
                     dp for the replicated rest, dp×pp for the stage
                     shards).
    """

    _items_counter = "tokens_total"
    _resize_adopted_ledger = True

    def __init__(self, model, optim, mesh, n_microbatches=4, seed=0,
                 loss_chunk=None, zero1=False, bucket_bytes=None,
                 compress=None, fused_optim=False, overlap_grad_chunks=1,
                 clip_norm=None):
        if model.frozen_param_names():
            raise NotImplementedError(
                "Module.freeze is not supported by PipelineLMTrainer "
                "(block params are stacked per stage, losing per-module "
                "identity); unfreeze or use SpmdTrainer")
        cfg = model.cfg
        if cfg.dropout:
            raise ValueError("PipelineLMTrainer requires dropout=0.0")
        if "pp" not in mesh.axis_names:
            raise ValueError("mesh needs a 'pp' axis")
        self.model = model
        self.optim = optim
        self.mesh = mesh
        self.n_micro = n_microbatches
        self.seed = seed
        self.n_stages = mesh.shape["pp"]
        if cfg.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers={cfg.n_layers} must divide by pp={self.n_stages}")
        n_dp = mesh.shape.get("dp", 1)
        if (zero1 or bucket_bytes or compress) and n_dp < 2:
            raise ValueError(
                "zero1/bucket_bytes/compress drive the dp-group gradient "
                f"exchange: the mesh needs a dp axis > 1 (got dp={n_dp})")
        if compress not in (None, "fp16", "float16", "bf16", "bfloat16"):
            # a typo'd mode would silently train at full fp32 wire
            raise ValueError(
                f"unknown compress mode {compress!r} "
                "(fp16/float16/bf16/bfloat16)")
        if zero1:
            from ..optim.optim_method import LAMB, LARS
            if isinstance(optim, (LARS, LAMB)):
                raise ValueError(
                    f"zero1 cannot shard {type(optim).__name__}: its "
                    "per-TENSOR trust ratios need whole-tensor norms, "
                    "and a dim-0 shard's norm is not the tensor's norm")
        self.zero1 = bool(zero1)
        self.bucket_bytes = bucket_bytes
        self.compress = compress
        self.clip_norm = clip_norm
        if fused_optim:
            if not hasattr(optim, "fused"):
                raise ValueError(
                    f"fused_optim=True: {type(optim).__name__} has no "
                    "fused kernel (supported: SGD, Adam, AdamW)")
            import copy
            # shallow copy, never mutate the user's instance (reuse
            # elsewhere without the flag keeps the default path)
            self.optim = optim = copy.copy(optim)
            optim.fused = True
        self.fused_optim = bool(fused_optim)
        self.overlap_chunks = int(overlap_grad_chunks)
        if self.overlap_chunks < 1 or n_microbatches % self.overlap_chunks:
            raise ValueError(
                f"overlap_grad_chunks={overlap_grad_chunks} must be >= 1 "
                f"and divide n_microbatches={n_microbatches}")
        self.template = model.blocks[0]
        self._block_names = [b.name for b in model.blocks]
        # chunked head+loss on the last stage (same lever as
        # SpmdTrainer(loss_chunk=...): logits capped at (B, c, V))
        self.loss_chunk = loss_chunk
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self._step_count = 0
        TelemetryHost.__init__(self)
        self._z1_rest = None
        self._z1_blocks = None

    # -- param plumbing ------------------------------------------------ #
    def _rename(self, tree, src, dst):
        return {k.replace(src, dst): {kk: vv for kk, vv in v.items()}
                for k, v in tree.items()}

    def _split(self, params):
        """model params -> (rest, blocks-stacked-on-leading-layer-axis)."""
        block_prefixes = tuple(n + "." for n in self._block_names)
        rest = {k: v for k, v in params.items()
                if not k.startswith(block_prefixes)}
        per_block = []
        for name in self._block_names:
            sub = {k: v for k, v in params.items()
                   if k.startswith(name + ".")}
            per_block.append(self._rename(sub, name, self.template.name))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *per_block)
        return rest, stacked

    def merge(self):
        """Back to the model's flat params dict (host-side convenience)."""
        rest, stacked = self.params["rest"], self.params["blocks"]
        out = dict(rest)
        for i, name in enumerate(self._block_names):
            sub = jax.tree_util.tree_map(lambda l: l[i], stacked)
            out.update(self._rename(sub, self.template.name, name))
        return out

    # -- setup --------------------------------------------------------- #
    def _has_tp(self):
        return "tp" in self.mesh.axis_names and self.mesh.shape["tp"] > 1

    def _stacked_placement(self, blocks):
        """Placement specs for the layer-stacked block params: always
        P('pp') on the stacking axis; with a tp mesh axis the inner dims
        additionally take the template module's megatron layout (its
        ``pspec``) — tensor parallel INSIDE each pipeline stage."""
        if not self._has_tp():
            return jax.tree_util.tree_map(lambda _: P("pp"), blocks)
        from .spmd import _filter_spec     # drop axes absent from mesh
        by_mod = {m.name: getattr(m, "pspec", {})
                  for m in self.template.modules()}
        out = {}
        for mod_name, sub in blocks.items():
            ps = by_mod.get(mod_name, {})
            out[mod_name] = {
                k: (P("pp", *_filter_spec(ps[k], self.mesh))
                    if k in ps and ps[k] is not None else P("pp"))
                for k in sub}
        return out

    def init(self):
        from jax.sharding import NamedSharding
        model_params = self.model.init(jax.random.PRNGKey(self.seed))
        rest, blocks = self._split(model_params)
        put = lambda t, spec: jax.tree_util.tree_map(
            lambda l: jax.device_put(l, NamedSharding(self.mesh, spec)), t)
        blk_place = self._stacked_placement(blocks)
        self.params = {
            "rest": put(rest, P()),
            "blocks": jax.tree_util.tree_map(
                lambda l, sp: jax.device_put(
                    l, NamedSharding(self.mesh, sp)), blocks, blk_place,
                is_leaf=lambda v: not isinstance(v, dict))}
        if self.zero1:
            self.opt_state = self._init_zero1_state(rest, blocks)
        else:
            self.opt_state = jax.jit(self.optim.init_state)(self.params)
        self._build()
        return self

    # -- zero1 over the dp axis of the pp-sharded model ----------------- #
    def _init_zero1_state(self, rest, blocks):
        """Shard-space optimizer state for the composed zero1 path.

        Two layouts, because a flat bucket must never mix pp-replicated
        and pp-varying leaves: ``rest`` (embed/norm/head — identical on
        every stage) sharded 1/dp, and the per-STAGE slice of the
        stacked blocks sharded 1/dp within each stage.  The outside-jit
        storage stacks every stage's shard space on dim 0, placed
        ``P(("pp", "dp"))`` — by sharding metadata each device holds
        exactly 1/(pp·dp) of the block moments, the composed-mesh
        memory claim."""
        from jax.sharding import NamedSharding
        from ..optim.distri_optimizer import fsdp_opt_state_specs
        from .zero import Zero1Layout
        n_dp = self.mesh.shape["dp"]
        S = self.n_stages
        local_blocks = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(
                (l.shape[0] // S,) + tuple(l.shape[1:]), l.dtype), blocks)
        self._z1_rest = Zero1Layout(rest, n_dp,
                                    bucket_bytes=self.bucket_bytes)
        self._z1_blocks = Zero1Layout(local_blocks, n_dp,
                                      bucket_bytes=self.bucket_bytes)
        space_r = self._z1_rest.stacked_space_zeros(1)
        space_b = self._z1_blocks.stacked_space_zeros(S)
        every = lambda t: jax.tree_util.tree_map(lambda _: True, t)
        self._o_specs = {
            "rest": fsdp_opt_state_specs(space_r, every(space_r),
                                         self.optim, spec=P("dp")),
            "blocks": fsdp_opt_state_specs(space_b, every(space_b),
                                           self.optim,
                                           spec=P(("pp", "dp")))}
        state = {"rest": jax.jit(self.optim.init_state)(space_r),
                 "blocks": jax.jit(self.optim.init_state)(space_b)}
        return jax.tree_util.tree_map(
            lambda l, sp: jax.device_put(l, NamedSharding(self.mesh, sp)),
            state, self._o_specs)

    def _build(self):
        from ..models.transformer import lm_token_nll, chunked_token_nll
        from ..nn.module import Ctx
        from ..optim.optimizer import _tree_nonfinite, _tree_sq
        from .allreduce import allreduce_gradients
        from .bucketer import GradBucketer
        model, template, optim = self.model, self.template, self.optim
        cfg = model.cfg
        n_micro, mesh = self.n_micro, self.mesh
        has_dp = "dp" in mesh.axis_names
        has_sp = "sp" in mesh.axis_names and mesh.shape["sp"] > 1
        loss_chunk = self.loss_chunk
        zero1 = self.zero1
        compress = self.compress
        clip_norm = self.clip_norm
        n_chunks = self.overlap_chunks
        z1r, z1b = self._z1_rest, self._z1_blocks
        telemetry = self._begin_step_build()
        bucketer_rest = bucketer_blocks = None
        if self.bucket_bytes and not zero1:
            # two dp bucket streams — one per param family — so a flat
            # bucket never mixes pp-replicated rest leaves with
            # pp-varying stage leaves (templates from the placed params:
            # _build always runs after init() placed them)
            S = self.n_stages
            local_blocks_t = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(
                    (l.shape[0] // S,) + tuple(l.shape[1:]), l.dtype),
                self.params["blocks"])
            bucketer_rest = GradBucketer(self.params["rest"],
                                         bucket_bytes=self.bucket_bytes)
            bucketer_blocks = GradBucketer(local_blocks_t,
                                           bucket_bytes=self.bucket_bytes)

        def chunk_loss(rest, blocks_stage, tokens_c, targets_c, m_chunk):
            """(masked total NLL on the last stage, grads wrt rest and
            this stage's blocks) for one gradient chunk of microbatches.
            Differentiates the LOCAL masked total — a psum inside the
            differentiated function would make every rank seed a
            cotangent through it and scale all gradients by n_stages;
            values are psum'd after the grad call."""
            def loss_fn(rest, blocks_stage):
                ctx = Ctx(state={}, training=True, rng_key=None)
                h = model.embed.apply(rest, tokens_c, ctx)
                h = h.astype(jnp.dtype(cfg.dtype))
                mbs = h.reshape((m_chunk, -1) + h.shape[1:])

                def stage_fn(stage_params, x):
                    def body(hh, blk):
                        c = Ctx(state={}, training=True, rng_key=None)
                        return template.apply(blk, hh, c), None
                    out, _ = lax.scan(body, x, stage_params)
                    return out

                outs = pipeline_run(stage_fn, blocks_stage, mbs, "pp")
                h_out = outs.reshape(h.shape)
                ctx2 = Ctx(state={}, training=True, rng_key=None)
                h_out = model.final_norm.apply(rest, h_out, ctx2)

                def head_fn(h_c):
                    return (model.head.apply(rest, h_c, ctx2)
                            if model.head is not None
                            else h_c @ rest[model.embed.name]["weight"].T)

                # same semantics as TransformerLM.token_nll: a chunk
                # covering the whole sequence means no chunking
                if loss_chunk and loss_chunk < h_out.shape[1]:
                    tot, _ = chunked_token_nll(head_fn, h_out, targets_c,
                                               loss_chunk)
                else:
                    tot, _ = lm_token_nll(head_fn(h_out), targets_c)
                return tot * last_stage_mask("pp")

            return jax.value_and_grad(loss_fn, argnums=(0, 1))(
                rest, blocks_stage)

        def exchange(g_rest, g_blocks):
            """One gradient chunk's collectives: pp-group psum of the
            stage-disjoint rest grads, then the dp-group exchange —
            issued HERE, per chunk, so XLA's async scheduler can launch
            them under the next chunk's pipeline compute instead of
            serializing every exchange behind the last microbatch.
            Returns (rest, blocks) grads — shard-space trees on the
            zero1 path, replicated/per-stage trees otherwise."""
            # rest grads live on different ranks (embed on stage 0,
            # final norm + head on the last stage, zeros elsewhere):
            # psum over pp combines the disjoint contributions into the
            # replicated global gradient; block grads stay per-stage
            g_rest = allreduce_gradients(g_rest, "pp", mean=False,
                                         group="pp")
            if not has_dp:
                return g_rest, g_blocks
            if zero1:
                return (z1r.scatter_grads(g_rest, "dp",
                                          compress=compress),
                        z1b.scatter_grads(g_blocks, "dp",
                                          compress=compress))
            if bucketer_rest is not None:
                return (bucketer_rest.allreduce(g_rest, "dp",
                                                compress=compress),
                        bucketer_blocks.allreduce(g_blocks, "dp",
                                                  compress=compress))
            return (allreduce_gradients(g_rest, "dp", compress=compress),
                    allreduce_gradients(g_blocks, "dp",
                                        compress=compress))

        def grads_and_loss(rest, blocks_stage, tokens, targets):
            """Chunked GPipe fwd/bwd + per-chunk collective issue.
            Returns (local mean loss, exchanged rest grads, exchanged
            block grads) — grads carry the 1/valid-token mean weighting,
            applied per chunk BEFORE the exchange so a compressed wire
            ships bounded per-token-scale values."""
            rows = tokens.shape[0]
            m_chunk = n_micro // n_chunks
            if rows % n_chunks:
                # unreachable via step() (which gates rows % n_micro,
                # and n_chunks | n_micro), but a direct _step_fn caller
                # must never silently drop the tail rows
                raise ValueError(
                    f"local batch {rows} must divide by "
                    f"overlap_grad_chunks={n_chunks}")
            rows_c = rows // n_chunks
            # the mean denominator (valid-token count) is param-free:
            # computed up front so per-chunk grads can be final-scaled
            cnt = jnp.maximum(
                jnp.sum((targets != -1).astype(jnp.float32)), 1.0)
            tot_acc, gr_acc, gb_acc = 0.0, None, None
            add = lambda a, b: a + b
            for k in range(n_chunks):
                tok_c = lax.slice_in_dim(tokens, k * rows_c,
                                         (k + 1) * rows_c, axis=0)
                tgt_c = lax.slice_in_dim(targets, k * rows_c,
                                         (k + 1) * rows_c, axis=0)
                tot, (g_rest, g_blocks) = chunk_loss(
                    rest, blocks_stage, tok_c, tgt_c, m_chunk)
                scale = lambda g: g / cnt
                g_rest = jax.tree_util.tree_map(scale, g_rest)
                g_blocks = jax.tree_util.tree_map(scale, g_blocks)
                g_rest, g_blocks = exchange(g_rest, g_blocks)
                tot_acc = tot_acc + tot
                if gr_acc is None:
                    gr_acc, gb_acc = g_rest, g_blocks
                else:
                    gr_acc = jax.tree_util.tree_map(add, gr_acc, g_rest)
                    gb_acc = jax.tree_util.tree_map(add, gb_acc, g_blocks)
            loss = lax.psum(tot_acc / cnt, "pp")
            if has_dp:
                loss = lax.pmean(loss, "dp")
            return loss, gr_acc, gb_acc

        def group_sq(fn, r, b, sharded):
            """Axis-group-scoped global reduction: the rest family is
            pp-REPLICATED (its zero1 dp shards psum over dp only — a pp
            psum would count it n_stages times), the block family varies
            over pp AND dp (psum over both on the zero1 shard space;
            over pp alone on the replicated-grad path)."""
            sr, sb = fn(r), fn(b)
            if sharded:             # zero1 shard space: 1/dp slices
                sr = lax.psum(sr, "dp")
                sb = lax.psum(sb, ("dp", "pp") if has_dp else "pp")
            else:
                sb = lax.psum(sb, "pp")
            return sr + sb

        def scoped_health(g_r, g_b, old_r, old_b, new_r, new_b, sharded):
            """health_scalars with per-axis-group psum scoping (the
            composed-mesh variant of optimizer.health_scalars)."""
            gn = jnp.sqrt(group_sq(_tree_sq, g_r, g_b, sharded))
            pn = jnp.sqrt(group_sq(_tree_sq, new_r, new_b, sharded))
            d = lambda a, o: jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                a, o)
            un = jnp.sqrt(group_sq(_tree_sq, d(new_r, old_r),
                                   d(new_b, old_b), sharded))
            return {"grad_norm": gn, "param_norm": pn, "update_norm": un,
                    "update_ratio": un / jnp.maximum(pn, 1e-12),
                    "nonfinite_grads": group_sq(_tree_nonfinite, g_r,
                                                g_b, sharded)}

        def clip(g_r, g_b, sharded):
            """Global-L2 clip with the same axis-group scoping."""
            total = jnp.sqrt(group_sq(_tree_sq, g_r, g_b, sharded))
            scale = jnp.minimum(1.0,
                                clip_norm / jnp.maximum(total, 1e-12))
            s = lambda g: g * scale
            return (jax.tree_util.tree_map(s, g_r),
                    jax.tree_util.tree_map(s, g_b))

        rest_specs = jax.tree_util.tree_map(lambda _: P(),
                                            self.params["rest"])
        blk_specs = jax.tree_util.tree_map(lambda _: P("pp"),
                                           self.params["blocks"])
        # in_specs may only mention MANUAL axes; auto-axis shardings (tp
        # on the stacked block params, sp on the token sequence dim) ride
        # on the arrays themselves (device_put in init()/step()) and
        # GSPMD propagates them
        tok_spec = P("dp") if has_dp else P()
        # with a tp and/or sp axis present, shard_map is manual over
        # pp/dp ONLY and tp/sp stay AUTO axes: XLA partitions each
        # stage's matmuls over tp (megatron layout from the template
        # pspecs) and the sequence dim over sp, inserting the collectives
        # — pp x tp / pp x sp composition without hand-written psums
        manual_kw = {}
        if self._has_tp() or has_sp:
            manual_kw["axis_names"] = frozenset(
                {"pp"} | ({"dp"} if has_dp else set()))

        if zero1:
            # the whole step — fwd/bwd, dp scatter, 1/dp-sharded update,
            # dp gather — runs inside ONE shard_map: each (stage,
            # dp-rank) touches only its shard-space slice of params and
            # moments; tp/sp stay AUTO inside (the update is
            # elementwise, trivially partitionable)
            def local(rest, blocks_stage, opt_r, opt_b, tokens, targets):
                loss, gsh_r, gsh_b = grads_and_loss(rest, blocks_stage,
                                                    tokens, targets)
                if clip_norm is not None:
                    gsh_r, gsh_b = clip(gsh_r, gsh_b, sharded=True)
                idx = lax.axis_index("dp")
                psh_r = z1r.local_shard(rest, idx)
                psh_b = z1b.local_shard(blocks_stage, idx)
                new_pr, new_or = optim.update(gsh_r, psh_r, opt_r)
                new_pb, new_ob = optim.update(gsh_b, psh_b, opt_b)
                new_rest = z1r.gather_params(new_pr, "dp")
                new_blocks = z1b.gather_params(new_pb, "dp")
                out = (loss, new_rest, new_blocks, new_or, new_ob)
                if telemetry:
                    out += (scoped_health(gsh_r, gsh_b, psh_r, psh_b,
                                          new_pr, new_pb, sharded=True),)
                return out

            out_specs = (P(), rest_specs, blk_specs,
                         self._o_specs["rest"], self._o_specs["blocks"])
            if telemetry:
                out_specs += (P(),)
            mapped = jax.shard_map(
                local, mesh=mesh,
                in_specs=(rest_specs, blk_specs, self._o_specs["rest"],
                          self._o_specs["blocks"], tok_spec, tok_spec),
                out_specs=out_specs, check_vma=False, **manual_kw)

            def step(params, opt_state, tokens, targets):
                out = mapped(params["rest"], params["blocks"],
                             opt_state["rest"], opt_state["blocks"],
                             tokens, targets)
                loss, new_rest, new_blocks, new_or, new_ob = out[:5]
                res = ({"rest": new_rest, "blocks": new_blocks},
                       {"rest": new_or, "blocks": new_ob}, loss)
                if telemetry:
                    res += (out[5],)
                return res
        else:
            def local(rest, blocks_stage, tokens, targets):
                loss, g_rest, g_blocks = grads_and_loss(
                    rest, blocks_stage, tokens, targets)
                if clip_norm is not None:
                    g_rest, g_blocks = clip(g_rest, g_blocks,
                                            sharded=False)
                out = (loss, (g_rest, g_blocks))
                if telemetry:
                    out += (scoped_health(g_rest, g_blocks, rest,
                                          blocks_stage, rest,
                                          blocks_stage, sharded=False),)
                return out

            out_specs = (P(), (rest_specs, blk_specs))
            if telemetry:
                out_specs += (P(),)
            mapped = jax.shard_map(
                local, mesh=mesh,
                in_specs=(rest_specs, blk_specs, tok_spec, tok_spec),
                out_specs=out_specs, check_vma=False, **manual_kw)

            def step(params, opt_state, tokens, targets):
                out = mapped(params["rest"], params["blocks"], tokens,
                             targets)
                loss, (g_rest, g_blocks) = out[:2]
                grads = {"rest": g_rest, "blocks": g_blocks}
                new_params, new_opt = optim.update(grads, params,
                                                   opt_state)
                res = (new_params, new_opt, loss)
                if telemetry:
                    # grad-norm scalars come from inside the shard_map
                    # (scoped psums; param/update norms there use the
                    # PRE-update params — the post-update norms the
                    # sentinel wants are refined below on the global
                    # arrays, where auto-jit reductions are global)
                    health = dict(out[2])
                    pn = jnp.sqrt(sum(
                        jnp.sum(l.astype(jnp.float32) ** 2)
                        for l in jax.tree_util.tree_leaves(new_params)))
                    un = jnp.sqrt(sum(
                        jnp.sum((a.astype(jnp.float32)
                                 - b.astype(jnp.float32)) ** 2)
                        for a, b in zip(
                            jax.tree_util.tree_leaves(new_params),
                            jax.tree_util.tree_leaves(params))))
                    health["param_norm"] = pn
                    health["update_norm"] = un
                    health["update_ratio"] = un / jnp.maximum(pn, 1e-12)
                    res += (health,)
                return res

        self._step_fn = jax.jit(self._accounted(step),
                                donate_argnums=(0, 1))

    # -- telemetry ------------------------------------------------------ #
    def set_telemetry(self, recorder, health: bool = True):
        """:meth:`TelemetryHost.set_telemetry` without the cost capture.
        The trace-time ``comm/group.<axis>.*`` accounting of the dp/pp
        exchanges lands in ``recorder``'s ring, and ``health`` adds the
        axis-group-scoped grad/param/update norms."""
        return super().set_telemetry(recorder, health, capture_cost=False)

    def _ledger_devices(self):
        return int(self.mesh.devices.size)

    def _rebuild_step(self):
        if self._step_fn is not None:
            self._step_fn = None
            self._build()

    # -- API ----------------------------------------------------------- #
    def step(self, tokens, targets):
        if self._step_fn is None:
            self.init()
        from jax.sharding import NamedSharding
        n_dp = self.mesh.shape.get("dp", 1)
        batch = jnp.asarray(tokens).shape[0]
        if batch % n_dp:
            raise ValueError(f"batch {batch} must divide by dp={n_dp}")
        if (batch // n_dp) % self.n_micro:
            raise ValueError(
                f"per-dp-shard batch {batch // n_dp} must divide by "
                f"n_microbatches={self.n_micro}")
        has_dp = "dp" in self.mesh.axis_names
        has_sp = ("sp" in self.mesh.axis_names
                  and self.mesh.shape["sp"] > 1)
        if has_sp:
            seq = jnp.asarray(tokens).shape[1]
            n_sp = self.mesh.shape["sp"]
            if seq % n_sp:
                raise ValueError(
                    f"sequence length {seq} must divide by sp={n_sp}")
            # sp is an AUTO axis: the sequence sharding rides on the
            # array (in_specs inside the partial-manual shard_map may
            # only mention manual axes)
            spec = P("dp" if has_dp else None, "sp")
        else:
            spec = P("dp") if has_dp else P()
        sh = NamedSharding(self.mesh, spec)
        rec = self._rec()
        rec.start_step(self._step_count)
        with rec.span("h2d"):
            tokens = jax.device_put(jnp.asarray(tokens), sh)
            targets = jax.device_put(jnp.asarray(targets), sh)
        (self.params, self.opt_state, loss), health = self._dispatch(
            self._step_fn, (self.params, self.opt_state, tokens, targets),
            (tokens, targets))
        self._step_count += 1
        self._record_step(self._step_count - 1,
                          int(np.prod(np.shape(tokens))), loss, health)
        return loss
