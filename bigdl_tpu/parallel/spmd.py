"""GSPMD trainer for the transformer flagship: dp × fsdp × tp × sp.

The DistriOptimizer (optim/distri_optimizer.py) mirrors the reference's
parameter-server loop with explicit shard_map collectives; this module is
the complementary *compiler-partitioned* path — the idiomatic TPU recipe:

  1. pick a Mesh (parallel/mesh.py), e.g. {'dp': 2, 'fsdp': 2, 'tp': 2}
  2. place parameters with NamedShardings (tp layout declared per-module
     via ``pspec``; an 'fsdp' dimension is layered onto the first free,
     divisible axis of every large parameter — ZeRO-3 by sharding alone)
  3. jit the whole train step and let the XLA partitioner insert the
     collectives (all-gather for fsdp params, psum after row-parallel
     matmuls, reduce-scatter in the backward)
  4. the one manual island: attention, via shard_map, wired into
     MultiHeadAttention — the ring over 'sp' (parallel/ring_attention.py)
     or, without one, the flash kernel over the batch and head axes (a
     Mosaic custom call is opaque to the partitioner, which would
     otherwise gather Q/K/V whole onto every chip).

Optimizer state sharding is *propagated*, not spelled out: every moment
tensor takes its parameter's sharding from the step that updates it.
(Measured on the chip, PR 21: ``jit(init_state)``'s own outputs do not
depend on the params' data and come back uncommitted on one device, so
the layout only holds from the first step's outputs on — and that change
of sharding type makes the second ``step()`` compile again.  Not fixed;
NOTES.md "Bring-up on the chip".)
"""
from __future__ import annotations

import time
from functools import partial
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib
from .ring_attention import ring_attention_shmap
from ..models.transformer import TransformerLM
from ..ops.flash_attention import flash_attention
from ..observability import collectives as _acct
from ..observability import DivergenceError
from ..observability.host import TelemetryHost
from ..optim.optimizer import make_accum_grads


def _filter_spec(spec: P, mesh: Mesh) -> P:
    """Drop axis names the mesh does not have."""
    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in mesh.axis_names)
            return kept if kept else None
        return e if e in mesh.axis_names else None
    return P(*(keep(e) for e in spec))


def _add_axis(spec: P, shape, mesh: Mesh, axis: str,
              min_size: int = 2 ** 16) -> P:
    """Layer ``axis`` onto the first free, divisible dim of a large
    param — the one sharding-layering rule ('fsdp' onto params, 'dp'
    onto optimizer moments for the zero1 annotation)."""
    if axis not in mesh.axis_names or int(np.prod(shape)) < min_size:
        return spec
    n = mesh.shape[axis]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, s) in enumerate(zip(entries, shape)):
        if e is None and s % n == 0:
            entries[i] = axis
            break
    return P(*entries)


def _add_fsdp(spec: P, shape, mesh: Mesh, min_size: int = 2 ** 16) -> P:
    """Layer 'fsdp' onto the first free, divisible dim of a large param."""
    return _add_axis(spec, shape, mesh, "fsdp", min_size)


def flash_attention_shmap(q, k, v, mesh: Mesh, causal: bool = True):
    """``flash_attention`` on (B, H, S, D) global arrays as a manual
    island: batch over the mesh's ``dp``/``fsdp`` axes, heads over
    ``tp``, sequence whole.  Batch rows and heads are embarrassingly
    parallel, so each device runs the kernel on its own block and no
    collective is needed — which GSPMD cannot work out by itself for a
    Pallas call (it has no partitioning rule, so operands would be
    replicated).  shard_map needs even blocks: a batch or head count the
    axes do not divide (a grad-accum microbatch smaller than dp) takes
    the plain call and the partitioner's own plan."""
    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    heads = "tp" if "tp" in mesh.axis_names else None
    n_batch = int(np.prod([mesh.shape[a] for a in batch]))
    if q.shape[0] % n_batch or q.shape[1] % mesh.shape.get("tp", 1):
        return flash_attention(q, k, v, causal=causal)
    spec = P(batch or None, heads, None, None)
    return jax.shard_map(partial(flash_attention, causal=causal),
                         mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


_MESH_ATTENTION = (ring_attention_shmap, flash_attention_shmap)


class SpmdTrainer(TelemetryHost):
    """Compiles one fused (fwd + bwd + update) XLA program over the mesh.
    ``set_telemetry`` / ``set_health`` / ``serve_metrics`` come from
    :class:`TelemetryHost` (attach before ``init()`` to compile once)."""

    _items_counter = "tokens_total"
    _capture_at_avals = False
    _resize_adopted_ledger = True

    def __init__(self, model: TransformerLM, optim, mesh: Optional[Mesh] = None,
                 fsdp: bool = True, seed: int = 0,
                 ring_attention: Optional[bool] = None,
                 min_fsdp_size: int = 2 ** 16, grad_accum: int = 1,
                 loss_chunk: Optional[int] = None, zero1: bool = False,
                 zero1_min_size: Optional[int] = None):
        self.model = model
        self.optim = optim
        self.mesh = mesh or mesh_lib.get_mesh()
        self.seed = seed
        self.min_fsdp_size = min_fsdp_size
        # ZeRO-1 by ANNOTATION (arXiv:2004.13336 — "automatic
        # cross-replica sharding of weight update"): optimizer moments
        # get 'dp' layered onto their first free, divisible dim via
        # sharding metadata, and a with_sharding_constraint pins the
        # updated state to the same layout — the GSPMD partitioner then
        # shards the elementwise update math 1/dp and inserts the
        # collectives itself.  Composes with tp (megatron pspecs) and
        # fsdp (moments already carry the param's fsdp dim; dp lands on
        # a different free dim).  Memory claim is enforced by the
        # sharding metadata, inspectable on opt_state leaves.
        if zero1 and self.mesh.shape.get("dp", 1) < 2:
            raise ValueError("zero1 shards the update over the dp axis: "
                             "the mesh needs dp > 1")
        self.zero1 = bool(zero1)
        self.zero1_min_size = (min_fsdp_size if zero1_min_size is None
                               else int(zero1_min_size))
        cfg = model.cfg
        if ring_attention is None:
            ring_attention = cfg.use_ring_attention
        self.ring = bool(ring_attention and "sp" in self.mesh.axis_names
                         and self.mesh.shape.get("sp", 1) > 1)
        self.fsdp = fsdp and "fsdp" in self.mesh.axis_names
        self._batch_axes = tuple(a for a in ("dp", "fsdp")
                                 if a in self.mesh.axis_names)
        self._seq_axis = "sp" if "sp" in self.mesh.axis_names else None
        self.grad_accum = int(grad_accum)
        # chunked head+loss: caps logits memory at (B, chunk, V) — see
        # TransformerLM.token_nll.  None = single full-sequence projection.
        self.loss_chunk = loss_chunk
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self._step_count = 0
        TelemetryHost.__init__(self)
        self._ckpt_layout = "orbax"
        self._ckpt_mgr = None
        self._shard_arrays = False      # elastic sliced saves (v2)
        self._preemption = None
        # device-side input transform compiled into the step (the
        # uint8-wire / device-augment hook for this path)
        self._input_transform = None
        # attached streaming dataset whose cursor rides in checkpoints
        self._data_pipeline = None

    # ------------------------------------------------------------------ #
    def _param_shardings(self, params):
        specs = self.model.param_pspecs(params)
        by_name = {m.name: m for m in self.model.modules()}
        out = {}
        for mod, sub in params.items():
            # modules may opt out of fsdp layering (fsdp_exempt=True):
            # the token embedding must, because layering 'fsdp' onto its
            # free dim makes the gather+residual pattern miscompile on
            # the GSPMD partitioner AND costs two involuntary-full-remat
            # reshards of its cotangent — see TokenEmbedding's note and
            # tests/test_partitioner_repro.py
            exempt = getattr(by_name.get(mod), "fsdp_exempt", False)
            out[mod] = {}
            for k, p in sub.items():
                spec = _filter_spec(specs[mod][k], self.mesh)
                if self.fsdp and not exempt:
                    spec = _add_fsdp(spec, p.shape, self.mesh,
                                     self.min_fsdp_size)
                out[mod][k] = NamedSharding(self.mesh, spec)
        return out

    def _zero1_opt_shardings(self, params, shardings, opt_state):
        """Per-leaf NamedShardings for the zero1-annotated optimizer
        state, as ``{leaf path: NamedSharding}`` for exactly the leaves
        the annotation touches: a moment leaf whose tree-path suffix
        names an existing param (and matches its shape) takes that
        param's spec with 'dp' layered onto the first free divisible
        dim.  Scalars and unmatched leaves are absent — they keep the
        (uncommitted) placement init gave them, so jit dispatch stays
        free to move them.  Path correspondence, not shape matching —
        the ``fsdp_opt_state_specs`` rule."""
        p_paths, _ = jax.tree_util.tree_flatten_with_path(params)
        sh_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda v: hasattr(v, "spec"))
        by_path = {tuple(path): (tuple(leaf.shape), sh.spec)
                   for (path, leaf), sh in zip(p_paths, sh_leaves)}

        out = {}

        def for_leaf(path, leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            for i in range(len(path)):
                hit = by_path.get(tuple(path[i:]))
                if hit is not None and hit[0] == shape:
                    spec = _add_axis(hit[1], shape, self.mesh, "dp",
                                     self.zero1_min_size)
                    out[tuple(path)] = NamedSharding(self.mesh, spec)
                    return leaf

        jax.tree_util.tree_map_with_path(for_leaf, opt_state)
        return out

    def _batch_sharding(self):
        ba = self._batch_axes
        lead = ba if len(ba) > 1 else (ba[0] if ba else None)
        return NamedSharding(self.mesh, P(lead, self._seq_axis))

    # ------------------------------------------------------------------ #
    def attach(self):
        """Wire this mesh's attention island into the model's attention
        modules (rebinding any hook a previous trainer left), remembering
        the old hooks so :meth:`detach` can restore standalone/other-mesh
        use of the model.  With an sp ring that is the ring; otherwise,
        on more than one device, the flash kernel sharded over batch and
        heads.  One device needs no island — only another trainer's
        taken back out."""
        fn = None
        if self.ring:
            fn = partial(ring_attention_shmap, mesh=self.mesh, causal=True)
        elif self.mesh.devices.size > 1:
            fn = partial(flash_attention_shmap, mesh=self.mesh)
        for blk in self.model.blocks:
            cur = blk.attn.attention_fn
            foreign = isinstance(cur, partial) \
                and cur.func in _MESH_ATTENTION
            # stash the model's TRUE original on the module itself; never
            # stash another trainer's mesh hook (interleaved trainers would
            # otherwise "restore" a foreign mesh's island on detach)
            if not foreign:
                blk.attn._pre_ring_attention_fn = cur
            if fn is not None:
                blk.attn.attention_fn = fn
            elif foreign:
                blk.attn.attention_fn = blk.attn._pre_ring_attention_fn
        self._attached = fn is not None
        return self

    def detach(self):
        """Restore the model's original attention hooks (pre any mesh
        island)."""
        if getattr(self, "_attached", False):
            for blk in self.model.blocks:
                if hasattr(blk.attn, "_pre_ring_attention_fn"):
                    blk.attn.attention_fn = blk.attn._pre_ring_attention_fn
            self._attached = False
        return self

    def init(self):
        self.attach()
        params = self.model.init(jax.random.PRNGKey(self.seed))
        shardings = self._param_shardings(params)
        self.params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        # jitted with sharded params -> moments inherit the param shardings
        self.opt_state = jax.jit(self.optim.init_state)(self.params)
        zero1_sh = None
        if self.zero1:
            zero1_sh = self._zero1_opt_shardings(params, shardings,
                                                 self.opt_state)
            self.opt_state = jax.tree_util.tree_map_with_path(
                lambda path, leaf: jax.device_put(
                    leaf, zero1_sh[tuple(path)])
                if tuple(path) in zero1_sh else leaf, self.opt_state)
        model, optim = self.model, self.optim

        n_accum = self.grad_accum

        loss_chunk = self.loss_chunk

        def loss_fn(p, tokens, targets, rng):
            from ..nn.module import Ctx
            ctx = Ctx(state={}, training=True, rng_key=rng)
            loss = model.loss(p, tokens, targets, loss_chunk=loss_chunk,
                              ctx=ctx)
            for sl in ctx.side_losses:   # e.g. MoE load-balancing aux
                loss = loss + sl
            return loss

        # model.loss is a MASKED token mean, so microbatches are
        # weighted by their valid-token count (equal weighting would
        # misweight padded batches — see make_accum_grads)
        grads_fn = make_accum_grads(
            lambda p, s, t, y, r: (loss_fn(p, t, y, r), s), n_accum,
            weight_fn=lambda t, y: (y != -1).sum())

        from ..optim.optimizer import health_scalars, mask_frozen_grads

        telemetry = self._begin_step_build()
        transform = self._input_transform

        def step(params, opt_state, tokens, targets, rng):
            if transform is not None:
                # traced-rng split only (GL005: no host state in the
                # trace); the transform fuses into the step program
                rng, t_rng = jax.random.split(rng)
                tokens = transform(tokens, t_rng)
            (loss, _), grads = grads_fn(params, {}, tokens, targets, rng)
            grads = mask_frozen_grads(model, grads)
            new_params, new_opt = optim.update(grads, params, opt_state)
            if zero1_sh is not None:
                # pin the updated state to the 1/dp layout: without the
                # constraint the partitioner may re-replicate moments to
                # match the (replicated-over-dp) grads, silently undoing
                # the memory win the annotation promises
                new_opt = jax.tree_util.tree_map_with_path(
                    lambda path, x: jax.lax.with_sharding_constraint(
                        x, zero1_sh[tuple(path)])
                    if tuple(path) in zero1_sh else x, new_opt)
            if telemetry:
                # global arrays under full-auto jit: the norm reductions
                # are already global, no explicit collective needed
                return (new_params, new_opt, loss,
                        health_scalars(grads, params, new_params))
            return new_params, new_opt, loss

        self._step_fn = jax.jit(step, donate_argnums=(0, 1))
        return self

    # -- telemetry ------------------------------------------------------- #
    def _ledger_devices(self):
        return int(self.mesh.devices.size)

    def _rebuild_step(self):
        """Re-jit for the new step signature WITHOUT losing training
        progress: init() re-randomizes params, so stash and restore."""
        if self._step_fn is None:
            return
        params, opt_state = self.params, self.opt_state
        self._step_fn = None
        self.init()
        if params is not None:
            self.params, self.opt_state = params, opt_state

    def set_input_transform(self, fn):
        """Compile ``fn(tokens, rng) -> tokens`` into the jitted step —
        the device-side augmentation hook for this path (the host ships
        the raw wire format, e.g. uint8, and the transform runs inside
        the step's XLA program).  The rng is split off the step's
        traced key: recompile-safe, deterministic across resume.  Like
        ``set_telemetry(health=...)``, changing it after ``init()``
        re-jits without losing training progress."""
        self._input_transform = fn
        self._rebuild_step()
        return self

    def set_data_pipeline(self, dataset):
        """Attach a cursor-capable streaming dataset
        (``data.sharded.ShardedRecordDataSet``): every manifest
        checkpoint then records ``dataset.state()`` — the exact read
        position of the last consumed batch — and restore re-positions
        the stream, so a preempted run never re-sees or skips a sample.
        Feed ``fit(...)`` from ``dataset.stream()``."""
        self._data_pipeline = dataset
        return self

    def straggler_report(self):
        """Per-host step-time attribution — the "which worker drags the
        synchronous step" answer.  Each process's recorder ring only
        holds its OWN records, so under multi-host this does one
        on-demand ``process_allgather`` of the local mean step time
        (never on the step path) and attributes over the gathered
        fleet; single-host (or merged-ring) setups attribute over the
        local records and return None when there's nothing per-host."""
        from ..observability.health import attribute_stragglers
        recs = self._rec().recent_records(rec_type="step")
        if jax.process_count() > 1:
            durs = [r["dur"] for r in recs
                    if isinstance(r.get("dur"), (int, float))]
            if not durs:
                return None
            from jax.experimental import multihost_utils
            gathered = np.asarray(multihost_utils.process_allgather(
                jnp.asarray([float(np.mean(durs))]))).reshape(-1)
            return attribute_stragglers(
                [{"type": "step", "step": 0, "dur": float(m),
                  "scalars": {"host": h}}
                 for h, m in enumerate(gathered)])
        return attribute_stragglers(self._rec().recent_records())

    def account_collectives(self, tokens, targets):
        """Compile the current step for these shapes and parse the
        partitioned HLO for the collectives GSPMD actually inserted
        (the compiler owns the op choice on this path, so static
        estimates would lie).  Sets ``collective/*`` gauges on the
        recorder and returns ``{op: wire_bytes}`` + a total.  One extra
        trace+compile (cache-served if shapes match a prior step)."""
        if self._step_fn is None:
            self.init()
        sh = self._batch_sharding()
        tokens = jax.device_put(jnp.asarray(tokens), sh)
        targets = jax.device_put(jnp.asarray(targets), sh)
        rng = jax.random.PRNGKey(self.seed + 1)
        lowered = self._step_fn.lower(self.params, self.opt_state,
                                      tokens, targets, rng)
        hlo = lowered.compile().as_text()
        n = int(np.prod(list(self.mesh.shape.values())))
        ops = _acct.hlo_collective_ops(hlo, n)
        rec = self._rec()
        by_op = {}
        for op, _, wire in ops:
            by_op[op] = by_op.get(op, 0.0) + wire
        total = sum(by_op.values())
        rec.reset_gauges("collective/")
        rec.reset_gauges("comm/group.")
        for op, wire in by_op.items():
            rec.gauge(f"collective/{op.replace('-', '_')}_wire_bytes",
                      wire)
        rec.gauge("collective/wire_bytes_per_step", total)
        rec.gauge("collective/bytes_per_step", total)
        # per-axis-group attribution: map the replica groups the
        # partitioner emitted back onto mesh axes — on this path the
        # compiler owns the op choice, so the HLO is the only honest
        # source of "which axis paid these bytes" (the MoE ep
        # all-to-all, the fsdp gathers, the dp grad reduction each land
        # in their own comm/group.<axis>.* family)
        groups = _acct.hlo_group_breakdown(hlo, self.mesh)
        for label, d in groups.items():
            for op, wire in d.items():
                if op == "wire_bytes":
                    continue
                rec.gauge(f"comm/group.{label}."
                          f"{op.replace('-', '_')}_wire_bytes", wire)
            rec.gauge(f"comm/group.{label}.wire_bytes_per_step",
                      d["wire_bytes"])
        return {"ops": by_op, "groups": groups,
                "wire_bytes_per_step": total}

    def step(self, tokens, targets):
        if self._step_fn is None:
            self.init()
        # jit traces lazily on first call: re-assert this trainer's ring
        # hooks so interleaved trainers on one model can't bake a foreign
        # mesh into our compiled step (compiled programs are unaffected)
        self.attach()
        rec = self._rec()
        step_span = None
        if self._trace_ctx is not None:
            step_span = self._trace_spine().begin(
                "train.step", self._trace_ctx, subsystem="train")
        rec.start_step(self._step_count)
        sh = self._batch_sharding()
        with rec.span("h2d"):
            tokens = jax.device_put(jnp.asarray(tokens), sh)
            targets = jax.device_put(jnp.asarray(targets), sh)
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed + 1),
                                 self._step_count)
        (self.params, self.opt_state, loss), health = self._dispatch(
            self._step_fn,
            (self.params, self.opt_state, tokens, targets, rng),
            (tokens, targets))
        self._step_count += 1
        # per-host step records: what the stall watchdog's straggler
        # attribution groups by
        self._record_step(
            self._step_count - 1, int(np.prod(np.shape(tokens))), loss,
            health, {"host": jax.process_index()}
            if jax.process_count() > 1 else None)
        if step_span is not None:
            step_span.end(step=self._step_count - 1)
        return loss

    def evaluate(self, batches, steps: Optional[int] = None):
        """Token-weighted mean cross-entropy and perplexity over
        ``batches`` of (tokens, targets), computed with the same mesh
        placement as training (dropout off).  ≙ Evaluator/Loss validation
        for the flagship path."""
        import itertools
        if self.params is None:
            self.init()
        self.attach()
        model = self.model
        if getattr(self, "_eval_fn", None) is None:
            loss_chunk = self.loss_chunk

            def eval_fn(params, tokens, targets):
                # same chunked head+loss as training: evaluate must not
                # re-introduce the (B, S, V) logits memory wall
                return model.token_nll(params, tokens, targets,
                                       loss_chunk=loss_chunk,
                                       training=False)
            self._eval_fn = jax.jit(eval_fn)
        sh = self._batch_sharding()
        if steps is not None:   # islice: never pull an extra batch from a
            batches = itertools.islice(batches, steps)  # shared iterator
        sums, counts = [], []
        for tokens, targets in batches:
            tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), sh)
            targets = jax.device_put(jnp.asarray(targets, jnp.int32), sh)
            s, c = self._eval_fn(self.params, tokens, targets)
            sums.append(s)      # device values: no per-batch host sync
            counts.append(c)
        total = float(sum(sums)) if sums else 0.0
        count = float(sum(counts)) if counts else 0.0
        if count == 0:
            raise ValueError(
                "evaluate: no valid tokens (empty batches, or every "
                "target is ignore_index)")
        loss = total / count
        res = {"loss": loss, "perplexity": float(np.exp(min(loss, 50.0))),
               "tokens": int(count)}
        vs = getattr(self, "_val_summary", None)
        if vs is not None:
            vs.add_scalar("Loss", res["loss"], self._step_count)
            vs.add_scalar("Perplexity", res["perplexity"],
                          self._step_count)
        return res

    # -- checkpointing --------------------------------------------------- #
    def _manifest_manager(self, path, keep=None, async_write=True):
        """CheckpointManager for this trainer with per-host shard
        ownership: shards are assigned round-robin over hosts by sorted
        shard name, each process snapshots and writes only the shards it
        owns, and host 0 merges the per-host part manifests into the
        single atomic MANIFEST.json commit (shared filesystem)."""
        from ..checkpoint import CheckpointManager
        mgr = self._ckpt_mgr
        if mgr is None or mgr.root != path:
            mgr = CheckpointManager(
                path, layout="manifest", async_write=async_write,
                keep_last=keep, recorder_fn=self._rec,
                process_index=jax.process_index(),
                process_count=jax.process_count())
            self._ckpt_mgr = mgr
        return mgr

    def _save_manifest_checkpoint(self, path: str, sync: bool = False,
                                  keep=None, async_write=True, tag=None):
        """Async sharded checkpoint via bigdl_tpu.checkpoint: params per
        top-level module + opt_state as CRC32C'd shards committed by an
        atomic manifest.  Only the blocking device→host copy of the
        OWNED shards runs on the step loop.

        The manifest records this trainer's mesh (v2), so restore can
        reshard onto a different one.  With ``shard_arrays`` each host
        writes per-device replica-0 slices (with index maps) instead of
        whole global trees — the representation that stays writable
        when no host can address a global array."""
        from ..checkpoint import reshard
        from ..checkpoint.manager import host_snapshot
        if self.params is None:
            raise ValueError("trainer not initialized; call init() first")
        mgr = self._manifest_manager(path, keep=keep,
                                     async_write=async_write)
        logical = {f"params/{mod}": sub
                   for mod, sub in self.params.items()}
        logical["opt_state"] = self.opt_state
        names = sorted(logical)
        shards, owned = {}, set()
        with self._rec().span("checkpoint.blocking"):
            for i, name in enumerate(names):
                tree = logical[name]
                if self._shard_arrays and reshard.all_array_leaves(tree):
                    # one slice shard per host per entry: every host
                    # enumerates every host's shard names (aligned file
                    # indices) but materializes only its own fragments
                    for k in range(mgr.process_count):
                        pname = f"{name}@p{k:03d}"
                        if k == mgr.process_index:
                            frag = reshard.split_fragments(
                                tree, process_index=k)
                            frag["of"] = name
                            shards[pname] = frag
                            owned.add(pname)
                        else:
                            shards[pname] = None
                elif i % mgr.process_count == mgr.process_index:
                    # whole-tree global shard, round-robin ownership
                    shards[name] = host_snapshot(tree)
                    owned.add(name)
                else:
                    # unowned placeholder: keeps shard indices aligned
                    # across hosts, never serialized
                    shards[name] = None
        meta = {"step": self._step_count, "seed": self.seed,
                "root": self.model.name}
        if self._data_pipeline is not None:
            # the data cursor is mesh-independent (the pipeline feeds
            # the GLOBAL batch), so it survives an elastic reshard
            # unchanged — dp4→dp2 resumes the identical sample stream
            meta["data_cursor"] = self._data_pipeline.state()
        mgr.save(shards, meta, tag=tag or f"step_{self._step_count}",
                 sync=sync, mesh=reshard.mesh_info(self.mesh),
                 owned=owned,
                 trace_ctx=self._trace_ctx.child()
                 if self._trace_ctx is not None else None)

    def save_checkpoint(self, path: str, layout: Optional[str] = None,
                        sync: bool = False, tag: Optional[str] = None):
        """Write params + optimizer state + step counter.

        ``layout="manifest"`` (or ``set_checkpoint(...,
        layout="manifest")``) uses the bigdl_tpu.checkpoint subsystem:
        async sharded writes, atomic manifest commit, CRC-verified
        resume.  The default ``"orbax"`` layout keeps the
        ecosystem-readable orbax directory: sharded jax Arrays are
        handed to orbax directly (``to_host=False``) so fsdp state is
        written shard-wise without materialising an unsharded host
        copy.  ≙ Optimizer.setCheckpoint for the compiler-partitioned
        flagship path."""
        import json
        import os
        from ..utils.serializer import save_pytree
        if layout is None:
            layout = self._ckpt_layout
        if layout == "manifest":
            return self._save_manifest_checkpoint(path, sync=sync, tag=tag)
        if self.params is None:
            raise ValueError("trainer not initialized; call init() first")
        # step-tagged snapshot + atomic 'latest' pointer (same crash-safe
        # pattern as Optimizer.save_checkpoint): a job killed mid-save
        # never destroys the previous snapshot.  An explicit tag (e.g.
        # the preemption path's preempt_step_<n>) names the dir, and
        # _prune_checkpoints' step_<n> pattern never collects it
        tag_dir = os.path.join(path, tag or f"step_{self._step_count}")
        save_pytree({"params": self.params, "opt_state": self.opt_state},
                    os.path.join(tag_dir, "state"), to_host=False)
        meta = {"step": self._step_count, "seed": self.seed,
                "root": self.model.name}
        if self._data_pipeline is not None:
            meta["data_cursor"] = self._data_pipeline.state()
        with open(os.path.join(tag_dir, "meta.json"), "w") as f:
            json.dump(meta, f)
        tmp = os.path.join(path, "latest.tmp")
        with open(tmp, "w") as f:
            f.write(os.path.basename(tag_dir))   # relocatable pointer
        os.replace(tmp, os.path.join(path, "latest"))

    def _rekey_root(self, tree, old_root, new_root):
        """Auto-named modules draw from a process-global uid counter, so a
        fresh trainer's param keys differ from the saved ones ONLY in the
        model-root prefix; rewrite it key-by-key (never by flatten
        order, which could silently permute same-shape leaves)."""
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if k == old_root:
                    k = new_root
                elif k.startswith(old_root + "."):
                    k = new_root + k[len(old_root):]
                out[k] = self._rekey_root(v, old_root, new_root)
            return out
        return tree

    def load_checkpoint(self, path: str):
        """Restore a save_checkpoint directory into this trainer: arrays
        come back on device with this trainer's shardings, and the step
        counter AND seed resume, so the data-order/dropout RNG stream
        continues exactly as in the uninterrupted run.  Manifest-layout
        checkpoints (CRC-verified, torn-checkpoint fallback) are tried
        first; the orbax layout remains readable."""
        import json
        import os
        from ..utils.serializer import load_pytree
        if self.params is None:
            self.init()
        restored = self._manifest_manager(path).restore_latest(
            with_manifest=True)
        if restored is not None and restored[0] == "manifest":
            _, trees, meta, mf = restored
            raw = {"params": {k[len("params/"):]: v
                              for k, v in trees.items()
                              if k.startswith("params/")},
                   "opt_state": trees["opt_state"]}
            return self._finish_restore(raw, meta, path,
                                        saved_mesh=mf.mesh if mf else None)
        latest = os.path.join(path, "latest")
        if os.path.exists(latest):
            with open(latest) as f:
                name = f.read().strip()
            if os.path.isabs(name) or os.sep in name:
                root = name     # legacy pointer holding a full path
            else:
                root = os.path.join(path, name)
        elif os.path.exists(os.path.join(path, "meta.json")):
            root = path     # direct snapshot directory
        else:
            raise FileNotFoundError(
                f"{path}: no 'latest' pointer or snapshot found")
        with open(os.path.join(root, "meta.json")) as f:
            meta = json.load(f)
        raw = load_pytree(os.path.join(root, "state"))
        return self._finish_restore(raw, meta, path)

    def _finish_restore(self, raw, meta, path, saved_mesh=None):
        """Validate a raw {params, opt_state} tree against this trainer
        and place it: shared tail of the manifest and orbax loaders.

        ``saved_mesh`` (the v2 manifest's save-time mesh) arms the
        reshard path: global arrays are mesh-invariant, so a topology
        change is purely a re-layout — ``device_put`` against THIS
        trainer's shardings — counted under ``elastic/*`` and recorded
        as an ``elastic_event``.  Shape mismatches raise errors that
        name both meshes and, when a mesh delta explains the mismatch,
        say so."""
        from ..checkpoint import reshard
        raw = self._rekey_root(raw, meta.get("root", self.model.name),
                               self.model.name)
        target_mesh = reshard.mesh_info(self.mesh)
        resharding = (saved_mesh is not None
                      and not reshard.same_mesh(saved_mesh, target_mesh))
        delta = reshard.describe_delta(saved_mesh, target_mesh)
        template = {"params": self.params, "opt_state": self.opt_state}
        if (jax.tree_util.tree_structure(raw)
                != jax.tree_util.tree_structure(template)):
            hint = f" (checkpoint {delta} — a mesh change never alters " \
                   "the tree structure; this is a different model)" \
                   if resharding else ""
            raise ValueError(
                f"{path}: checkpoint tree does not match this trainer's "
                f"model (after root-name normalisation){hint}")

        def dt(a):
            # dtype without materializing the leaf: np.asarray on a live
            # sharded template forces a device-to-host copy (and raises on
            # non-fully-addressable multi-host arrays)
            d = getattr(a, "dtype", None)
            return np.dtype(d) if d is not None else np.asarray(a).dtype

        def check(v, t, where):
            if tuple(np.shape(v)) != tuple(np.shape(t)) or dt(v) != dt(t):
                msg = (f"{path}: leaf {jax.tree_util.keystr(where)} is "
                       f"{np.shape(v)}/{dt(v)}, model expects "
                       f"{np.shape(t)}/{dt(t)}")
                why = reshard.explain_shape_delta(
                    np.shape(v), np.shape(t), saved_mesh, target_mesh)
                if why is not None:
                    msg += (f". Explainable by the mesh delta — {why}. "
                            f"Checkpoint {delta}. Re-save it with "
                            "shard_arrays=True (elastic v2 slice shards "
                            "carry global index maps) and restore will "
                            "reassemble and reshard onto this mesh; see "
                            "docs/checkpointing.md § Elastic resume.")
                elif saved_mesh is not None:
                    msg += (f". Checkpoint {delta}; global shapes are "
                            "mesh-invariant, so this mismatch is NOT "
                            "explained by the mesh change — the saved "
                            "model differs from this trainer's.")
                raise ValueError(msg)
            return v

        raw = jax.tree_util.tree_map_with_path(
            lambda w, v, t: check(v, t, w), raw, template)
        rec = self._rec()
        shardings = self._param_shardings(self.params)
        with rec.span("elastic.reshard" if resharding
                      else "checkpoint.restore"):
            # place-then-own: device_put shards the host leaf during the
            # transfer (no full-size unsharded device intermediate — the
            # property the orbax save path promises), and the sharded
            # jnp.array(copy=True) guarantees jax-owned buffers —
            # device_put of an aligned numpy array can be zero-copy on
            # CPU, and params are donated every step
            self.params = jax.tree_util.tree_map(
                lambda v, s: jnp.array(jax.device_put(np.asarray(v), s),
                                       copy=True),
                raw["params"], shardings)
            # opt-state leaves stay UNCOMMITTED: at init they come out of
            # jit the same way, and the next step call's jit dispatch
            # places them against the params' shardings without the
            # committed-device conflicts an explicit device_put would
            # cause — which is also what re-partitions Adam moments onto
            # a changed mesh without spelling their layout out twice.
            # copy=True, not asarray: a zero-copy alias of the loader's
            # numpy buffer must never reach the donating step (see
            # Optimizer.load_checkpoint)
            self.opt_state = jax.tree_util.tree_map(
                lambda v: jnp.array(np.asarray(v), copy=True),
                raw["opt_state"])
        if resharding:
            n_leaves = len(jax.tree_util.tree_leaves(raw))
            rec.inc("elastic/reshards")
            rec.inc("elastic/resharded_leaves", n_leaves)
            rec.emit_record("elastic_event", kind="reshard",
                            step=meta.get("step"), saved_mesh=saved_mesh,
                            target_mesh=target_mesh, leaves=n_leaves)
            print(f"[elastic] resharded {n_leaves} leaves: {delta}",
                  flush=True)
        self._step_count = meta["step"]
        self.seed = meta.get("seed", self.seed)
        cursor = meta.get("data_cursor")
        if cursor is not None and self._data_pipeline is not None:
            self._data_pipeline.restore(cursor)
        return self

    def set_checkpoint(self, path: str, every_steps: int = 1000,
                       keep: int = 3, layout: str = "orbax",
                       async_write: bool = True,
                       shard_arrays: bool = False,
                       handle_preemption: bool = False):
        """Checkpoint every ``every_steps`` steps during fit(), retaining
        the newest ``keep`` snapshots (0 = keep all)
        (≙ Optimizer.setCheckpoint with a several_iteration trigger).
        ``layout="manifest"`` routes through bigdl_tpu.checkpoint:
        background sharded writes with per-host shard ownership and an
        atomic CRC-verified manifest commit; retention then runs in the
        manager's GC.

        ``shard_arrays`` (manifest layout) switches to elastic v2 slice
        shards: each host writes per-device replica-0 array fragments
        with global index maps, so restore can reassemble on ANY mesh —
        the save mode that works even when no host addresses a global
        array.  ``handle_preemption`` installs a SIGTERM handler (same
        contract as ``Optimizer.set_checkpoint``): fit() finishes the
        in-flight write, commits a final ``preempt_step_<n>`` checkpoint
        synchronously, and returns cleanly."""
        if every_steps < 1:
            raise ValueError("every_steps must be >= 1")
        if keep < 0:
            raise ValueError("keep must be >= 0")
        if layout not in ("orbax", "manifest"):
            raise ValueError(f"unknown checkpoint layout {layout!r}")
        if shard_arrays and layout != "manifest":
            raise ValueError("shard_arrays requires layout='manifest'")
        self._ckpt = (path, int(every_steps), int(keep))
        self._ckpt_layout = layout
        self._shard_arrays = bool(shard_arrays)
        if layout == "manifest":
            self._ckpt_mgr = None       # rebuild with this retention
            self._manifest_manager(path, keep=int(keep) or None,
                                   async_write=async_write)
        if handle_preemption:
            from ..checkpoint import PreemptionHandler
            if self._preemption is None:
                self._preemption = PreemptionHandler()
            self._preemption.install()
        return self

    def _prune_checkpoints(self, path: str, keep: int):
        import os
        import re
        import shutil
        if keep < 1:
            return
        latest = os.path.join(path, "latest")
        pointed = None
        if os.path.exists(latest):
            with open(latest) as f:
                pointed = os.path.basename(f.read().strip())
        snaps = []
        for d in os.listdir(path):
            m = re.fullmatch(r"step_(\d+)", d)
            full = os.path.join(path, d)
            if m and os.path.isdir(full):
                # rank by mtime, not step number: a run resumed from an
                # older snapshot must not have its fresh checkpoints
                # crowded out by stale higher-step dirs of a dead run
                snaps.append((os.path.getmtime(full), int(m.group(1)),
                              d, full))
        snaps.sort()   # mtime first; step number breaks coarse-mtime ties
        for _, _, name, full in snaps[:-keep]:
            if name != pointed:  # never delete the snapshot 'latest' names
                shutil.rmtree(full, ignore_errors=True)

    def set_weight_stream(self, publisher):
        """Attach a live train→serve weight stream
        (:class:`~bigdl_tpu.serving.WeightStreamPublisher`): evaluated
        once per ``fit`` step against the global step count; on fire
        the sharded params are snapshotted to owning host copies and
        published through the canary gate off the step loop.  ``None``
        detaches."""
        self._weight_stream = publisher
        return self

    def set_val_summary(self, summary):
        """ValidationSummary target for :meth:`evaluate` results (≙
        Optimizer.set_val_summary): each evaluate() writes Loss and
        Perplexity at the current training step."""
        self._val_summary = summary
        return self

    def set_train_summary(self, summary):
        """TensorBoard Loss/Throughput scalars (≙
        Optimizer.set_train_summary, incl. set_summary_trigger gating).
        Losses are buffered as device values and flushed every
        ``summary_flush_every`` steps (default 100) and on exit — even
        on an exception — so summaries add no per-step device->host
        sync but a crashed run keeps its curve."""
        self._train_summary = summary
        return self

    def _flush_summary(self, buffered, tokens_seen, t0):
        """Write buffered (step, device_loss) pairs; returns []"""
        summary = self._train_summary
        trig = getattr(summary, "get_summary_trigger",
                       lambda _t: None)("Loss")
        for s, l in buffered:
            if trig is None or trig(SimpleNamespace(iteration=s)):
                summary.add_scalar("Loss", float(l), s)
        if buffered:
            wall = max(time.time() - t0, 1e-9)
            summary.add_scalar("Throughput", tokens_seen / wall,
                               buffered[-1][0])
        return []

    def fit(self, batches, steps: Optional[int] = None, log_every: int = 0,
            summary_flush_every: int = 100):
        losses = []
        buffered = []
        tokens_seen = 0
        ckpt = getattr(self, "_ckpt", None)
        summary = getattr(self, "_train_summary", None)
        t0 = time.time()
        if self._watchdog is not None:
            self._watchdog.start()      # re-arms after a previous fit()
        try:
            for i, (tokens, targets) in enumerate(batches):
                if steps is not None and i >= steps:
                    break
                try:
                    loss = self.step(tokens, targets)
                except DivergenceError as e:
                    mon = self._health_monitor
                    if (mon is None or mon.policy != "rollback"
                            or ckpt is None
                            or mon.rollbacks >= self._max_rollbacks):
                        raise
                    if self._ckpt_mgr is not None:
                        self._ckpt_mgr.wait()   # let an in-flight write
                        # commit: it may be the newest intact checkpoint
                    try:
                        self.load_checkpoint(ckpt[0])
                    except Exception:
                        raise e     # no restorable checkpoint: diverge
                    mon.rollbacks += 1
                    mon.reset_statistics()
                    mon.mark_recovered()
                    print(f"[health] rollback {mon.rollbacks}/"
                          f"{self._max_rollbacks}: {e}; resumed from "
                          f"step {self._step_count}", flush=True)
                    continue
                if log_every and (i + 1) % log_every == 0:
                    print(f"step {i + 1}: loss={float(loss):.4f} "
                          f"({(i + 1) / (time.time() - t0):.2f} it/s)")
                if (self._preemption is not None
                        and self._preemption.requested and ckpt):
                    # SIGTERM: finish any in-flight async write, commit
                    # a final checkpoint synchronously, stop cleanly —
                    # the elastic supervisor (or the next job) resumes
                    # it, on this mesh or a smaller one
                    losses.append(loss)
                    self.save_checkpoint(
                        ckpt[0], sync=True,
                        tag=f"preempt_step_{self._step_count}")
                    print(f"[preemption] final checkpoint at step "
                          f"{self._step_count} committed; stopping "
                          "cleanly", flush=True)
                    break
                if ckpt and self._step_count % ckpt[1] == 0:
                    self.save_checkpoint(ckpt[0])
                    if self._ckpt_layout == "orbax":
                        # manifest layout: retention runs in the
                        # manager's own GC on the writer thread
                        self._prune_checkpoints(ckpt[0], ckpt[2])
                stream = getattr(self, "_weight_stream", None)
                if stream is not None:
                    # owning host snapshot taken synchronously (the
                    # next step donates params); publish rides the
                    # stream worker.  loss stays on device — the shim
                    # state only carries the step count
                    stream.maybe_publish(self.params,
                                         step=self._step_count)
                losses.append(loss)
                if summary is not None:
                    tokens_seen += int(np.prod(np.shape(tokens)))
                    buffered.append((self._step_count, loss))
                    if len(buffered) >= summary_flush_every:
                        buffered = self._flush_summary(buffered,
                                                       tokens_seen, t0)
        finally:
            if summary is not None and buffered:
                self._flush_summary(buffered, tokens_seen, t0)
            if self._ckpt_mgr is not None:
                # drain the async writer: every triggered checkpoint is
                # committed and durable when fit() returns
                self._ckpt_mgr.wait()
            if self._watchdog is not None:
                # a finished loop is not a stalled one: /healthz scrapes
                # after fit() must not flag the growing idle step age
                self._watchdog.stop()
        return [float(l) for l in losses]
