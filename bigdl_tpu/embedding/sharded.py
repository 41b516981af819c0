"""Row-sharded embedding tables, model-parallel over one mesh axis.

The reference frames recommendation as the planet-scale workload
(SparseTensor + LookupTableSparse, PAPER.md §1–2): embedding tables too
big for one device, batches gather/scatter-bound rather than FLOP-bound.
Here the table's ROWS are partitioned over a mesh axis and a lookup is
resolved with the classic model-parallel exchange:

  1. each device owns a contiguous row range and holds its slice of the
     (padded, batch-sharded) id matrix;
  2. ids are bucketed by owner shard and shipped with ONE
     ``lax.all_to_all`` (the request leg);
  3. each owner gathers its requested rows locally;
  4. a second ``all_to_all`` returns the embeddings (the reply leg);
  5. replies are scattered back to their original flat positions and
     combined per bag with the same weighted ``segment_sum`` the
     single-device :func:`bigdl_tpu.tensor.embedding_bag` uses.

Bitwise discipline: the exchange is a pure permutation of gathers — the
per-position embedding matrix it reconstitutes is value-identical to
the single-device dense gather, and the combine runs the identical op
sequence on it, so forward AND backward are bitwise-equal to
:func:`dense_bag` on one device (the parity tests assert exactly that;
see docs/embedding.md).  Wire volume of both legs is attributed at
trace time through the PR-13 per-axis-group accounting
(``comm/group.<axis>.*``) plus the ``embedding/*`` family.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..nn.module import Module
from ..nn.init import Xavier, init_tensor
from ..observability.collectives import account_collective


def row_shard_spec(n_index: int, n_shards: int):
    """(rows_per_shard, padded_rows): rows are dealt in contiguous
    blocks, padded so every shard holds the same static count."""
    rows = -(-int(n_index) // int(n_shards))
    return rows, rows * int(n_shards)


def pad_table(weight, n_shards: int):
    """Zero-pad a (V, D) table to (rows_per_shard * n_shards, D) so a
    ``P(axis)`` sharding splits it into equal row blocks."""
    v = weight.shape[0]
    _, padded = row_shard_spec(v, n_shards)
    if padded == v:
        return weight
    return jnp.concatenate(
        [weight, jnp.zeros((padded - v,) + weight.shape[1:],
                           weight.dtype)], axis=0)


# --------------------------------------------------------------------- #
# shared building blocks — used by BOTH the sharded path and the dense  #
# reference so the two can never diverge in op sequence                 #
# --------------------------------------------------------------------- #
def _positions_emb(table, gid):
    """Per-position embeddings for 0-based global ids; invalid ids
    (``gid < 0``, the padding sentinel) contribute exactly +0.0."""
    valid = gid >= 0
    emb = jnp.take(table, jnp.clip(gid, 0, table.shape[0] - 1), axis=0)
    return jnp.where(valid[..., None], emb, 0.0)


def _combine(emb_flat, wts_flat, rows, n_bags, combiner):
    """Weighted per-bag combine of flat per-position embeddings — the
    static-shape twin of :func:`bigdl_tpu.tensor.embedding_bag`'s
    combine (same segment_sum order, same denominators)."""
    summed = jax.ops.segment_sum(emb_flat * wts_flat[:, None], rows,
                                 num_segments=n_bags)
    if combiner == "sum":
        return summed
    if combiner == "mean":
        denom = jax.ops.segment_sum(wts_flat, rows, num_segments=n_bags)
        return summed / jnp.maximum(denom, 1e-7)[:, None]
    denom2 = jax.ops.segment_sum(wts_flat * wts_flat, rows,
                                 num_segments=n_bags)
    return summed / jnp.sqrt(jnp.maximum(denom2, 1e-7))[:, None]


def _flatten_bags(ids, per_id_weights):
    """(B, L) 1-based padded ids -> (flat 0-based gid with -1 padding,
    flat weights with 0.0 at padding, flat bag/segment ids)."""
    b, l = ids.shape
    gid = ids.astype(jnp.int32).reshape(-1) - 1          # 0 (pad) -> -1
    valid = gid >= 0
    if per_id_weights is None:
        wts = valid.astype(jnp.float32)
    else:
        wts = jnp.where(valid, per_id_weights.reshape(-1)
                        .astype(jnp.float32), 0.0)
    rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), l)
    return gid, wts, rows


def dense_bag(weight, ids, per_id_weights=None, combiner="sum"):
    """Single-device dense-gather reference: padded (B, L) 1-based ids
    (0 = padding) over a replicated (V, D) table.  Semantics match
    :func:`bigdl_tpu.tensor.embedding_bag` on the equivalent
    SparseTensor; shapes are static, so it jits without recompiles
    across batches of one bucket size."""
    if combiner not in ("sum", "mean", "sqrtn"):
        raise ValueError(f"combiner must be sum|mean|sqrtn: {combiner}")
    gid, wts, rows = _flatten_bags(ids, per_id_weights)
    emb = _positions_emb(weight, gid)
    return _combine(emb, wts, rows, ids.shape[0], combiner)


# --------------------------------------------------------------------- #
# the all-to-all exchange (runs per device, inside shard_map)           #
# --------------------------------------------------------------------- #
def _exchange_gather(table_local, gid, axis, rows_per_shard, n_shards,
                     capacity):
    """Fetch ``table[gid]`` when rows live on their owner shard.

    ``gid``: (S,) 0-based global row ids, -1 = padding.  Returns (S, D)
    per-position embeddings in the ORIGINAL order — padding rows are
    exactly +0.0 — so downstream math is identical to the dense path.

    ``capacity`` bounds the per-destination bucket (static shape of the
    exchange); ids past a full bucket are dropped silently IN-GRAPH, so
    callers must guarantee capacity >= the worst per-owner count — the
    default ``capacity = S`` always holds, the dedup stage's host-side
    planner picks tighter ladders it can prove.
    """
    s = gid.shape[0]
    cap = int(capacity) if capacity else s
    k = lax.axis_index(axis)
    valid = gid >= 0
    # padding stays local (owner = self) and ships a -1 sentinel
    owner = jnp.where(valid, gid // rows_per_shard, k).astype(jnp.int32)
    order = jnp.argsort(owner, stable=True)
    sowner = owner[order]
    sgid = gid[order]
    starts = jnp.searchsorted(sowner, jnp.arange(n_shards, dtype=jnp.int32))
    slot = jnp.arange(s, dtype=jnp.int32) - starts[sowner]
    send = jnp.full((n_shards, cap), -1, jnp.int32)
    send = send.at[sowner, slot].set(sgid, mode="drop")
    # request leg: bucket j of `send` lands on device j; received row j
    # is device j's bucket for me
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    lrow = recv - k * rows_per_shard
    rvalid = (lrow >= 0) & (lrow < rows_per_shard) & (recv >= 0)
    flat = jnp.clip(lrow, 0, rows_per_shard - 1).reshape(-1)
    emb = jnp.take(table_local, flat, axis=0).reshape(
        n_shards, cap, table_local.shape[1])
    emb = jnp.where(rvalid[..., None], emb, 0.0)
    # reply leg: ship the gathered rows back to the requesters
    back = lax.all_to_all(emb, axis, split_axis=0, concat_axis=0)
    flat_sorted = back[sowner, slot]
    # unsort: scatter each reply to its original flat position
    return jnp.zeros_like(flat_sorted).at[order].set(flat_sorted)


def _account_exchange(n_shards, cap, dim, itemsize, axis, recorder=None):
    """Trace-time wire attribution of one lookup exchange (both legs),
    through the PR-13 per-axis-group accounting plus ``embedding/*``."""
    if recorder is None:
        from ..observability.recorder import get_recorder
        recorder = get_recorder()
    if not recorder.enabled:
        return
    id_bytes = n_shards * cap * 4
    emb_bytes = n_shards * cap * dim * itemsize
    account_collective("all-to-all", id_bytes, float(id_bytes),
                       recorder=recorder, group=axis)
    account_collective("all-to-all", emb_bytes, float(emb_bytes),
                       recorder=recorder, group=axis)
    pre = "embedding/"
    for suffix, val in (("lookup_exchange_bytes",
                         float(id_bytes + emb_bytes)),
                        ("exchange_ids", float(n_shards * cap))):
        recorder.gauge(pre + suffix,
                       recorder.gauge_value(pre + suffix) + val)


class ShardedEmbeddingBag(Module):
    """Embedding bag whose table rows are sharded over mesh ``axis``.

    Input is the padded-dense bag layout the host dedup stage emits —
    ``ids`` (B, L) int32, 1-based, 0 = padding — or a tuple
    ``(ids, per_id_weights)``; with ``dedup`` stats from
    :mod:`bigdl_tpu.embedding.dedup`, input is
    ``(uniq_ids (n_shards, U), inverse (B, L))`` and only the unique
    ids cross the wire.  Output is (B, n_output), batch-sharded over
    the same axis (B must divide by the axis size).

    The layer initializes exactly like a dense (V, D) Xavier table and
    zero-pads to the shard grid, so a replicated single-device
    :func:`dense_bag` over ``params[...]["weight"][:n_index]`` is the
    bitwise reference for both forward and backward.
    """

    def __init__(self, n_index, n_output, mesh=None, axis="tp",
                 combiner="sum", capacity=None, name=None):
        super().__init__(name=name)
        if combiner not in ("sum", "mean", "sqrtn"):
            raise ValueError(f"combiner must be sum|mean|sqrtn: {combiner}")
        self.n_index = int(n_index)
        self.n_output = int(n_output)
        self.axis = axis
        self.combiner = combiner
        self.capacity = capacity
        self._mesh = mesh

    # mesh is resolved lazily so a module built before create_mesh works
    @property
    def mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import get_mesh
            self._mesh = get_mesh()
        return self._mesh

    @property
    def n_shards(self):
        return int(self.mesh.shape[self.axis])

    def init(self, rng):
        w = init_tensor(self, rng, (self.n_index, self.n_output),
                        self.n_index, self.n_output, Xavier())
        return {self.name: {"weight": pad_table(w, self.n_shards)}}

    def table_sharding(self):
        """NamedSharding placing the padded table rows on their owners —
        what a planet-scale table actually is: 1/n per device."""
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P(self.axis))

    def apply(self, params, x, ctx):
        w = self.own(params)["weight"]
        mesh = self.mesh
        n = self.n_shards
        rows, padded = row_shard_spec(self.n_index, n)
        if w.shape[0] != padded:
            raise ValueError(
                f"{self.name}: table has {w.shape[0]} rows, shard grid "
                f"needs {padded} (= {rows} x {n}); init with pad_table")
        if isinstance(x, (tuple, list)) and len(x) == 2 \
                and getattr(x[0], "ndim", 0) == 2 \
                and getattr(x[1], "ndim", 0) == 2 \
                and jnp.issubdtype(jnp.asarray(x[1]).dtype, jnp.integer):
            return self._apply_dedup(w, x[0], x[1], mesh, n, rows)
        if isinstance(x, (tuple, list)):
            ids, per_id_weights = x[0], x[1]
        else:
            ids, per_id_weights = x, None
        return self._apply_plain(w, ids, per_id_weights, mesh, n, rows)

    def _apply_plain(self, w, ids, per_id_weights, mesh, n, rows):
        b, l = ids.shape
        if b % n:
            raise ValueError(f"batch {b} must divide by axis "
                             f"{self.axis}={n}")
        lb = b // n
        s = lb * l
        cap = int(self.capacity) if self.capacity else s
        _account_exchange(n, cap, self.n_output,
                          np.dtype(np.float32).itemsize, self.axis)
        combiner = self.combiner

        def local(table_local, ids_local, wts_local=None):
            gid, wts, segs = _flatten_bags(ids_local, wts_local)
            emb = _exchange_gather(table_local, gid, self.axis, rows, n,
                                   cap)
            return _combine(emb, wts, segs, lb, combiner)

        if per_id_weights is None:
            fn = jax.shard_map(local, mesh=mesh,
                               in_specs=(P(self.axis), P(self.axis)),
                               out_specs=P(self.axis), check_vma=False)
            return fn(w, ids)
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(self.axis), P(self.axis), P(self.axis)),
            out_specs=P(self.axis), check_vma=False)
        return fn(w, ids, per_id_weights)

    def _apply_dedup(self, w, uniq_ids, inverse, mesh, n, rows):
        """Dedup mode: exchange only the per-device unique ids, then
        gather per-position embeddings locally through ``inverse``.
        Forward is bitwise-identical to the plain path (a gather of
        gathers).  Backward first folds each device's duplicate-row
        grads into per-unique partial sums (segment_sum over
        ``inverse``) before the scatter — the cross-device accumulation
        is reassociated relative to dense's flat per-occurrence
        scatter-add, so dedup backward matches the dense reference
        within the float32 reassociation envelope, not bitwise (the
        plain path IS bitwise; tests assert both contracts).
        ``inverse`` slots for padding positions must point at a -1
        (sentinel) uniq slot — :func:`dedup.dedup_for_mesh` guarantees
        one."""
        b, l = inverse.shape
        if b % n:
            raise ValueError(f"batch {b} must divide by axis "
                             f"{self.axis}={n}")
        if uniq_ids.shape[0] != n:
            raise ValueError(
                f"uniq_ids leading dim {uniq_ids.shape[0]} != axis size "
                f"{n} (one unique-id row per device)")
        lb = b // n
        u = uniq_ids.shape[1]
        cap = int(self.capacity) if self.capacity else u
        _account_exchange(n, cap, self.n_output,
                          np.dtype(np.float32).itemsize, self.axis)
        combiner = self.combiner

        def local(table_local, uniq_local, inv_local):
            uid = uniq_local.reshape(-1).astype(jnp.int32)   # already 0-based
            uniq_emb = _exchange_gather(table_local, uid, self.axis,
                                        rows, n, cap)
            inv = inv_local.reshape(-1)
            emb = jnp.take(uniq_emb, jnp.clip(inv, 0, u - 1), axis=0)
            valid = (inv >= 0) & (uid[jnp.clip(inv, 0, u - 1)] >= 0)
            emb = jnp.where(valid[:, None], emb, 0.0)
            wts = valid.astype(jnp.float32)
            segs = jnp.repeat(jnp.arange(lb, dtype=jnp.int32), l)
            return _combine(emb, wts, segs, lb, combiner)

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(self.axis), P(self.axis), P(self.axis)),
            out_specs=P(self.axis), check_vma=False)
        return fn(w, uniq_ids, inverse)


def reference_table(params, bag: ShardedEmbeddingBag):
    """The unpadded (V, D) view of a ShardedEmbeddingBag's table — what
    the single-device :func:`dense_bag` reference consumes."""
    return params[bag.name]["weight"][:bag.n_index]
