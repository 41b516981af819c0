"""Gradient synchronization strategies
(≙ parameters/AllReduceParameter.scala, FP16CompressedTensor.scala,
ParameterOperations.scala).

The reference implements a partitioned parameter server on the Spark block
manager: each task slices its gradient into #partitions blocks, puts them,
each partition aggregates its slice, applies the update, and workers fetch
the new weight slices (AllReduceParameter.scala:222 aggregateGradientPartition,
:273 putGradients).  FP16CompressedTensor halves the bytes on the wire.

On TPU these become XLA collectives over the mesh:

  all-reduce            -> lax.psum(grads, 'dp')            (replicated params)
  partitioned PS        -> reduce_scatter + all_gather      (FSDP, sharded
                           params/opt state — same comm volume as the
                           reference's partitioned scheme, but on ICI)
  fp16 compression      -> cast to bf16/fp16 before psum, upcast after
                           (bf16 preferred on TPU: same 16 bits, fp32 range)
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import collectives as _acct

log = logging.getLogger(__name__)


def _path_str(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _report_dense_fallback(counter: str, names, op: str):
    """Sharding coverage must be observable, not silent: leaves that fall
    back to a dense per-leaf collective (dim 0 not divisible / masked
    out) bump a ``comm/*`` counter once per trace and name themselves in
    a debug log.  Runs at trace time — once per compiled program, so the
    counter reads 'how many leaves the last-built step left unsharded'
    (re-traces re-report, like the collective gauges)."""
    if not names:
        return
    from ..observability.recorder import get_recorder
    rec = get_recorder()
    if rec.enabled:
        rec.inc(counter, len(names))
    log.debug("%s dense fallback for %d leaves (dim 0 not divisible by "
              "the axis, or masked unsharded): %s", op, len(names),
              ", ".join(names))


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda g: g.astype(dtype)
        if jnp.issubdtype(g.dtype, jnp.floating) else g, tree)


def _axis_size_or_none(axis_name):
    """Static axis size when called under shard_map/pmap tracing; None
    outside a binding context (pure-function unit tests)."""
    try:
        return lax.axis_size(axis_name)
    except Exception:
        return None


def allreduce_gradients(grads, axis_name: str = "dp",
                        compress: Optional[str] = None, mean: bool = True,
                        group: Optional[str] = None):
    """Sum (or mean) gradients across the axis, optionally compressed to
    16-bit on the wire (≙ FP16CompressedTensor).  Call inside shard_map.

    Compressed means ship the 1/n-scaled gradient (pre-scaled in fp32,
    then cast): a raw 16-bit ring SUM of n shards can overflow fp16's
    65504 range, and the same mean-on-the-wire rule keeps this path
    numerically identical to the bucketed exchange
    (:class:`~bigdl_tpu.parallel.bucketer.GradBucketer`).

    Accounts the ring all-reduce volume (raw and on-the-wire bytes) to
    the active telemetry recorder at trace time — shapes are static
    here, so the numbers are exact per executed step.  ``group`` names
    the parallelism group for the ``comm/group.<axis>.*`` family
    (defaults to the axis name on a composed mesh — pass explicitly
    when ``axis_name`` is a tuple)."""
    orig_dtypes = jax.tree_util.tree_map(lambda g: g.dtype, grads)
    n = _axis_size_or_none(axis_name)
    if group is None and isinstance(axis_name, str):
        group = axis_name
    if n is not None:
        raw = _acct.tree_bytes(grads)
        wire_item = _acct.compressed_itemsize(compress)
        wire = _acct.tree_bytes(grads, wire_itemsize=wire_item)
        _acct.account_collective(
            "allreduce", _acct.ring_allreduce_bytes(raw, n),
            _acct.ring_allreduce_bytes(wire, n), group=group)
    cast_to = {"fp16": jnp.float16, "float16": jnp.float16,
               "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}.get(compress)
    if cast_to is not None:
        if mean and n is not None:
            grads = jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) / n).astype(cast_to)
                if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
            reduced = lax.psum(grads, axis_name)
        else:       # mean=False keeps sum semantics; n unknown outside
            grads = _cast(grads, cast_to)      # a binding context
            reduced = lax.pmean(grads, axis_name) if mean \
                else lax.psum(grads, axis_name)
    else:
        reduced = lax.pmean(grads, axis_name) if mean \
            else lax.psum(grads, axis_name)
    return jax.tree_util.tree_map(
        lambda g, d: g.astype(d), reduced, orig_dtypes)


def reduce_scatter_gradients(grads, axis_name: str = "dp", mean: bool = True,
                             mask=None, group: Optional[str] = None):
    """Each shard keeps 1/N of every sharded gradient leaf (scatter dim 0)
    — the FSDP half of the partitioned parameter server.  ``mask`` (a
    params-shaped tree of bools, e.g. from :func:`shardable_mask_dim0`)
    marks which leaves are dim-0-sharded; without it, any leaf whose
    dim 0 divides the axis size is scattered.  Unsharded leaves are
    all-reduced instead.  Call inside shard_map with FULL-shape grads.

    Trace-time accounting: scattered leaves ride a reduce-scatter
    (S*(n-1)/n wire bytes), unscattered ones a full all-reduce."""
    n = lax.axis_size(axis_name)
    if group is None and isinstance(axis_name, str):
        group = axis_name
    rs_bytes, ar_bytes = [0], [0]
    dense_leaves = []

    def rs(path, g, s=None):
        sharded = (g.ndim > 0 and g.shape[0] % n == 0) if s is None else s
        if not sharded:
            ar_bytes[0] += _acct.leaf_bytes(g)
            dense_leaves.append(_path_str(path))
            return lax.pmean(g, axis_name) if mean else lax.psum(g, axis_name)
        rs_bytes[0] += _acct.leaf_bytes(g)
        out = lax.psum_scatter(g, axis_name, scatter_dimension=0,
                               tiled=True)
        return out / n if mean else out

    if mask is None:
        out = jax.tree_util.tree_map_with_path(rs, grads)
    else:
        out = jax.tree_util.tree_map_with_path(rs, grads, mask)
    _report_dense_fallback("comm/unsharded_leaves", dense_leaves,
                           "reduce_scatter_gradients")
    if rs_bytes[0]:
        _acct.account_collective(
            "reduce_scatter", _acct.ring_gather_bytes(rs_bytes[0], n),
            _acct.ring_gather_bytes(rs_bytes[0], n), group=group)
    if ar_bytes[0]:
        _acct.account_collective(
            "allreduce", _acct.ring_allreduce_bytes(ar_bytes[0], n),
            _acct.ring_allreduce_bytes(ar_bytes[0], n), group=group)
    return out


def allgather_params(params, axis_name: str = "dp", mask=None,
                     group: Optional[str] = None):
    """Rebuild full parameters from dim-0 shards (the getWeights fetch).
    ``mask`` marks which leaves are actually sharded (replicated leaves
    must NOT be gathered — that would tile N copies); without a mask any
    non-scalar leaf is gathered."""
    n = _axis_size_or_none(axis_name)
    if group is None and isinstance(axis_name, str):
        group = axis_name
    ag_bytes = [0]
    skipped_leaves = []

    def ag(path, p, s=None):
        if p.ndim == 0 or (s is not None and not s):
            skipped_leaves.append(_path_str(path))
            return p
        ag_bytes[0] += _acct.leaf_bytes(p) * (n or 1)  # full gathered size
        return lax.all_gather(p, axis_name, axis=0, tiled=True)

    if mask is None:
        out = jax.tree_util.tree_map_with_path(ag, params)
    else:
        out = jax.tree_util.tree_map_with_path(ag, params, mask)
    _report_dense_fallback("comm/ungathered_leaves", skipped_leaves,
                           "allgather_params")
    if ag_bytes[0] and n:
        _acct.account_collective(
            "allgather", _acct.ring_gather_bytes(ag_bytes[0], n),
            _acct.ring_gather_bytes(ag_bytes[0], n), group=group)
    return out


def shardable_mask_dim0(tree, n):
    """Bool mask over ``tree``: True where a leaf's dim 0 is divisible by
    ``n`` (those leaves get dim-0-sharded for FSDP; the rest stay
    replicated).  Computed host-side from GLOBAL shapes."""
    def mark(p):
        return p.ndim > 0 and p.shape[0] % n == 0
    return jax.tree_util.tree_map(mark, tree)
