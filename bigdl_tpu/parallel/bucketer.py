"""Bucketed, overlappable gradient exchange (≙ the reference's
``AllReduceParameter`` + ``FP16CompressedTensor`` pipeline,
arXiv:1804.05839).

One monolithic all-reduce of the whole gradient tree serializes the
exchange behind the *last* gradient of backward.  The reference BigDL
instead sliced gradients into per-partition blocks and overlapped their
exchange with compute; the XLA-native version of that trick is to emit
**one collective per fixed-size flat bucket** so the async collective
scheduler (`-start`/`-done` pairs on TPU) can launch each bucket's
all-reduce as soon as its inputs are ready — overlapping the exchange
with the tail of backward instead of waiting for all of it.

:class:`GradBucketer` packs gradient leaves into flat buckets of
``bucket_bytes`` in **backward-emission order** (reverse of the forward
flatten order — the deepest modules' gradients materialize first, so
their bucket's collective can start first), keeping each bucket
single-dtype so packing round-trips bit-exactly.  ``compress="fp16"``
halves the wire payload per bucket: pre-scale by 1/n in fp32, cast to
fp16 for the ring (the mean is what travels — a raw fp16 *sum* of n
shards can overflow half precision's 65504 range), upcast to the leaf
dtype after.  Uncompressed bucketed exchange is bit-identical to the
monolithic ``allreduce_gradients`` path (elementwise psum over the same
replicas; asserted in tests).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..observability import collectives as _acct

_CAST = {"fp16": jnp.float16, "float16": jnp.float16,
         "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}


class GradBucketer:
    """Host-side bucket plan for one gradient-tree template.

    ``order`` controls packing order: ``"backward"`` (default — reverse
    flatten order, first-ready-first), ``"forward"``, or ``"size"``
    (largest leaves first, evening out bucket fill).  A leaf larger
    than ``bucket_bytes`` gets a bucket of its own.
    """

    def __init__(self, params_template, bucket_bytes: int = 4 << 20,
                 order: str = "backward"):
        if order not in ("backward", "forward", "size"):
            raise ValueError(f"unknown bucket order {order!r}")
        leaves, self.treedef = jax.tree_util.tree_flatten(params_template)
        self.n_leaves = len(leaves)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.dtypes = [jnp.dtype(l.dtype) for l in leaves]
        self.bucket_bytes = int(bucket_bytes)
        idx = list(range(self.n_leaves))
        if order == "backward":
            idx = idx[::-1]
        elif order == "size":
            idx.sort(key=lambda i: -self.sizes[i])
        self.buckets: List[List[int]] = []      # lists of leaf indices
        cur, cur_bytes, cur_dt = [], 0, None
        for i in idx:
            nbytes = self.sizes[i] * self.dtypes[i].itemsize
            if cur and (self.dtypes[i] != cur_dt
                        or cur_bytes + nbytes > self.bucket_bytes):
                self.buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
            cur_dt = self.dtypes[i]
        if cur:
            self.buckets.append(cur)

    def __len__(self):
        return len(self.buckets)

    # -- pack / unpack ---------------------------------------------------- #
    def pack(self, grads):
        """Gradient tree -> list of flat single-dtype bucket vectors."""
        leaves = jax.tree_util.tree_leaves(grads)
        out = []
        for bucket in self.buckets:
            if len(bucket) == 1:
                out.append(jnp.ravel(leaves[bucket[0]]))
            else:
                out.append(jnp.concatenate(
                    [jnp.ravel(leaves[i]) for i in bucket]))
        return out

    def unpack(self, vecs):
        """Inverse of :meth:`pack`."""
        leaves = [None] * self.n_leaves
        for bucket, vec in zip(self.buckets, vecs):
            off = 0
            for i in bucket:
                leaves[i] = vec[off:off + self.sizes[i]].reshape(
                    self.shapes[i]).astype(self.dtypes[i])
                off += self.sizes[i]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- the exchange ------------------------------------------------------ #
    def allreduce(self, grads, axis_name: str = "dp",
                  compress: Optional[str] = None, mean: bool = True,
                  group: Optional[str] = None):
        """Per-bucket all-reduce of ``grads`` inside ``shard_map``.

        Trace-time accounting mirrors ``allreduce_gradients``:
        ``collective/allreduce_bytes`` raw vs ``_wire_bytes`` post-
        compression, plus a ``collective/buckets`` gauge with the
        per-step collective count.  ``group`` (default: the axis name)
        attributes the volume to its parallelism group's
        ``comm/group.<axis>.*`` family — on a composed mesh each axis
        runs its own bucket stream, accounted separately."""
        n = lax.axis_size(axis_name)
        if group is None and isinstance(axis_name, str):
            group = axis_name
        cast_to = _CAST.get(compress)
        vecs = self.pack(grads)
        raw = sum(_acct.leaf_bytes(v) for v in vecs)
        wire_item = _acct.compressed_itemsize(compress)
        wire = raw if wire_item is None else sum(
            v.shape[0] * wire_item for v in vecs)
        _acct.account_collective("allreduce",
                                 _acct.ring_allreduce_bytes(raw, n),
                                 _acct.ring_allreduce_bytes(wire, n),
                                 group=group)
        from ..observability.recorder import get_recorder
        rec = get_recorder()
        if rec.enabled:
            # accumulated, like bytes_per_step: a composed/overlap-
            # chunked step issues several bucket streams per trace, and
            # last-write would under-report all but the final stream.
            # The collective/ and comm/group. prefixes reset together
            # on every rebuild AND re-trace, so single-stream paths
            # read exactly as before
            rec.gauge("collective/buckets",
                      rec.gauge_value("collective/buckets")
                      + float(len(vecs)))
            if group is not None:
                rec.gauge(f"comm/group.{group}.buckets",
                          rec.gauge_value(f"comm/group.{group}.buckets")
                          + float(len(vecs)))

        out = []
        for v in vecs:
            orig = v.dtype
            if cast_to is not None:
                if mean:        # the 1/n mean travels: fp16-sum-safe
                    v = (v.astype(jnp.float32) / n).astype(cast_to)
                else:
                    v = v.astype(cast_to)
                v = lax.psum(v, axis_name).astype(orig)
            else:
                v = lax.pmean(v, axis_name) if mean \
                    else lax.psum(v, axis_name)
            out.append(v)
        return self.unpack(out)
