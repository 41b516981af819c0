"""Mixture-of-Experts FFN with expert parallelism (TPU-era addition; the
reference has no MoE — this extends the transformer flagship the way
GShard/Switch-Transformer do, mapped to the 'ep' mesh axis).

TPU-first design: routing is ONE softmax + top-k, dispatch/combine are
dense one-hot einsums over a fixed capacity per expert (static shapes; no
sorting, no ragged tensors), and the expert FFN is a single batched
einsum over the leading expert dim.  Under GSPMD the expert dim is
sharded over the 'ep' mesh axis (and d_ff over 'tp'), so the partitioner
lowers dispatch/combine to all-to-alls over ICI and each chip runs only
its local experts.

Load-balancing auxiliary loss (Switch Transformer eq. 4) rides on
``ctx.add_loss`` so every training driver that sums side losses
(make_train_step, DistriOptimizer, SpmdTrainer) picks it up.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .module import Module


class SwitchFFN(Module):
    """Top-k routed SwiGLU experts with fixed capacity.

    Input (B, S, d_model) -> output (B, S, d_model).  ``n_experts`` is
    sharded over the 'ep' mesh axis when present (pspec below);
    ``capacity_factor`` bounds tokens per expert at
    ceil(top_k * tokens / n_experts * capacity_factor) — overflow tokens
    are dropped (their combine weight is zero), underflow slots compute
    zeros, exactly as in Switch Transformer.
    """

    def __init__(self, d_model, d_ff, n_experts, top_k=1,
                 capacity_factor=1.25, aux_loss_weight=1e-2,
                 router_noise=0.0, name=None):
        super().__init__(name=name)
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 or 2")
        self.d_model = d_model
        self.d_ff = d_ff
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        self.router_noise = router_noise
        self.pspec = {"router": P(None, None),
                      "w1": P("ep", None, "tp"),
                      "w3": P("ep", None, "tp"),
                      "w2": P("ep", "tp", None)}

    def init(self, rng):
        k0, k1, k2, k3 = jax.random.split(rng, 4)
        E, D, F = self.n_experts, self.d_model, self.d_ff
        s_in, s_out = D ** -0.5, F ** -0.5
        return {self.name: {
            "router": jax.random.normal(k0, (D, E), jnp.float32) * s_in,
            "w1": jax.random.normal(k1, (E, D, F), jnp.float32) * s_in,
            "w3": jax.random.normal(k3, (E, D, F), jnp.float32) * s_in,
            "w2": jax.random.normal(k2, (E, F, D), jnp.float32) * s_out,
        }}

    def _capacity(self, n_tokens):
        cap = int(self.top_k * n_tokens / self.n_experts
                  * self.capacity_factor + 0.999)
        return max(cap, 1)

    def apply(self, params, x, ctx):
        p = self.own(params)
        dt = x.dtype
        B, S, D = x.shape
        E = self.n_experts
        N = B * S
        C = self._capacity(N)
        xt = x.reshape(N, D)

        # ---- routing (fp32 for a stable softmax) --------------------- #
        logits = jnp.dot(xt.astype(jnp.float32), p["router"])
        if ctx.training and self.router_noise > 0.0:
            logits = logits + self.router_noise * jax.random.normal(
                ctx.rng(self), logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)            # (N, E)

        gates = jnp.zeros((N, E), jnp.float32)
        masked = probs
        for _ in range(self.top_k):
            idx = jnp.argmax(masked, axis=-1)
            onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
            gates = gates + onehot * probs
            masked = masked * (1.0 - onehot)
        sel = gates > 0.0                                   # (N, E) bool

        # ---- capacity assignment: position of each token in its expert #
        pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1  # (N, E)
        keep = sel & (pos < C)
        # dispatch/combine tensors (N, E, C): one-hot over capacity slots
        slot = jax.nn.one_hot(jnp.where(keep, pos, -1), C,
                              dtype=jnp.float32)            # (N, E, C)
        combine = slot * gates[..., None]                   # weights in slots

        # ---- expert computation (batched over E) --------------------- #
        expert_in = jnp.einsum("nec,nd->ecd", slot.astype(dt), xt)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                   p["w1"].astype(dt))) \
            * jnp.einsum("ecd,edf->ecf", expert_in, p["w3"].astype(dt))
        expert_out = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
        out = jnp.einsum("nec,ecd->nd", combine.astype(dt), expert_out)

        # ---- load-balancing aux loss (Switch eq. 4) ------------------ #
        if ctx.training and self.aux_loss_weight > 0.0:
            frac_tokens = jnp.mean(sel.astype(jnp.float32), axis=0)
            frac_probs = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(frac_tokens * frac_probs) / self.top_k
            ctx.add_loss(self.aux_loss_weight * aux.astype(jnp.float32))

        return out.reshape(B, S, D)


# --------------------------------------------------------------------- #
# fine-grained routed experts, no capacity                              #
# --------------------------------------------------------------------- #
# Test hook: the Pallas grouped matmul in interpret mode on the CPU.
_INTERPRET = False

# (tm, tk, tn) of the Pallas grouped matmul: large weight tiles (an
# expert's matrix is streamed in one or two), and row tiles no taller than
# the rows an expert has: every (expert, row tile) visit multiplies a
# whole tile, so with 32 rows an expert a 512-row tile is sixteen times
# the work (a chunk's three matmuls took 1.5 ms each against 0.5 of
# bytes; my chip run, PR 28).
_TILING = (128, 2048, 1024)


def grouped_matmul_path(backend=None):
    """``(route, why)`` of :func:`grouped_matmul`: ``"pallas"`` on a TPU
    (also under the interpret test hook), else ``"ragged_dot"``."""
    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        return "pallas", "tpu backend"
    if _INTERPRET:
        return "pallas", "interpret mode"
    return "ragged_dot", f"backend {backend!r} is not tpu"


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group: lhs
    (m, k), rhs (groups, k, n), group_sizes (groups,) int32 whose sum may
    be under m.  Rows past the sum belong to no group: their result is
    UNDEFINED (the caller masks them).  On a TPU the megablox Pallas
    kernel that ships with JAX, whose grid is the row tiles that hold a
    group's rows, so the weights it reads are those of the groups that
    have rows; elsewhere ``lax.ragged_dot``, which XLA expands to a dense
    product over every group."""
    if grouped_matmul_path()[0] == "ragged_dot":
        return lax.ragged_dot(lhs, rhs, group_sizes)
    # the package's `gmm` attribute is its differentiable wrapper, which
    # takes no output dtype; the kernel itself is the submodule's
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = _TILING
    if m <= tm:
        tm = -(-m // 16) * 16
    pad = -m % tm
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, k), lhs.dtype)])
    out = gmm.gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                  tiling=(tm, min(tk, k), min(tn, n)),
                  interpret=jax.default_backend() != "tpu")
    return out[:m] if pad else out


# what the gate's half of an expert goes through: SwiGLU's SiLU, or the
# ReLU of a ReGLU expert
_GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


# jitted under a name of its own, so that the device trace and the
# compiled program's metadata show the experts' matmuls apart from the
# step's other work
@functools.partial(jax.jit, static_argnames=("activation",))
def _moe_experts(xs, w1, w3, w2, group_sizes, activation="silu"):
    h = _GATE_ACTIVATIONS[activation](grouped_matmul(xs, w1, group_sizes)) \
        * grouped_matmul(xs, w3, group_sizes)
    return grouped_matmul(h, w2, group_sizes)


class RoutedExperts(Module):
    """Top-k routed gated experts with no capacity: no token is dropped.
    An expert is ``W2(act(W1 x) * (W3 x))``, ``activation`` "silu"
    (SwiGLU) or "relu" (ReGLU).

    The router scores every expert (``softmax(r W_r)`` in float32), takes
    the ``top_k`` largest and weighs a chosen expert by its probability
    over the sum of the chosen ones (which is the softmax over the chosen
    logits alone).  It reads the experts' own input, or ``router_input``
    where :meth:`apply` is given one (a router placed before attention
    reads the block's input).  The (token, expert) pairs are sorted
    by expert and go through :func:`grouped_matmul`: rows follow the
    pairs, so a decode step reads the experts its tokens touch and no
    others, and an expert that takes most of the tokens just has more
    rows.  ``ctx.token_mask`` (one bool a token; None: all) takes padding
    and dead slots out of the routing.  Counts into ``ctx``:
    ``moe/pairs``, ``moe/experts_touched`` (experts with at least one
    token), ``moe/expert_load_max`` (the fullest expert's tokens).

    The constructor's other arguments, each default today's behaviour:

    ``scoring`` "sigmoid": a score is ``s = sigmoid(r W_r)``; the gates
    of the chosen are ``routed_scale * s_e / sum_chosen(s)``.
    ``router_bias``: a learned ``router_bias`` (n_experts,) added to the
    scores for the SELECTION only (``s' = s + b``; the gates are from
    ``s``).  ``n_groups`` / ``top_groups``: group-limited selection: the
    experts lie in ``n_groups`` equal groups, a group scores the sum of
    its two largest ``s'``, and the ``top_k`` are taken among the experts
    of the ``top_groups`` best groups.
    ``held`` = (first, count): this layer HOLDS experts ``first ..
    first + count - 1`` of the ``n_experts`` its router scores (one
    chip's share under expert parallelism: the other chips hold the
    rest).  Its weights have ``count`` experts; pairs whose expert is
    absent are dropped before the sort, exactly as masked tokens are, and
    the layer returns its own share of the sum: what the absent experts
    would add is the other chips'.  ``moe/pairs`` counts the held pairs
    and ``moe/pairs_routed`` (counted only here) all the valid tokens'.
    ``shared_d_ff`` > 0: a shared SwiGLU expert of that width beside the
    routed ones, which every token goes through (``shared_w1`` /
    ``shared_w3`` / ``shared_w2``).

    Input (B, S, d_model) -> output (B, S, d_model).  The expert dim is
    sharded over 'ep' as :class:`SwitchFFN`'s is.
    """

    def __init__(self, d_model, d_ff, n_experts, top_k, name=None,
                 activation="silu", scoring="softmax", n_groups=0,
                 top_groups=0, routed_scale=1.0, router_bias=False,
                 held=None, shared_d_ff=0):
        super().__init__(name=name)
        if not 0 < top_k <= n_experts:
            raise ValueError(f"top_k {top_k} of {n_experts} experts")
        if activation not in _GATE_ACTIVATIONS:
            raise ValueError(f"activation {activation!r} is none of "
                             f"{sorted(_GATE_ACTIVATIONS)}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r} is neither softmax nor "
                             "sigmoid")
        if n_groups and (n_experts % n_groups or not
                         0 < top_groups <= n_groups
                         or top_k > top_groups * (n_experts // n_groups)):
            raise ValueError(f"{n_experts} experts in {n_groups} groups, "
                             f"top {top_k} of the best {top_groups}")
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= n_experts):
            raise ValueError(f"held {held} of {n_experts} experts")
        self.activation, self.scoring = activation, scoring
        self.d_model, self.d_ff = d_model, d_ff
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.n_groups, self.top_groups = int(n_groups), int(top_groups)
        self.routed_scale = float(routed_scale)
        self.router_bias = bool(router_bias)
        self.held = None if held is None else (int(held[0]), int(held[1]))
        self.shared_d_ff = int(shared_d_ff)
        self.pspec = {"router": P(None, None),
                      "w1": P("ep", None, "tp"), "w3": P("ep", None, "tp"),
                      "w2": P("ep", "tp", None),
                      "shared_w1": P(None, "tp"), "shared_w3": P(None, "tp"),
                      "shared_w2": P("tp", None)}

    @property
    def n_held(self):
        """Experts whose weights this layer has."""
        return self.n_experts if self.held is None else self.held[1]

    def init(self, rng):
        k0, k1, k2, k3 = jax.random.split(rng, 4)
        E, D, F = self.n_held, self.d_model, self.d_ff
        s_in, s_out = D ** -0.5, F ** -0.5
        p = {
            "router": jax.random.normal(k0, (D, self.n_experts),
                                        jnp.float32) * s_in,
            "w1": jax.random.normal(k1, (E, D, F), jnp.float32) * s_in,
            "w3": jax.random.normal(k3, (E, D, F), jnp.float32) * s_in,
            "w2": jax.random.normal(k2, (E, F, D), jnp.float32) * s_out,
        }
        if self.router_bias:
            p["router_bias"] = jnp.zeros((self.n_experts,), jnp.float32)
        if self.shared_d_ff:
            Fs = self.shared_d_ff
            ks = jax.random.split(jax.random.fold_in(rng, 4), 3)
            p["shared_w1"] = jax.random.normal(ks[0], (D, Fs)) * s_in
            p["shared_w3"] = jax.random.normal(ks[1], (D, Fs)) * s_in
            p["shared_w2"] = jax.random.normal(ks[2], (Fs, D)) * Fs ** -0.5
        return {self.name: p}

    def route(self, params, xt):
        """xt (N, D) -> (expert ids (N, top_k), gates (N, top_k) f32)."""
        p = self.own(params)
        logits = jnp.dot(
            xt.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if self.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        choice = scores
        if self.router_bias:
            choice = choice + p["router_bias"].astype(jnp.float32)
        if self.n_groups:
            n, g = choice.shape[0], self.n_groups
            by_group = choice.reshape(n, g, -1)
            best = lax.top_k(lax.top_k(by_group, 2)[0].sum(-1),
                             self.top_groups)[1]          # (N, top_groups)
            kept = (best[:, :, None] == jnp.arange(g)[None, None, :]).any(1)
            choice = jnp.where(kept[:, :, None], by_group,
                               -jnp.inf).reshape(n, -1)
        if choice is scores:
            picked, idx = lax.top_k(scores, self.top_k)
        else:
            # chosen by the corrected scores, weighed by the scores
            _, idx = lax.top_k(choice, self.top_k)
            picked = jnp.take_along_axis(scores, idx, axis=-1)
        gate = picked / picked.sum(-1, keepdims=True)
        return idx, gate if self.routed_scale == 1.0 \
            else self.routed_scale * gate

    def apply(self, params, x, ctx, router_input=None):
        p = self.own(params)
        dt = x.dtype
        B, S, D = x.shape
        N, K, E = B * S, self.top_k, self.n_held
        xt = x.reshape(N, D)
        idx, gate = self.route(params, xt if router_input is None
                               else router_input.reshape(N, D))
        valid = jnp.ones((N,), bool) if ctx.token_mask is None \
            else ctx.token_mask.reshape(N)
        if self.held is None:
            mine = valid[:, None]
        else:
            # the experts this layer holds, numbered from 0: pairs of the
            # others are the other chips'
            idx = idx - self.held[0]
            mine = valid[:, None] & (idx >= 0) & (idx < E)
            ctx.count("moe/pairs_routed", valid.sum() * K)
        # pairs sorted by expert; those of masked tokens (and of absent
        # experts) sort behind every group and are never touched
        key = jnp.where(mine, idx, E).reshape(N * K)
        order = jnp.argsort(key, stable=True)
        # (a compare-and-sum, not a scatter-add: thousands of updates
        # into a few dozen bins serialise on a TPU)
        load = (key[:, None] == jnp.arange(E)[None, :]).sum(
            0, dtype=jnp.int32)
        xs = jnp.take(xt, order // K, axis=0)
        ys = _moe_experts(xs, p["w1"].astype(dt), p["w3"].astype(dt),
                          p["w2"].astype(dt), load,
                          activation=self.activation)
        rows = jnp.arange(N * K) < load.sum()
        ys = jnp.where(rows[:, None],
                       ys.astype(jnp.float32)
                       * gate.reshape(N * K)[order][:, None], 0.0)
        # back into pair order, then the pairs of a token add up
        out = jnp.take(ys, jnp.argsort(order), axis=0).reshape(N, K, D).sum(1)
        ctx.count("moe/pairs", load.sum())
        ctx.count("moe/experts_touched", (load > 0).sum())
        ctx.count("moe/expert_load_max", load.max())
        if self.shared_d_ff:
            h = jax.nn.silu(jnp.dot(xt, p["shared_w1"].astype(dt))) \
                * jnp.dot(xt, p["shared_w3"].astype(dt))
            out = out + jnp.dot(h, p["shared_w2"].astype(dt)
                                ).astype(jnp.float32)
        return out.astype(dt).reshape(B, S, D)
