"""TransformerLM — the long-context flagship model.

The reference's sequence models stop at unrolled RNNs
(models/rnn/SimpleRNN.scala, nn/Recurrent.scala); this decoder-only
transformer is the TPU-era flagship that exercises every parallel axis:

  dp    batch sharded over data parallel
  fsdp  parameters/optimizer state sharded (see parallel/spmd.py)
  tp    megatron-style sharded attention heads + MLP hidden dim
  sp    sequence sharded, exact attention via the ppermute ring
        (parallel/ring_attention.py)

TPU-first design decisions:
  * The model is written as a *global-array* program: matmuls carry
    ``PartitionSpec`` hints (each parallel-aware module exposes ``pspec``)
    and the GSPMD partitioner inserts the tp collectives; only the ring
    attention is a manual ``shard_map`` island (parallel/spmd.py wires it).
  * RoPE positions, causal masks etc. use global indices, so the same code
    is correct sharded or not.
  * bf16 activations / fp32 params by default; per-block ``jax.checkpoint``
    (rematerialisation) trades MXU FLOPs for HBM when ``remat=True``.
  * head_dim defaults to 128 = one MXU tile, so flash attention's Pallas
    kernel runs full-width.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..nn.module import Module, Ctx
from ..nn.normalization import RMSNorm
from ..ops.flash_attention import (flash_attention, DEFAULT_MASK_VALUE,
                                   _mask as _attn_mask)
from ..ops.sparse_attention import attend as sparse_attend
from ..nn import init as init_lib


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 2048
    dropout: float = 0.0
    rope_theta: float = 10000.0
    dtype: str = "float32"          # activation/compute dtype
    remat: bool = False             # per-block rematerialisation
    use_ring_attention: bool = False  # sp-sharded seq (needs mesh w/ 'sp')
    tie_embeddings: bool = False
    moe_experts: int = 0            # >0: routed experts ('ep'-sharded)
    moe_top_k: int = 1
    # a capacity per expert, overflow dropped (SwitchFFN); None: no
    # capacity and nothing dropped (RoutedExperts), d_ff the expert width
    moe_capacity_factor: Optional[float] = 1.25
    n_kv_heads: Optional[int] = None  # grouped heads; None: n_heads
    head_dim: Optional[int] = None    # None: d_model // n_heads
    qk_norm: bool = False           # per-head RMSNorm of q and k, pre-rope
    # learned sparse attention: an indexer of index_heads x index_dim
    # against one index key a token picks the index_top_k keys a query
    # attends (index_heads 0: dense causal attention)
    index_heads: int = 0
    index_dim: int = 64
    index_top_k: int = 2048
    # layers of more than one kind, one entry a layer (None: all alike).
    # windows[l] > 0: layer l is a sliding-window layer, a query at t
    # attends t - W < s <= t (0: global, causal); rope_layers[l] False:
    # layer l rotates neither q nor k (no positional embedding)
    windows: Optional[Sequence[int]] = None
    rope_layers: Optional[Sequence[bool]] = None
    # routed experts (moe_capacity_factor None): the gate's activation,
    # and whether the router reads the block's attention-normed input
    # (a router placed before attention) rather than the experts' own
    moe_activation: str = "silu"
    moe_router_pre_attention: bool = False
    # latent attention (kv_lora_rank > 0): a token caches ONE row of
    # kv_lora_rank + qk_rope_dim columns (a normed latent, then the one
    # rotated key every head shares) from which each head's key and value
    # are up-projected; queries go through a low-rank pair (q_lora_rank)
    # to n_heads x (qk_nope_dim + qk_rope_dim), values are v_head_dim wide
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN (latent attention's rope): {"factor", "original_max_position_
    # embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}
    rope_scaling: Optional[dict] = None
    # the first dense_layers blocks of a model of routed experts keep a
    # dense SwiGLU, dense_d_ff wide
    dense_layers: int = 0
    dense_d_ff: Optional[int] = None
    # RoutedExperts' routing (its constructor's arguments of these names):
    # how the router scores, group-limited selection, the gates' scale, a
    # selection-only bias, which experts this chip holds (first, count)
    # of the moe_experts the router scores, and a shared expert's width
    moe_scoring: str = "softmax"
    moe_groups: int = 0
    moe_top_groups: int = 0
    moe_routed_scale: float = 1.0
    moe_router_bias: bool = False
    moe_held: Optional[Tuple[int, int]] = None
    moe_shared_d_ff: int = 0

    def __post_init__(self):
        if self.head_dim is None and self.kv_lora_rank:
            self.head_dim = self.qk_nope_dim + self.qk_rope_dim
        if self.head_dim is None:
            assert self.d_model % self.n_heads == 0
            self.head_dim = self.d_model // self.n_heads
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        assert self.n_heads % self.n_kv_heads == 0
        for per_layer in (self.windows, self.rope_layers):
            assert per_layer is None or len(per_layer) == self.n_layers
        assert not (self.windows and any(self.windows)
                    and self.index_heads), \
            "a sliding window and a learned selection do not combine"
        assert not (self.kv_lora_rank and (
            self.index_heads or self.windows and any(self.windows))), \
            "latent attention is global and dense"

    def layer_window(self, i: int) -> int:
        return int(self.windows[i]) if self.windows else 0

    def layer_rope(self, i: int) -> bool:
        return bool(self.rope_layers[i]) if self.rope_layers else True


def rope_frequencies(dim: int, theta: float, scaling: Optional[dict] = None):
    """The ``dim / 2`` inverse frequencies of a rotary embedding:
    ``theta ** (-2j / dim)``, or under YaRN (``scaling``, the published
    ``rope_scaling`` group) ``(1 - g_j) / (factor theta_j) + g_j /
    theta_j`` with ``g`` one minus the linear ramp between the correction
    dims of ``beta_fast`` and ``beta_slow`` rotations over the original
    length: fast frequencies keep their own, slow ones are interpolated."""
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return freqs
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / scaling["factor"] * ramp + freqs * (1.0 - ramp)


def yarn_mscale(scaling: Optional[dict]) -> float:
    """YaRN's ``m = 0.1 mscale_all_dim ln(factor) + 1``, whose square
    scales the softmax (1 with no scaling).  cos and sin stay unscaled:
    that holds where ``mscale == mscale_all_dim``, all this supports."""
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    assert scaling["mscale"] == scaling["mscale_all_dim"], scaling
    return 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0


def apply_rope(x, positions, theta: float = 10000.0, freqs=None):
    """Rotary position embedding. x: (B, H, S, D), positions: (S,) global;
    ``freqs`` (D/2,) in place of the plain ``theta`` ladder."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    # re-interleave
    y = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return y.astype(x.dtype)


def apply_rope_rows(x, positions, theta: float = 10000.0, freqs=None):
    """:func:`apply_rope` with a PER-ROW position: x (B, H, 1, D),
    positions (B,) — the continuous-batching decode shape, where every
    batch row (slot) sits at its own global offset.  Same op sequence as
    :func:`apply_rope` (freqs → angles → cos/sin → rotate) so a row here
    is bitwise the row ``apply_rope`` would produce at that position."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (B, D/2)
    cos = jnp.cos(angles)[:, None, None, :]          # (B, 1, 1, D/2)
    sin = jnp.sin(angles)[:, None, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    y = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return y.astype(x.dtype)


class TokenEmbedding(Module):
    """0-based token embedding, vocab-sharded over tp (P('tp', None))
    and EXEMPT from fsdp layering (fsdp_exempt) — the weight is
    replicated over 'fsdp', sharded only over 'tp'.

    Root cause (round 3, closing NOTES item 2): when the table is
    sharded over TWO mesh axes on a 3-axis (dp, fsdp, tp) mesh and the
    batch is dp×fsdp-sharded, the GSPMD partitioner MISCOMPILES the
    gather + residual-matmul pattern — `take(w, ids) + take(w, ids) @ wo`
    alone computes values off by O(1) in fp32 (jax 0.9.0 CPU backend;
    checked-in repro: tests/test_partitioner_repro.py, which fails with
    an update-me message if a newer jax fixes it).  This is why the
    earlier d_model layout P(None,'tp') (which became P('fsdp','tp')
    under fsdp layering) changed the partitioned forward's loss
    (6.0741 vs 6.0859 on the tiny preset).  Keeping the table out of
    fsdp ALSO removes both "Involuntary full rematerialization" GSPMD
    warnings: the cotangent reshard no longer needs a mesh-axis
    transpose, and training-step parity is exact
    (tests/test_parallel.py::test_spmd_trainer_parallel_matches_single).
    """

    fsdp_exempt = True

    def __init__(self, vocab_size, d_model, name=None):
        super().__init__(name=name)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.pspec = {"weight": P("tp", None)}

    def init(self, rng):
        w = jax.random.normal(rng, (self.vocab_size, self.d_model),
                              jnp.float32) * (self.d_model ** -0.5)
        return {self.name: {"weight": w}}

    def apply(self, params, x, ctx):
        w = self.own(params)["weight"]
        return jnp.take(w, x.astype(jnp.int32), axis=0)


class MultiHeadAttention(Module):
    """Causal self-attention with RoPE + flash attention.

    ``n_heads`` query heads of ``head_dim`` share ``n_kv_heads`` key/value
    heads (grouped-query attention; equal by default); ``qk_norm`` puts a
    per-head RMSNorm on q and k before the rotation; ``index_heads`` > 0
    adds the learned selection of :mod:`bigdl_tpu.ops.sparse_attention`:
    three small projections of the block's normed input (index queries,
    ONE index key a token under a LayerNorm, a weight an index head) score
    every earlier token, and a query attends its ``index_top_k`` best.

    tp layout (megatron): wq/wk/wv column-sharded on the head dim
    (P(None, 'tp')), wo row-sharded (P('tp', None)) — under GSPMD the
    partitioner emits exactly one psum after wo.  When
    ``cfg.use_ring_attention`` the spmd trainer swaps the attention core
    for the sp ring (see parallel/spmd.py: _RING_HOOK).  The flash
    kernel and the ring take q, k and v of equal heads: grouped K and V
    are repeated for them (the cached paths never repeat).

    ``window`` > 0 makes this layer a sliding-window layer (a query at
    ``t`` attends ``t - window < s <= t``), ``rope`` False one that
    rotates neither q nor k: what ``TransformerConfig.windows`` /
    ``rope_layers`` say of the layer, handed over by the block.
    """

    latent = False        # per-head K and V rows (LatentAttention: one row)
    rope_freqs = None     # the plain theta ladder

    def __init__(self, cfg: TransformerConfig, name=None, window: int = 0,
                 rope: bool = True):
        super().__init__(name=name)
        self.cfg = cfg
        self.window, self.rope = int(window), bool(rope)
        self.pspec = {"wq": P(None, "tp"), "wk": P(None, "tp"),
                      "wv": P(None, "tp"), "wo": P("tp", None)}
        # the spmd trainer injects a mesh-aware attention fn here
        self.attention_fn = None

    @property
    def sparse(self):
        return self.cfg.index_heads > 0

    def init(self, rng):
        cfg = self.cfg
        ks = list(jax.random.split(rng, 4)) \
            + [jax.random.fold_in(rng, i) for i in (4, 5, 6)]
        scale = cfg.d_model ** -0.5
        mk = lambda k, n: jax.random.normal(
            k, (cfg.d_model, n), jnp.float32) * scale
        qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        p = {"wq": mk(ks[0], qd), "wk": mk(ks[1], kvd), "wv": mk(ks[2], kvd),
             "wo": jax.random.normal(ks[3], (qd, cfg.d_model), jnp.float32)
             * qd ** -0.5}
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
            p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        if self.sparse:
            p["wqi"] = mk(ks[4], cfg.index_heads * cfg.index_dim)
            p["wki"] = mk(ks[5], cfg.index_dim)
            p["wwi"] = mk(ks[6], cfg.index_heads)
            p["ki_norm"] = jnp.ones((cfg.index_dim,), jnp.float32)
            p["ki_bias"] = jnp.zeros((cfg.index_dim,), jnp.float32)
        return {self.name: p}

    # -- projections ------------------------------------------------------ #
    def project_qkv(self, params, x, rope):
        """x (B, S, d_model) -> q (B, H, S, Dh), k and v (B, Hkv, S, Dh);
        ``rope`` rotates a (B, heads, S, D) array at the call's positions
        (one row of positions for a sequence, or one position a batch row
        for slot-batched decode).  Split from the attention itself so a
        paged KV cache can own the write and the attention in between
        (:meth:`TransformerBlock.apply_decode`); :meth:`project_out`
        closes it."""
        cfg = self.cfg
        p = self.own(params)
        b, s, _ = x.shape
        dt = x.dtype

        def proj(w, heads, gain=None):
            y = jnp.dot(x, w.astype(dt)).reshape(b, s, heads, cfg.head_dim)
            if gain is not None:
                y = _rms(y, gain)
            return jnp.transpose(y, (0, 2, 1, 3))

        q = rope(proj(p["wq"], cfg.n_heads, p.get("q_norm")))
        k = rope(proj(p["wk"], cfg.n_kv_heads, p.get("k_norm")))
        return q, k, proj(p["wv"], cfg.n_kv_heads)

    def project_index(self, params, x, rope):
        """The indexer's reading of x (B, S, d_model): index queries
        (B, S, Hi, Di) and the token's ONE index key (B, S, Di), both
        rotated, and a weight an index head (B, S, Hi)."""
        cfg = self.cfg
        p = self.own(params)
        b, s, _ = x.shape
        dt = x.dtype
        qi = jnp.dot(x, p["wqi"].astype(dt)).reshape(
            b, s, cfg.index_heads, cfg.index_dim)
        qi = jnp.swapaxes(rope(jnp.swapaxes(qi, 1, 2)), 1, 2)
        ki = jnp.dot(x, p["wki"].astype(dt)).astype(jnp.float32)
        mu = ki.mean(-1, keepdims=True)
        ki = (ki - mu) * lax.rsqrt(
            jnp.square(ki - mu).mean(-1, keepdims=True) + 1e-6) \
            * p["ki_norm"] + p["ki_bias"]
        ki = rope(ki.astype(dt)[:, None])[:, 0]
        return qi, ki, jnp.dot(x, p["wwi"].astype(dt))

    def project_out(self, params, o):
        """Output projection of an attention result o (B, H, S, Dh) ->
        (B, S, d_model)."""
        b, _, s, _ = o.shape
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, -1)
        return jnp.dot(o, self.own(params)["wo"].astype(o.dtype))

    # -- the three entry points ------------------------------------------- #
    def apply(self, params, x, ctx):
        cfg = self.cfg
        b, s, _ = x.shape
        positions = jnp.arange(s)
        rope = self.rotation(lambda t: apply_rope(t, positions,
                                                  cfg.rope_theta))
        q, k, v = self.project_qkv(params, x, rope)
        if self.sparse:
            qi, ki, w = self.project_index(params, x, rope)
            o = sparse_attend(q, k, v, jnp.broadcast_to(positions, (b, s)),
                              jnp.full((b,), s), (qi, ki, w),
                              cfg.index_top_k)
        elif self.window:
            o = sparse_attend(q, k, v, jnp.broadcast_to(positions, (b, s)),
                              jnp.full((b,), s), window=self.window)
        else:
            if cfg.n_kv_heads != cfg.n_heads:
                rep = cfg.n_heads // cfg.n_kv_heads
                k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            if self.attention_fn is not None:
                o = self.attention_fn(q, k, v)
            else:
                o = flash_attention(q, k, v, causal=True)
        return self.project_out(params, o)

    def rotation(self, rope):
        """``rope`` for a layer that rotates, the identity for one that
        does not."""
        return rope if self.rope else (lambda t: t)

    def apply_cached(self, params, x, cache, start):
        """Incremental attention for generation: project the ``s`` new
        positions (global offsets ``start + arange(s)``), write their k/v
        (and index keys) into the static-length cache
        (``lax.dynamic_update_slice`` — the compiled program is
        position-independent), and attend q against the whole cache under
        a global causal mask that also hides unwritten cache slots.  One
        code path covers prompt prefill (s = prompt length) and decode
        (s = 1)."""
        cfg = self.cfg
        b, s, _ = x.shape
        positions = start + jnp.arange(s)
        rope = self.rotation(lambda t: apply_rope(t, positions,
                                                  cfg.rope_theta))
        q, k, v = self.project_qkv(params, x, rope)
        new = {"k": lax.dynamic_update_slice(
                   cache["k"], k.astype(cache["k"].dtype), (0, 0, start, 0)),
               "v": lax.dynamic_update_slice(
                   cache["v"], v.astype(cache["v"].dtype), (0, 0, start, 0))}
        if not self.sparse and not self.window \
                and cfg.n_kv_heads == cfg.n_heads:
            # one K/V head a query head and no selection: the plain
            # sequence, kept as it was so that the programs of the models
            # that have always taken it do not change
            k_pos = jnp.arange(new["k"].shape[2])
            s_ = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            new["k"].astype(jnp.float32)) \
                / np.sqrt(cfg.head_dim)
            # same mask primitive as the kernels; kv_len = start + s also
            # masks unwritten cache slots explicitly
            mask = _attn_mask(positions, k_pos, start + s, True)
            s_ = jnp.where(mask[None, None], s_, DEFAULT_MASK_VALUE)
            w_ = jax.nn.softmax(s_, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", w_,
                           new["v"].astype(jnp.float32)).astype(x.dtype)
            return self.project_out(params, o), new
        index = None
        if self.sparse:
            qi, ki, w = self.project_index(params, x, rope)
            new["ki"] = lax.dynamic_update_slice(
                cache["ki"], ki.astype(cache["ki"].dtype), (0, start, 0))
            index = (qi, new["ki"], w)
        o = sparse_attend(q, new["k"], new["v"],
                          jnp.broadcast_to(positions, (b, s)),
                          jnp.broadcast_to(start + s, (b,)), index,
                          cfg.index_top_k, window=self.window)
        return self.project_out(params, o), new


def _rms(y, gain, eps=1e-6):
    yf = y.astype(jnp.float32)
    return (yf * lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + eps)
            * gain).astype(y.dtype)


class LatentAttention(Module):
    """Causal self-attention whose cache is ONE row a token (multi-head
    latent attention).  With ``h`` the block's normed input:

        c_q = RMSNorm(h W_qa)                      (q_lora_rank)
        [q_n,i ; q_r,i] = c_q W_qb                 (qk_nope + qk_rope a head)
        [c ; k_r] = h W_kva;  c <- RMSNorm(c)      (kv_lora_rank + qk_rope)
        k_r <- rope(k_r)  (one a token, every head's);  q_r,i <- rope(q_r,i)
        [k_n,i ; v_i] = c W_kvb                    (qk_nope + v_head a head)
        score_i(t, s) = (q_n,i(t) . k_n,i(s) + q_r,i(t) . k_r(s)) * scale
        a_i = softmax_s(score_i) v_i;  out = concat_i(a_i) W_o

    ``scale`` is ``(qk_nope + qk_rope) ** -0.5`` times the square of
    YaRN's ``m`` (:func:`yarn_mscale`); the rotation is YaRN's
    (:func:`rope_frequencies`).  What is cached is the row ``[c ; k_r]``
    (:meth:`latent_rows`), never a per-head key or value.  The full
    forward and the static cache up-project every cached row
    (:meth:`attend_rows`); through a paged cache a decode step is
    ABSORBED: with ``W_kvb = [W_uk,i ; W_uv,i]`` the query ``q~_i =
    [q_n,i W_uk,i^T ; q_r,i]`` (:meth:`absorb`) scores against the row
    itself, the cache returns ``o~_i = softmax . c`` and ``a_i = o~_i
    W_uv,i`` (:meth:`up_values`); a prompt chunk hands the cache its
    un-absorbed queries and both halves of ``W_kvb``
    (:meth:`up_weights`), and the cache's route picks the formula.

    tp layout: heads column-sharded on ``wq_b`` / ``wkv_b``, ``wo``
    row-sharded; the two down-projections are replicated.
    """

    latent = True
    window, rope, sparse = 0, True, False

    def __init__(self, cfg: TransformerConfig, name=None):
        super().__init__(name=name)
        self.cfg = cfg
        self.pspec = {"wq_b": P(None, "tp"), "wkv_b": P(None, "tp"),
                      "wo": P("tp", None)}
        self.attention_fn = None          # (the spmd trainer's hook: unused)
        self.sm_scale = float((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
                              * yarn_mscale(cfg.rope_scaling) ** 2)

    @property
    def rope_freqs(self):
        cfg = self.cfg
        return rope_frequencies(cfg.qk_rope_dim, cfg.rope_theta,
                                cfg.rope_scaling)

    @property
    def row_dim(self):
        """Columns of the cached row: the latent, then the rope key."""
        return self.cfg.kv_lora_rank + self.cfg.qk_rope_dim

    def init(self, rng):
        cfg = self.cfg
        ks = jax.random.split(rng, 5)
        h, qd = cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim
        mk = lambda k, m, n: jax.random.normal(k, (m, n), jnp.float32) \
            * m ** -0.5
        return {self.name: {
            "wq_a": mk(ks[0], cfg.d_model, cfg.q_lora_rank),
            "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
            "wq_b": mk(ks[1], cfg.q_lora_rank, h * qd),
            "wkv_a": mk(ks[2], cfg.d_model, self.row_dim),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
            "wkv_b": mk(ks[3], cfg.kv_lora_rank,
                        h * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "wo": mk(ks[4], h * cfg.v_head_dim, cfg.d_model)}}

    # -- projections ------------------------------------------------------ #
    def queries(self, params, x, rope):
        """x (B, S, d_model) -> q_n (B, H, S, qk_nope) and q_r (B, H, S,
        qk_rope), the latter rotated by ``rope``."""
        cfg, p, dt = self.cfg, self.own(params), x.dtype
        b, s, _ = x.shape
        c_q = _rms(jnp.dot(x, p["wq_a"].astype(dt)), p["q_norm"])
        q = jnp.dot(c_q, p["wq_b"].astype(dt)).reshape(
            b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
        q = jnp.transpose(q, (0, 2, 1, 3))
        return q[..., :cfg.qk_nope_dim], rope(q[..., cfg.qk_nope_dim:])

    def latent_rows(self, params, x, rope):
        """x (B, S, d_model) -> the rows a cache holds, (B, S, kv_lora_rank
        + qk_rope): the latent under its norm, then the ONE rotated key."""
        p, dt, rank = self.own(params), x.dtype, self.cfg.kv_lora_rank
        kv = jnp.dot(x, p["wkv_a"].astype(dt))
        c = _rms(kv[..., :rank], p["kv_norm"])
        return jnp.concatenate([c, rope(kv[..., rank:][:, None])[:, 0]], -1)

    def up_weights(self, params, dtype):
        """``W_kvb`` by head: W_uk (rank, H, qk_nope), W_uv (rank, H,
        v_head)."""
        cfg = self.cfg
        w = self.own(params)["wkv_b"].astype(dtype).reshape(
            cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]

    def absorb(self, params, q_n, q_r):
        """The query that scores against a cached row as it lies:
        ``[q_n,i W_uk,i^T ; q_r,i]`` (B, H, S, rank + qk_rope)."""
        w_uk, _ = self.up_weights(params, q_n.dtype)
        return jnp.concatenate(
            [jnp.einsum("bhsn,rhn->bhsr", q_n, w_uk), q_r], -1)

    def up_values(self, params, o):
        """An absorbed result ``softmax . c`` (B, H, S, rank) -> a head's
        values (B, H, S, v_head)."""
        _, w_uv = self.up_weights(params, o.dtype)
        return jnp.einsum("bhsr,rhv->bhsv", o, w_uv)

    def project_out(self, params, o):
        b, _, s, _ = o.shape
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, -1)
        return jnp.dot(o, self.own(params)["wo"].astype(o.dtype))

    def attend_rows(self, params, q_n, q_r, rows, mask):
        """Un-absorbed attention of queries (B, H, S, .) over cached rows
        (B, L, rank + qk_rope), every row's key and value up-projected;
        ``mask`` (B, S, L) bool.  Scores and softmax float32."""
        rank = self.cfg.kv_lora_rank
        w_uk, w_uv = self.up_weights(params, rows.dtype)
        c, k_r = rows[..., :rank], rows[..., rank:]
        k_n = jnp.einsum("blr,rhn->bhln", c, w_uk)
        v = jnp.einsum("blr,rhv->bhlv", c, w_uv)
        s_ = (jnp.einsum("bhsn,bhln->bhsl", q_n, k_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhsr,blr->bhsl", q_r, k_r,
                           preferred_element_type=jnp.float32)) \
            * self.sm_scale
        w_ = jax.nn.softmax(
            jnp.where(mask[:, None], s_, DEFAULT_MASK_VALUE), axis=-1)
        return jnp.einsum("bhsl,bhlv->bhsv", w_.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(q_n.dtype)

    # -- the entry points that own their keys -------------------------------- #
    def apply(self, params, x, ctx):
        s = x.shape[1]
        positions = jnp.arange(s)
        rope = lambda t: apply_rope(t, positions, freqs=self.rope_freqs)
        q_n, q_r = self.queries(params, x, rope)
        rows = self.latent_rows(params, x, rope)
        mask = positions[None, :, None] >= positions[None, None, :]
        return self.project_out(
            params, self.attend_rows(params, q_n, q_r, rows, mask))

    def apply_cached(self, params, x, cache, start):
        """:meth:`MultiHeadAttention.apply_cached` over a static cache of
        latent rows ``{"latent": (B, L, rank + qk_rope)}``."""
        s = x.shape[1]
        positions = start + jnp.arange(s)
        rope = lambda t: apply_rope(t, positions, freqs=self.rope_freqs)
        q_n, q_r = self.queries(params, x, rope)
        rows = self.latent_rows(params, x, rope)
        new = {"latent": lax.dynamic_update_slice(
            cache["latent"], rows.astype(cache["latent"].dtype),
            (0, start, 0))}
        k_pos = jnp.arange(new["latent"].shape[1])
        mask = _attn_mask(positions, k_pos, start + s, True)[None]
        return self.project_out(params, self.attend_rows(
            params, q_n, q_r, new["latent"], mask)), new

    def through_cache(self, params, x, rope, kv_io, chunk: bool):
        """The paged seam (see :meth:`TransformerBlock.apply_decode`) for
        a latent layer: ``kv_io(name, q, rows[, up])`` writes the rows
        (B, S, rank + qk_rope) and attends.  A decode step hands it the
        absorbed query and takes ``softmax . c`` (B, H, 1, rank) back; a
        chunk hands it the un-absorbed query ``[q_n ; q_r]`` and ``up`` =
        (W_uk, W_uv), and takes the heads' values (1, H, C, v_head)."""
        q_n, q_r = self.queries(params, x, rope)
        rows = self.latent_rows(params, x, rope)
        if chunk:
            a = kv_io(self.name, jnp.concatenate([q_n, q_r], -1), rows,
                      up=self.up_weights(params, x.dtype))
        else:
            a = self.up_values(params, kv_io(
                self.name, self.absorb(params, q_n, q_r), rows))
        return self.project_out(params, a)


class SwiGLU(Module):
    """Gated MLP: (silu(x w1) * x w3) w2 — two column-sharded matmuls in,
    one row-sharded out; XLA fuses the gate elementwise into the matmul
    epilogue, so the MXU sees three big GEMMs and HBM sees no extra trip."""

    def __init__(self, cfg: TransformerConfig, name=None, d_ff=None):
        super().__init__(name=name)
        self.cfg = cfg
        self.d_ff = int(d_ff or cfg.d_ff)    # (a leading dense layer's own)
        self.pspec = {"w1": P(None, "tp"), "w3": P(None, "tp"),
                      "w2": P("tp", None)}

    def init(self, rng):
        cfg, d_ff = self.cfg, self.d_ff
        k1, k2, k3 = jax.random.split(rng, 3)
        s_in = cfg.d_model ** -0.5
        s_out = d_ff ** -0.5
        return {self.name: {
            "w1": jax.random.normal(k1, (cfg.d_model, d_ff)) * s_in,
            "w3": jax.random.normal(k3, (cfg.d_model, d_ff)) * s_in,
            "w2": jax.random.normal(k2, (d_ff, cfg.d_model)) * s_out,
        }}

    def apply(self, params, x, ctx):
        p = self.own(params)
        dt = x.dtype
        h = jax.nn.silu(jnp.dot(x, p["w1"].astype(dt))) \
            * jnp.dot(x, p["w3"].astype(dt))
        return jnp.dot(h, p["w2"].astype(dt))


class TransformerBlock(Module):
    """Pre-norm attention, then the MLP (dense, or routed experts), each
    around a residual.  ``layer`` is the block's index: what kind of
    attention layer it is comes from ``cfg.windows`` / ``cfg.rope_layers``
    there (``cfg.kv_lora_rank`` > 0: :class:`LatentAttention` in every
    layer), and whether a model of routed experts keeps a dense MLP in
    it from ``cfg.dense_layers``.  With ``cfg.moe_router_pre_attention`` the experts' router
    reads the block's attention-normed input ``norm1(x)``, while the
    experts themselves read ``norm2(x + attention)`` as ever."""

    def __init__(self, cfg: TransformerConfig, name=None, layer: int = 0):
        super().__init__(name=name)
        self.cfg = cfg
        self.norm1 = RMSNorm(cfg.d_model, name=f"{self.name}.norm1")
        if cfg.kv_lora_rank:
            self.attn = LatentAttention(cfg, name=f"{self.name}.attn")
        else:
            self.attn = MultiHeadAttention(cfg, name=f"{self.name}.attn",
                                           window=cfg.layer_window(layer),
                                           rope=cfg.layer_rope(layer))
        self.norm2 = RMSNorm(cfg.d_model, name=f"{self.name}.norm2")
        self.router_pre_attention = False
        if cfg.moe_experts > 0 and layer < cfg.dense_layers:
            # a leading dense layer of a model of routed experts
            self.mlp = SwiGLU(cfg, name=f"{self.name}.mlp",
                              d_ff=cfg.dense_d_ff)
        elif cfg.moe_experts > 0 and cfg.moe_capacity_factor is None:
            from ..nn.moe import RoutedExperts
            self.mlp = RoutedExperts(
                cfg.d_model, cfg.d_ff, cfg.moe_experts, cfg.moe_top_k,
                name=f"{self.name}.moe", activation=cfg.moe_activation,
                scoring=cfg.moe_scoring, n_groups=cfg.moe_groups,
                top_groups=cfg.moe_top_groups,
                routed_scale=cfg.moe_routed_scale,
                router_bias=cfg.moe_router_bias, held=cfg.moe_held,
                shared_d_ff=cfg.moe_shared_d_ff)
            self.router_pre_attention = cfg.moe_router_pre_attention
        elif cfg.moe_experts > 0:
            from ..nn.moe import SwitchFFN
            self.mlp = SwitchFFN(cfg.d_model, cfg.d_ff, cfg.moe_experts,
                                 top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.moe_capacity_factor,
                                 name=f"{self.name}.moe")
        else:
            self.mlp = SwiGLU(cfg, name=f"{self.name}.mlp")

    def children(self):
        return [self.norm1, self.attn, self.norm2, self.mlp]

    def init(self, rng):
        out = {}
        for i, c in enumerate(self.children()):
            out.update(c.init(jax.random.fold_in(rng, i)))
        return out

    def apply(self, params, x, ctx):
        n1 = self.norm1.apply(params, x, ctx)
        h = x + self._drop(self.attn.apply(params, n1, ctx), ctx)
        return h + self._drop(self._mlp(params, h, n1, ctx), ctx)

    def _mlp(self, params, h, n1, ctx):
        """The MLP of ``h`` (the stream after attention); ``n1`` is the
        block's attention-normed input, which a router placed before
        attention reads."""
        u = self.norm2.apply(params, h, ctx)
        if self.router_pre_attention:
            return self.mlp.apply(params, u, ctx, router_input=n1)
        return self.mlp.apply(params, u, ctx)

    def apply_cached(self, params, x, ctx, cache, start):
        n1 = self.norm1.apply(params, x, ctx)
        a, cache = self.attn.apply_cached(params, n1, cache, start)
        h = x + a
        return h + self._mlp(params, h, n1, ctx), cache

    def apply_decode(self, params, x, ctx, positions, kv_io):
        """Slot-batched single-token decode: x (B, 1, d_model),
        positions (B,).  ``kv_io(attn_name, q, k_new, v_new[, index]) ->
        o`` is the paged-KV seam — it writes this token's k/v rows into
        the cache and returns the attention of q (B, H, 1, Dh) over the
        slot's keys, the rows just written included (the same
        update-then-attend order :meth:`apply_cached` uses), as
        (B, H, 1, Dh).  The cache owns the attention because how it is
        computed depends on where the pages lie, not on the model.  A
        sparse attention hands it ``index`` = (qi (B, 1, Hi, Di), ki
        (B, 1, Di), w (B, 1, Hi)) as well: the index key is cached with
        k and v, and the selection is the cache's to apply.  A latent
        layer's seam is ``kv_io(attn_name, q, rows[, up])``
        (:meth:`LatentAttention.through_cache`): one row a token in, and
        for a decode step an absorbed query's ``softmax . c`` out."""
        return self._through_cache(
            params, x, ctx, kv_io,
            lambda t: apply_rope_rows(t, positions, self.cfg.rope_theta,
                                      self.attn.rope_freqs))

    def apply_chunk(self, params, x, ctx, start, kv_io):
        """A chunk of ONE sequence's prompt through the same seam: x
        (1, C, d_model) at positions ``start + arange(C)``; ``kv_io``
        writes the chunk's rows and attends each query over the slot's
        cache up to its own position."""
        positions = start + jnp.arange(x.shape[1])
        return self._through_cache(
            params, x, ctx, kv_io,
            lambda t: apply_rope(t, positions, self.cfg.rope_theta,
                                 self.attn.rope_freqs), chunk=True)

    def _through_cache(self, params, x, ctx, kv_io, rope, chunk=False):
        n1 = self.norm1.apply(params, x, ctx)
        if self.attn.latent:
            h = x + self.attn.through_cache(params, n1, rope, kv_io, chunk)
            return h + self._mlp(params, h, n1, ctx)
        rope = self.attn.rotation(rope)
        qkv = self.attn.project_qkv(params, n1, rope)
        if self.attn.sparse:
            qkv += (self.attn.project_index(params, n1, rope),)
        h = x + self.attn.project_out(params, kv_io(self.attn.name, *qkv))
        return h + self._mlp(params, h, n1, ctx)

    def _drop(self, x, ctx):
        rate = self.cfg.dropout
        if not ctx.training or rate <= 0.0:
            return x
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.rng(self), keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


class LMHead(Module):
    """Final projection to vocab logits, vocab-sharded over tp."""

    def __init__(self, cfg: TransformerConfig, name=None):
        super().__init__(name=name)
        self.cfg = cfg
        self.pspec = {"weight": P(None, "tp")}

    def init(self, rng):
        cfg = self.cfg
        w = jax.random.normal(rng, (cfg.d_model, cfg.vocab_size),
                              jnp.float32) * (cfg.d_model ** -0.5)
        return {self.name: {"weight": w}}

    def apply(self, params, x, ctx):
        return jnp.dot(x, self.own(params)["weight"].astype(x.dtype))


class TransformerLM(Module):
    """Decoder-only causal LM. tokens (B, S) int -> logits (B, S, V)."""

    def __init__(self, cfg: TransformerConfig, name=None):
        super().__init__(name=name)
        self.cfg = cfg
        self.embed = TokenEmbedding(cfg.vocab_size, cfg.d_model,
                                    name=f"{self.name}.embed")
        self._remat_blocks = None
        self.blocks = [TransformerBlock(cfg, name=f"{self.name}.block{i}",
                                        layer=i)
                       for i in range(cfg.n_layers)]
        self.final_norm = RMSNorm(cfg.d_model, name=f"{self.name}.final_norm")
        self.head = None if cfg.tie_embeddings else \
            LMHead(cfg, name=f"{self.name}.head")

    def children(self):
        out = [self.embed] + self.blocks + [self.final_norm]
        if self.head is not None:
            out.append(self.head)
        return out

    def init(self, rng):
        out = {}
        for i, c in enumerate(self.children()):
            out.update(c.init(jax.random.fold_in(rng, i)))
        return out

    def apply_trunk(self, params, x, ctx):
        """Everything up to (and including) the final norm: (B, S) int ->
        hidden states (B, S, d_model) in cfg.dtype."""
        cfg = self.cfg
        h = self.embed.apply(params, x, ctx)
        h = h.astype(jnp.dtype(cfg.dtype))

        if cfg.remat and self._remat_blocks is None:
            # lazily, AFTER the model is fully built, so the wrappers'
            # uids never shift the model's own auto names; nn.Remat also
            # threads inner state/side-losses (e.g. MoE aux losses) out
            # through the checkpoint boundary, which the old hand-rolled
            # remat silently dropped
            from ..nn import Remat
            self._remat_blocks = [Remat(b) for b in self.blocks]
        for blk in (self._remat_blocks if cfg.remat else self.blocks):
            h = blk.apply(params, h, ctx)

        return self.final_norm.apply(params, h, ctx)

    def head_logits(self, params, h, ctx):
        """Vocab projection of trunk hiddens (dtype preserved)."""
        if self.head is not None:
            return self.head.apply(params, h, ctx)
        w = params[self.embed.name]["weight"]            # (V, D) tied
        return jnp.dot(h, w.T.astype(h.dtype))

    def apply(self, params, x, ctx):
        h = self.apply_trunk(params, x, ctx)
        return self.head_logits(params, h, ctx).astype(jnp.float32)

    def token_nll(self, params, tokens, targets, *, ignore_index=-1,
                  loss_chunk=None, training=False, rng=None, ctx=None):
        """(sum of masked token NLLs, valid-token count), optionally with
        the head+loss computed per sequence chunk.

        ``loss_chunk=c`` (must divide S) never materializes more than
        (B, c, V) logits: each chunk's projection and log-sum-exp run
        under ``jax.checkpoint`` inside a ``lax.scan``, so the backward
        recomputes chunk logits instead of holding the full (B, S, V)
        fp32 tensor — the memory wall for long-context vocab losses
        (S=8k, V=32k is 1 GB per sample in fp32).  Numerics are
        identical to the unchunked path (same per-token log-sum-exp;
        only the summation order over chunks differs).
        """
        if ctx is None:
            ctx = Ctx(state={}, training=training, rng_key=rng)
        h = self.apply_trunk(params, tokens, ctx)
        S = h.shape[1]
        if not loss_chunk or loss_chunk >= S:
            logits = self.head_logits(params, h, ctx).astype(jnp.float32)
            return lm_token_nll(logits, targets, ignore_index)
        head_ctx = Ctx(state={}, training=ctx.training, rng_key=None)
        return chunked_token_nll(
            lambda h_c: self.head_logits(params, h_c, head_ctx),
            h, targets, loss_chunk, ignore_index)

    def loss(self, params, tokens, targets, *, ignore_index=-1,
             loss_chunk=None, training=False, rng=None, ctx=None):
        """Mean masked token cross-entropy (see :meth:`token_nll`)."""
        tot, cnt = self.token_nll(params, tokens, targets,
                                  ignore_index=ignore_index,
                                  loss_chunk=loss_chunk, training=training,
                                  rng=rng, ctx=ctx)
        return tot / jnp.maximum(cnt, 1.0)

    # -- generation (kv cache) ----------------------------------------- #
    def init_cache(self, batch: int, dtype=None, cache_len=None):
        """Static-length kv cache, one entry per block, keyed by the
        attention module's name (so caches survive pytree transforms).
        ``cache_len`` defaults to max_len; generate() sizes it to
        prompt+new so each decode step attends over exactly the tokens
        that can exist, not the full context window."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        n = int(cache_len or cfg.max_len)
        shape = (batch, cfg.n_kv_heads, n, cfg.head_dim)

        def one():
            if cfg.kv_lora_rank:
                return {"latent": jnp.zeros(
                    (batch, n, cfg.kv_lora_rank + cfg.qk_rope_dim), dt)}
            out = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            if cfg.index_heads:
                out["ki"] = jnp.zeros((batch, n, cfg.index_dim), dt)
            return out

        return {blk.attn.name: one() for blk in self.blocks}

    def kv_geometry(self):
        """What a paged cache has to know of this model's rows
        (``PagedKVCache``'s arguments of these names): KV heads of
        ``head_dim`` under ``q_heads`` query heads, with index keys or
        not; or, for latent attention, ONE row a token of ``head_dim`` =
        rank + rope columns whose first ``latent_rank`` are the value too,
        scored at the model's own ``sm_scale``."""
        cfg = self.cfg
        if cfg.kv_lora_rank:
            attn = self.blocks[0].attn
            return dict(n_heads=1, q_heads=cfg.n_heads,
                        head_dim=attn.row_dim, latent_rank=cfg.kv_lora_rank,
                        sm_scale=attn.sm_scale)
        return dict(n_heads=cfg.n_kv_heads, q_heads=cfg.n_heads,
                    head_dim=cfg.head_dim,
                    index_dim=cfg.index_dim if cfg.index_heads else 0,
                    index_top_k=cfg.index_top_k if cfg.index_heads else 0)

    def apply_with_cache(self, params, tokens, cache, start):
        """logits for ``tokens`` (B, s) written at global offset ``start``
        into ``cache``; returns (logits fp32 (B, s, V), new cache)."""
        cfg = self.cfg
        ctx = Ctx(state={}, training=False, rng_key=None)
        h = self.embed.apply(params, tokens, ctx).astype(jnp.dtype(cfg.dtype))
        new_cache = {}
        for blk in self.blocks:
            h, new_cache[blk.attn.name] = blk.apply_cached(
                params, h, ctx, cache[blk.attn.name], start)
        h = self.final_norm.apply(params, h, ctx)
        if self.head is not None:
            logits = self.head.apply(params, h, ctx)
        else:
            w = params[self.embed.name]["weight"]
            logits = jnp.dot(h, w.T.astype(h.dtype))
        return logits.astype(jnp.float32), new_cache

    def decode_tokens(self, params, tokens, positions, kv_io, ctx=None):
        """Continuous-batching decode core: one new token per slot.

        ``tokens`` (B,) int32 are each slot's freshly emitted token,
        ``positions`` (B,) its global index (== the slot's current
        sequence length), and ``kv_io(attn_name, q, k_new, v_new) ->
        o`` the paged-cache write/attend seam (see
        :meth:`TransformerBlock.apply_decode`).  Returns fp32 logits
        (B, V) for each slot's NEXT position.  Unlike
        :meth:`apply_with_cache` every batch row advances at its own
        offset, which is what lets a serving engine admit/retire
        requests per decode step instead of per batch.  ``ctx`` (the
        engine's, optional) carries which slots are live
        (``token_mask``) and takes what the layers count."""
        cfg = self.cfg
        if ctx is None:
            ctx = Ctx(state={}, training=False, rng_key=None)
        h = self.embed.apply(params, tokens[:, None], ctx)
        h = h.astype(jnp.dtype(cfg.dtype))
        for blk in self.blocks:
            h = blk.apply_decode(params, h, ctx, positions, kv_io)
        h = self.final_norm.apply(params, h, ctx)
        return self.head_logits(params, h, ctx)[:, 0].astype(jnp.float32)

    def prefill_chunk(self, params, tokens, start, n_valid, kv_io, ctx=None):
        """One chunk of one sequence's prompt against its paged cache:
        ``tokens`` (1, C) at positions ``start + arange(C)`` of which the
        first ``n_valid`` are the prompt's (the rest pad the last chunk),
        through the blocks' :meth:`TransformerBlock.apply_chunk` seam.
        Returns fp32 logits (V,) after the chunk's last valid token: the
        request's first token when this is its last chunk."""
        cfg = self.cfg
        if ctx is None:
            ctx = Ctx(state={}, training=False, rng_key=None)
        ctx.token_mask = (jnp.arange(tokens.shape[1]) < n_valid)[None]
        h = self.embed.apply(params, tokens, ctx).astype(jnp.dtype(cfg.dtype))
        for blk in self.blocks:
            h = blk.apply_chunk(params, h, ctx, start, kv_io)
        h = lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
        h = self.final_norm.apply(params, h, ctx)
        return self.head_logits(params, h, ctx)[0, 0].astype(jnp.float32)

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, rng=None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 params_transform=None):
        """Autoregressive decode with a kv cache: ONE compiled prefill
        (prompt length) + ONE compiled ``lax.scan`` of single-token steps
        (static shapes throughout, so repeated calls with equal prompt
        length/batch reuse both programs).  temperature 0 = greedy, else
        softmax sampling with ``rng``.  Returns (B, prompt+new) tokens.

        ≙ the reference's RecurrentDecoder generation loop
        (nn/RecurrentDecoder.scala) rebuilt for attention models.

        ``params_transform`` maps the params INSIDE the compiled
        program (e.g. quantized.dequantize_weights for weight-only-int8
        serving: weights live in HBM as int8; the reconstruct traces
        into the program where XLA places it).
        """
        cfg = self.cfg
        prompt = jnp.asarray(prompt, jnp.int32)
        b, s0 = prompt.shape
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if max_new_tokens < 1:
            return prompt
        if s0 + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt({s0}) + max_new_tokens({max_new_tokens}) exceeds "
                f"max_len={cfg.max_len}")
        if temperature > 0.0 and rng is None:
            rng = jax.random.PRNGKey(0)

        def select(logits_last, key):
            if temperature <= 0.0:
                return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
            lg = logits_last / temperature
            if top_k is not None and top_k < lg.shape[-1]:
                kth = lax.top_k(lg, top_k)[0][..., -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            if top_p is not None and 0.0 < top_p < 1.0:
                # nucleus: keep the smallest prefix of the sorted probs
                # whose mass reaches top_p (the top token always survives)
                srt = jnp.sort(lg, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(srt, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = cum - probs < top_p
                cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                                 keepdims=True)
                lg = jnp.where(lg < cutoff, -jnp.inf, lg)
            return jax.random.categorical(key, lg, axis=-1).astype(
                jnp.int32)

        memo = getattr(self, "_gen_fns", None)
        if memo is None:
            memo = self._gen_fns = {}
        memo_key = (b, s0, int(max_new_tokens), float(temperature),
                    top_k, top_p, id(params_transform))
        hit = memo.get(memo_key)
        # the memo value holds a strong ref to the transform so its id()
        # can't be recycled by a new object while the entry lives, and
        # identity is re-checked on hit anyway (a raw id() match after
        # garbage collection would hand back a program with the OLD
        # transform baked in)
        if hit is not None and hit[0] is params_transform:
            return hit[1](params, prompt, rng)

        @jax.jit
        def run(params, prompt, rng):
            if params_transform is not None:
                params = params_transform(params)
            cache = self.init_cache(
                b, cache_len=s0 + max_new_tokens)
            logits, cache = self.apply_with_cache(params, prompt, cache, 0)
            key0, key = (jax.random.split(rng) if rng is not None
                         else (None, None))
            tok = select(logits[:, -1], key0)

            def step(carry, i):
                tok, cache, key = carry
                # `tok` is the token AT position s0+i: write it there and
                # sample position s0+i+1's token
                lg, cache = self.apply_with_cache(
                    params, tok[:, None], cache, s0 + i)
                if key is not None:
                    key, sub = jax.random.split(key)
                else:
                    sub = None
                nxt = select(lg[:, -1], sub)
                return (nxt, cache, key), tok

            (last, _, _), toks = lax.scan(
                step, (tok, cache, key), jnp.arange(max_new_tokens - 1))
            out = jnp.moveaxis(toks, 0, 1)               # (B, new-1)
            return jnp.concatenate([prompt, out, last[:, None]], axis=1)

        memo[memo_key] = (params_transform, run)
        if len(memo) > 8:   # bound compiled-program retention
            memo.pop(next(iter(memo)))
        return run(params, prompt, rng)

    def generate_beam(self, params, prompt, max_new_tokens: int,
                      beam_size: int = 4, eos_id: Optional[int] = None,
                      length_penalty: float = 0.0):
        """Beam-search decode with the kv cache.

        Keeps ``beam_size`` hypotheses per sequence: the cache runs at
        batch B*beam and is gathered along the beam dim after each step's
        top-k over (beam x vocab) continuations.  Beams that emit
        ``eos_id`` freeze (score stops accumulating, eos repeats).
        Returns (tokens (B, s0+new), scores (B,)) of the best hypothesis;
        scores are summed token log-probs / (length ** length_penalty).
        """
        cfg = self.cfg
        prompt = jnp.asarray(prompt, jnp.int32)
        b, s0 = prompt.shape
        if not 1 <= beam_size <= cfg.vocab_size:
            raise ValueError(f"beam_size must be in [1, vocab_size], "
                             f"got {beam_size}")
        if max_new_tokens < 1:
            return prompt, jnp.zeros((b,), jnp.float32)
        if s0 + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt({s0}) + max_new_tokens({max_new_tokens}) exceeds "
                f"max_len={cfg.max_len}")
        K = int(beam_size)
        memo = getattr(self, "_gen_fns", None)
        if memo is None:
            memo = self._gen_fns = {}
        memo_key = ("beam", b, s0, int(max_new_tokens), K, eos_id,
                    float(length_penalty))
        if memo_key in memo:
            return memo[memo_key](params, prompt)

        @jax.jit
        def run(params, prompt):
            cache = self.init_cache(
                b, cache_len=s0 + max_new_tokens)
            logits, cache = self.apply_with_cache(params, prompt, cache, 0)
            logp0 = jax.nn.log_softmax(logits[:, -1], axis=-1)   # (B, V)
            V = logp0.shape[-1]
            scores, tok0 = lax.top_k(logp0, K)                   # (B, K)
            # tile the prompt-filled cache across beams: (B*K, H, L, Dh)
            cache = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, K, axis=0), cache)
            tok = tok0.reshape(b * K).astype(jnp.int32)
            alive = (tok0 != eos_id) if eos_id is not None else None
            lengths = jnp.ones((b, K), jnp.float32)   # tok0 counts as 1

            def step(carry, i):
                tok, scores, cache, alive, lengths = carry
                # `tok` occupies position s0+i: write it there, then score
                # position s0+i+1 candidates
                lg, cache = self.apply_with_cache(
                    params, tok[:, None], cache, s0 + i)
                logp = jax.nn.log_softmax(lg[:, 0], axis=-1)     # (B*K, V)
                logp = logp.reshape(b, K, V)
                if alive is not None:
                    # finished beams: only "emit eos again at score 0"
                    frozen = jnp.full((V,), -jnp.inf
                                      ).at[eos_id].set(0.0)
                    logp = jnp.where(alive[..., None], logp,
                                     frozen[None, None, :])
                total = scores[..., None] + logp                 # (B,K,V)
                flat_scores, flat_idx = lax.top_k(
                    total.reshape(b, K * V), K)                  # (B, K)
                src_beam = flat_idx // V                         # (B, K)
                new_tok = (flat_idx % V).astype(jnp.int32)
                # reindex caches and alive to the surviving beams
                gather_rows = (jnp.arange(b)[:, None] * K
                               + src_beam).reshape(b * K)
                cache = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, gather_rows, axis=0), cache)
                lengths = jnp.take_along_axis(lengths, src_beam, axis=1)
                if alive is not None:
                    parent_alive = jnp.take_along_axis(alive, src_beam,
                                                       axis=1)
                    # frozen beams' repeated eos does not count as length
                    lengths = lengths + parent_alive.astype(jnp.float32)
                    alive = parent_alive & (new_tok != eos_id)
                else:
                    lengths = lengths + 1.0
                tok = new_tok.reshape(b * K)
                return ((tok, flat_scores, cache, alive, lengths),
                        (new_tok, src_beam))

            carry = (tok, scores, cache, alive, lengths)
            carry, (toks, srcs) = lax.scan(
                step, carry, jnp.arange(max_new_tokens - 1))
            _, scores, _, _, lengths = carry
            # backtrack: follow src_beam pointers from the best final beam
            norm = scores
            if length_penalty:
                norm = scores / (jnp.maximum(lengths, 1.0)
                                 ** length_penalty)
            best = jnp.argmax(norm, axis=-1)                     # (B,)

            def backtrack(beam, toks, srcs):
                # toks/srcs: (steps, B, K); walk backwards per batch row
                def back(carry, sr_tk):
                    beam = carry
                    sr, tk = sr_tk
                    t = jnp.take_along_axis(tk, beam[:, None],
                                            axis=1)[:, 0]
                    beam = jnp.take_along_axis(sr, beam[:, None],
                                               axis=1)[:, 0]
                    return beam, t

                beam, rev = lax.scan(back, beam, (srcs, toks),
                                     reverse=True)
                return beam, rev

            first_beam, rev = backtrack(best, toks, srcs)
            first_tok = jnp.take_along_axis(tok0, first_beam[:, None],
                                            axis=1)
            seq = jnp.concatenate(
                [prompt, first_tok, jnp.moveaxis(rev, 0, 1)], axis=1)
            best_score = jnp.take_along_axis(norm, best[:, None],
                                             axis=1)[:, 0]
            return seq, best_score

        memo[memo_key] = run
        if len(memo) > 8:
            memo.pop(next(iter(memo)))
        return run(params, prompt)

    # ------------------------------------------------------------------ #
    def param_pspecs(self, params):
        """PartitionSpec pytree matching ``params``; modules declare their
        tp layout via ``pspec``, everything else is replicated (the fsdp
        dimension is layered on top by parallel/spmd.py)."""
        specs = {}
        by_name = {m.name: m for m in self.modules()}
        for mod_name, sub in params.items():
            mod = by_name.get(mod_name)
            ps = getattr(mod, "pspec", {}) if mod is not None else {}
            specs[mod_name] = {k: ps.get(k, P()) for k in sub}
        return specs


def chunked_token_nll(head_fn, h, targets, loss_chunk,
                      ignore_index: int = -1):
    """(total masked NLL, valid count) with the vocab projection done per
    sequence chunk under ``jax.checkpoint`` inside a ``lax.scan``.

    ``head_fn(h_chunk) -> logits_chunk`` closes over the head params;
    their gradient contributions accumulate through the scan transpose.
    Peak logits memory is (B, loss_chunk, V).  Shared by
    :meth:`TransformerLM.token_nll` and the pipeline trainer."""
    B, S, D = h.shape
    # a chunk larger than the sequence would PAD UP and materialize more
    # logits than the unchunked path — clamp, never grow
    loss_chunk = min(loss_chunk, S)
    if S % loss_chunk:
        # ragged tail (e.g. an odd-length eval batch): pad h with zeros
        # and the targets with ignore_index so the tail contributes
        # nothing, instead of crashing mid-evaluate
        pad = loss_chunk - (S % loss_chunk)
        h = jnp.concatenate(
            [h, jnp.zeros((B, pad, D), h.dtype)], axis=1)
        targets = jnp.concatenate(
            [targets,
             jnp.full((B, pad), ignore_index, targets.dtype)], axis=1)
        S = S + pad
    n = S // loss_chunk
    hc = jnp.moveaxis(h.reshape(B, n, loss_chunk, D), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n, loss_chunk), 1, 0)

    @jax.checkpoint
    def chunk_nll(h_c, t_c):
        logits = head_fn(h_c).astype(jnp.float32)
        return lm_token_nll(logits, t_c, ignore_index)

    def body(carry, xs):
        tot, cnt = chunk_nll(*xs)
        return (carry[0] + tot, carry[1] + cnt), None

    (tot, cnt), _ = lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), (hc, tc))
    return tot, cnt


def lm_token_nll(logits, targets, ignore_index: int = -1):
    """(sum of masked token NLLs, valid-token count) — the shared core of
    training and evaluation losses."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.clip(targets, 0, logits.shape[-1] - 1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (targets != ignore_index).astype(jnp.float32)
    return (nll * mask).sum(), mask.sum()


def lm_cross_entropy(logits, targets, ignore_index: int = -1):
    """Mean token cross-entropy. logits (B, S, V) fp32, targets (B, S) int."""
    total, count = lm_token_nll(logits, targets, ignore_index)
    return total / jnp.maximum(count, 1.0)


PRESETS = {
    "tiny": dict(vocab_size=256, d_model=128, n_heads=2, n_layers=2,
                 d_ff=256, max_len=256),
    "base": dict(vocab_size=32000, d_model=768, n_heads=6, n_layers=12,
                 d_ff=3072, max_len=2048),  # head_dim 128 = one MXU tile
    "long8k": dict(vocab_size=32000, d_model=1024, n_heads=8, n_layers=16,
                   d_ff=4096, max_len=8192, remat=True,
                   use_ring_attention=True, dtype="bfloat16"),
}


def build(preset: str = "base", **overrides) -> TransformerLM:
    cfg = TransformerConfig(**{**PRESETS[preset], **overrides})
    return TransformerLM(cfg)
