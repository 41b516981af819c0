"""Continuous-batching decode engine: token streaming over a paged KV
cache.

The PR-2 :class:`~bigdl_tpu.serving.ServingEngine` batches *fixed-shape*
forward passes — the right contract for classification, the wrong one
for token streaming, where a request's cost is per generated token and
a static batch idles every chip on its slowest member.  This engine
decodes at **slot** granularity instead:

    submit(prompt)                      client threads
       └─ bounded waiting queue         shed at the door when full
            └─ decode loop (one thread, owns the device pool)
                 admit  → free slot + pages → PREFILL (bucketed prompt
                          length through the PR-2 BucketLadder: one
                          AOT-compiled program per bucket, so a mixed
                          prompt stream compiles NOTHING post-warmup)
                 step   → ONE jitted fixed-shape program advances every
                          live slot by one token (per-slot positions,
                          page-table scatter, then attention over the
                          live pages — kvcache.py ``attend``: a Pallas
                          kernel on a TPU, a gathered window elsewhere)
                 retire → eos / max_new / deadline: free the slot's
                          pages, complete the future, recycle the slot
                 evict  → a slot that cannot grow a page when the pool
                          saturates evicts the YOUNGEST other admission
                          (never an older one — the oldest request
                          always completes, which is what makes the
                          dance livelock-free); the victim re-queues
                          and on readmission RE-PREFILLS its prompt
                          then REPLAYS its recorded tokens through the
                          decode program (same programs, same inputs →
                          the rebuilt KV is bitwise the evicted one,
                          so greedy decode continues exactly)

Slot membership changes every step, shapes never do: dead slots ride
along as masked rows (page-table ``-1`` = no page read, attention of
zeros / scatter drops), so join/leave churn is data, not a recompile.
Decode throughput scales with slot occupancy, not with the slowest
request in a static batch — ``scripts/decode_smoke.py`` pins the
ordering (≥ 1.5× as a CPU timing) and zero post-warmup recompiles
under churn; device numbers are ``chip_smoke.py``'s serve stage and
PERF.md section 5.

Per-token SLO accounting (families in docs/observability.md):
``decode/ttft_ms`` (submit → first token) and ``decode/intertoken_ms``
histograms, ``decode/*`` counters, ``kv/*`` pool gauges, and a
per-request PR-5 trace (admit → queue → prefill → one ``token`` span
per decode batch) in the same bounded :class:`TraceRing` /trace serves.
Shed requests finish their trace with a terminal cause span *before*
their future fails — the ServingEngine contract, kept on the decode
path too.

The engine speaks the ServingEngine replica protocol (``submit`` /
``predict`` / ``warmup`` / ``shutdown`` / ``pending_rows`` /
``max_queue_fill`` / ``stats`` / ``registry`` / ``recorder``), so a
:class:`~bigdl_tpu.serving.ReplicaSet` fronts decode replicas
unchanged — health scoring reads the per-token ``serving.rows``
progress, wedge ejection and failover re-decode on a peer, and
:class:`~bigdl_tpu.serving.CanaryPublisher` golden-DECODE-validates
weight publications (bit-identical rollback included).  Pair with
:class:`~bigdl_tpu.serving.stream.WeightStreamPublisher` for live
train→serve weight streaming.

Fault site: ``serving.decode_step`` fires ahead of every decode-step
dispatch (``delay`` = a wedged decode step — what the chaos leg arms;
``err`` = the step fails, live requests complete exceptionally and a
ReplicaSet fails them over).
"""
from __future__ import annotations

import queue as queue_mod
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as faultplane
from ..nn.module import Ctx
from ..observability import Recorder
from .buckets import BucketLadder
from .kvcache import PagedKVCache
from .queue import (EngineClosedError, EngineIntrospection,
                    LoadShedError)
from .registry import ModelRegistry

_END = object()


class DecodeStream:
    """One streaming decode: iterate :meth:`tokens` as they are emitted
    (ints), or wait for :attr:`future` — the full ``prompt + generated``
    int32 array.  A shed/failed request raises from both."""

    def __init__(self):
        self.future: Future = Future()
        self._q: "queue_mod.Queue" = queue_mod.Queue()

    def tokens(self):
        while True:
            t = self._q.get()
            if t is _END:
                # the future resolves before the end marker lands, so a
                # shed/failed request raises HERE too — a truncated
                # stream must never look like a short success
                exc = self.future.exception() if self.future.done() \
                    else None
                if exc is not None:
                    raise exc
                return
            yield t

    def result(self, timeout: Optional[float] = None):
        return self.future.result(timeout)


class _DecodeRequest:
    """One request across its whole lifecycle (including evictions)."""

    __slots__ = ("prompt", "max_new", "temperature", "eos_id", "deadline",
                 "arrival", "stream", "generated", "trace", "slot",
                 "first_token_at", "last_token_at", "evictions",
                 "replay_i", "prefilled")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 temperature: float, eos_id: Optional[int],
                 deadline: Optional[float], stream: DecodeStream,
                 trace=None):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.deadline = deadline     # absolute monotonic seconds or None
        self.arrival = time.monotonic()
        self.stream = stream
        self.generated: List[int] = []
        self.trace = trace
        self.slot: Optional[int] = None
        self.first_token_at: Optional[float] = None
        self.last_token_at: Optional[float] = None
        self.evictions = 0
        # readmission replay cursor: > 0 while the slot is re-feeding
        # its recorded tokens through the decode program to rebuild the
        # evicted KV bitwise (see DecodeEngine._prefill)
        self.replay_i = 0
        # chunked prefill: how many of the prompt's tokens are cached
        # while its chunks still run (the slot is held, and takes no
        # part in decode steps); None before and after
        self.prefilled: Optional[int] = None

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline


class DecodeEngine(EngineIntrospection):
    """Slot-based continuous-batching decode over one TransformerLM.

    ``registry`` / ``model_name``  the served entry; its module must be
                    a :class:`~bigdl_tpu.models.transformer.TransformerLM`
                    (``apply_with_cache`` prefill + ``decode_tokens``).
                    Weight hot-swap goes through the registry
                    (``swap_weights`` / CanaryPublisher) — the decode
                    loop picks up a new snapshot at the next step.
    ``slots``       concurrent sequences in the step batch
    ``page_size`` / ``pool_pages``  paged-KV geometry (kvcache.py);
                    ``pool_pages`` defaults to ``slots * max_context /
                    page_size`` (no eviction pressure); smaller pools
                    evict.  A model with sliding-window layers
                    (``cfg.windows``) has a pool a KIND of layer:
                    ``pool_pages`` is then ``{"global": n, "window": n}``
                    (a kind left out: every slot's whole table, which for
                    a window layer is a ring of ``window / page_size +
                    prefill_chunk / page_size + 1`` pages)
    ``max_context`` longest prompt+generation a slot may hold
    ``max_prompt``  admission cap on client prompt length
                    (readmissions may re-prefill up to max_context)
    ``prefill_chunk``  prompt tokens one prefill program takes of a long
                    prompt.  Where ``max_prompt`` is at most this, every
                    prompt is prefilled whole, in the tick that admits
                    it, through the bucket ladder.  Where it is more,
                    prompts go through ONE chunk program, a chunk a tick
                    between decode steps, each chunk attending the
                    slot's own pages (so the gap a prefill puts between
                    two tokens of the live slots is one chunk's, however
                    long the prompt); a multiple of ``page_size``.  A
                    model with sliding-window layers takes EVERY prompt
                    in chunks: a whole-prompt prefill would have to fold
                    its rows into a ring narrower than the prompt
    ``max_new_tokens``  default generation budget per request
    ``max_waiting`` waiting-queue bound, in requests — beyond it
                    submit sheds with :class:`LoadShedError`
                    (pool-exhaustion backpressure reaches the client
                    as queue growth, then as sheds)
    ``int8_kv``     store KV pages int8 with per-channel scales
                    (attends by the gathered window, not the kernel)
    ``eos_id``      default stop token (None = run to max_new)
    ``seed``        sampling RNG seed (temperature > 0 requests)
    """

    #: a "row" here is one token of a SEQUENCE: ReplicaSet.predict must
    #: submit prompts whole, never slice them into batch chunks
    row_splittable = False

    def __init__(self, registry: ModelRegistry, model_name: str = "lm", *,
                 slots: int = 8, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 max_context: Optional[int] = None,
                 max_prompt: Optional[int] = None,
                 prefill_chunk: int = 512,
                 max_new_tokens: int = 32, max_waiting: int = 64,
                 int8_kv: bool = False, kv_dtype=None,
                 eos_id: Optional[int] = None, seed: int = 0,
                 recorder: Optional[Recorder] = None,
                 trace_requests: bool = True, trace_capacity: int = 512,
                 report_every: int = 32):
        from ..observability.profile import TraceRing
        self.registry = registry
        self.model_name = model_name
        entry = registry.get(model_name)
        model = entry.model
        if not hasattr(model, "apply_with_cache") \
                or not hasattr(model, "decode_tokens"):
            raise TypeError(
                f"DecodeEngine serves TransformerLM-style models with "
                f"apply_with_cache/decode_tokens; got "
                f"{type(model).__name__}")
        self.model = model
        cfg = model.cfg
        self.slots = int(slots)
        self.max_context = int(cfg.max_len if max_context is None
                               else max_context)
        if not 1 < self.max_context <= cfg.max_len:
            raise ValueError(f"max_context {self.max_context} must be in "
                             f"(1, max_len={cfg.max_len}]")
        self.max_prompt = int(self.max_context - 1 if max_prompt is None
                              else max_prompt)
        if not 0 < self.max_prompt < self.max_context:
            raise ValueError(f"max_prompt {self.max_prompt} must be in "
                             f"(0, max_context={self.max_context})")
        self.max_new_tokens = int(max_new_tokens)
        self.max_waiting = int(max_waiting)
        self.eos_id = eos_id
        self.recorder = recorder if recorder is not None else Recorder()
        if self.recorder.enabled and self.recorder.get_ledger() is None:
            # goodput attribution: the decode loop folds every elapsed
            # interval by slot occupancy (goodput/queue_wait/idle), so
            # the engine owns its device (1 until multi-device decode)
            from ..observability.goodput import GoodputLedger
            self.recorder.set_ledger(GoodputLedger(
                name=f"decode:{model_name}", devices=1))
        self.trace_ring = TraceRing(trace_capacity) if trace_requests \
            else None
        self.report_every = int(report_every)
        # prefill buckets only ever see client prompts: a readmission
        # re-prefills its PROMPT and replays the generated tail through
        # the decode program, so the ladder tops out at max_prompt —
        # compiling buckets up to max_context would burn minutes of
        # warmup on programs nothing can reach
        # a prompt past the chunk goes through the one chunk program, and
        # then so does every prompt: the ladder is empty
        self.prefill_chunk = int(prefill_chunk)
        windows = [cfg.layer_window(i) for i in range(cfg.n_layers)]
        # (latent rows go into pages by the chunk program alone)
        self.chunked = self.max_prompt > self.prefill_chunk \
            or any(windows) or bool(cfg.kv_lora_rank)
        if self.chunked and self.prefill_chunk % page_size:
            raise ValueError(f"prefill_chunk {prefill_chunk} must be a "
                             f"multiple of page_size {page_size}")
        self.ladder = () if self.chunked else BucketLadder(self.max_prompt)
        # the table a chunk program takes: the pages of the longest prompt
        self._chunk_pages = -(-self.max_prompt // self.prefill_chunk) \
            * self.prefill_chunk // page_size
        self.kv = PagedKVCache(
            [blk.attn.name for blk in model.blocks], **model.kv_geometry(),
            n_pages=pool_pages, page_size=page_size, n_slots=self.slots,
            max_context=self.max_context,
            dtype=kv_dtype or jnp.dtype(cfg.dtype), int8=int8_kv,
            windows=windows if any(windows) else None,
            ring_slack=self.prefill_chunk, recorder=self.recorder)
        self.recorder.gauge("kv/index_bytes", self.kv.index_bytes())
        if self.kv.latent_rank:
            self.recorder.gauge("kv/latent_row_bytes",
                                self.kv.latent_row_bytes())
            self.recorder.gauge("kv/latent_bytes", self.kv.latent_bytes())
        self._base_key = jax.random.PRNGKey(int(seed))
        self._pool = self.kv.init_pool()
        self._pool_avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._pool)
        # slot state — mutated only by the decode thread
        self._lengths = np.zeros(self.slots, np.int32)
        self._last_tokens = np.zeros(self.slots, np.int32)
        self._admitted_at = np.zeros(self.slots, np.float64)
        self._live: Dict[int, _DecodeRequest] = {}
        self._steps = 0
        self._cached_snap = None
        self._cached_params = None
        # shared state — every read/write under self._lock (a Condition)
        self._lock = threading.Condition()
        self._waiting: List[_DecodeRequest] = []
        self._programs: Dict[Any, Any] = {}
        # what the layers of each program count (Ctx.count), in the order
        # of the vector the program returns beside its tokens
        self._count_names: Dict[str, List[str]] = {}
        self._warmed = False
        self._closed = False
        self._drain = True
        self._thread: Optional[threading.Thread] = None
        self._http_server = None

    # -- lifecycle -------------------------------------------------------- #
    def warmup(self, name: Optional[str] = None):
        """AOT-compile every prefill bucket plus the decode step — the
        zero-recompile line in the sand: compiles here count
        ``decode/warmup_compiles``, any compile after it counts
        ``decode/recompiles`` (and on a TPU, a blown token SLO)."""
        if name is not None and name != self.model_name:
            raise KeyError(f"DecodeEngine serves {self.model_name!r}, "
                           f"not {name!r}")
        from ..observability.goodput import ledger_phase
        with self.recorder.span("decode.warmup"), \
                ledger_phase(self.recorder, "compile_warmup"):
            for bucket in self.ladder:
                self._program("prefill", bucket)
            if self.chunked:
                self._program("chunk")
            self._program("decode")
        with self._lock:
            self._warmed = True
        return self

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop admissions; ``drain=True`` finishes live + queued work,
        ``drain=False`` fails it fast with :class:`EngineClosedError`."""
        with self._lock:
            self._closed = True
            self._drain = bool(drain)
            t = self._thread
            server, self._http_server = self._http_server, None
            self._lock.notify_all()
        if server is not None:
            server.stop()
        if t is not None:
            t.join(timeout)
        return self

    def telemetry_sources(self):
        """``[(model_name, recorder)]`` — the aggregator attachment
        hook (``aggregator.add(engine)`` scrapes the ``decode/*`` +
        ``kv/*`` SLO families)."""
        return [(self.model_name, self.recorder)]

    # -- request path ----------------------------------------------------- #
    def submit(self, name: str, x, deadline_ms: Optional[float] = None,
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               eos_id: Optional[int] = None, trace_ctx=None) -> Future:
        """Enqueue one prompt; returns the Future of the full
        ``prompt + generated`` int32 array.  ``deadline_ms`` sheds the
        request when it expires before OR during decode (terminal
        ``deadline`` trace span, then the future fails).  ``trace_ctx``
        threads an upstream
        :class:`~bigdl_tpu.observability.context.TraceContext` into the
        slot-lifetime trace, so one trace id covers admission through
        every per-token step."""
        return self.stream(name, x, deadline_ms=deadline_ms,
                           max_new_tokens=max_new_tokens,
                           temperature=temperature, eos_id=eos_id,
                           trace_ctx=trace_ctx).future

    def stream(self, name: str, x, deadline_ms: Optional[float] = None,
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               eos_id: Optional[int] = None,
               trace_ctx=None) -> DecodeStream:
        """Like :meth:`submit` but returns the :class:`DecodeStream`,
        whose :meth:`~DecodeStream.tokens` iterator yields tokens as
        the decode loop emits them."""
        t_admit = time.monotonic()
        if name != self.model_name:
            raise KeyError(f"DecodeEngine serves {self.model_name!r}, "
                           f"not {name!r}")
        prompt = np.asarray(x, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size > self.max_prompt:
            raise ValueError(f"prompt length {prompt.size} exceeds "
                             f"max_prompt {self.max_prompt}")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new > self.max_context:
            raise ValueError(
                f"prompt({prompt.size}) + max_new({max_new}) exceeds "
                f"max_context {self.max_context}")
        if not self.kv.fits_pool(prompt.size + max_new):
            # a request the whole pool cannot hold would self-evict
            # forever once it ran alone — reject loudly at the door
            raise ValueError(
                f"request needs {self.kv.pages_for(prompt.size + max_new)}"
                f" pages at full length, pool has {self.kv.n_pages}; "
                "grow pool_pages or shrink max_new_tokens")
        rec = self.recorder
        rec.inc("decode/requests")
        rec.inc("serving.requests")
        ring = self.trace_ring
        tr = ring.new_trace(self.model_name, ctx=trace_ctx) \
            if ring is not None else None
        if tr is not None:
            tr.meta.update(prompt_len=int(prompt.size), max_new=max_new)
        deadline = None if deadline_ms is None \
            else t_admit + float(deadline_ms) / 1e3
        stream = DecodeStream()
        req = _DecodeRequest(prompt, max_new, temperature,
                             eos_id if eos_id is not None else self.eos_id,
                             deadline, stream, trace=tr)
        if tr is not None:
            now = time.monotonic()
            tr.add_span("admit", t_admit, now)
            tr.open("queue", now)
        with self._lock:
            if self._closed:
                if tr is not None:
                    tr.discard("queue")
                    tr.terminal("engine_closed", time.monotonic(),
                                name="closed")
                    ring.finish(tr)
                raise EngineClosedError("decode engine is shut down")
            if len(self._waiting) >= self.max_waiting:
                rec.inc("decode/shed_queue_full")
                if tr is not None:
                    tr.discard("queue")
                    tr.terminal("queue_full", time.monotonic())
                    ring.finish(tr)
                raise LoadShedError(
                    "queue_full",
                    f"{len(self._waiting)} requests waiting, cap "
                    f"{self.max_waiting}")
            self._waiting.append(req)
            self._ensure_loop_locked()
            self._lock.notify_all()
            depth = len(self._waiting)
        rec.gauge("decode/queue_depth", depth)
        return stream

    def predict(self, name: str, x, timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None, **kw):
        """Synchronous decode (the CanaryPublisher golden-decode path):
        greedy by default, deterministic, so two predictions from the
        same snapshot are bitwise equal."""
        return self.submit(name, x, deadline_ms=deadline_ms,
                           **kw).result(timeout)

    # -- replica-protocol introspection ------------------------------------ #
    def pending_rows(self) -> int:
        """Outstanding work in tokens: queued prompts + generation
        budgets, plus what live slots still owe.  Zero means fully
        idle — the canary quiesce gate."""
        with self._lock:
            waiting = list(self._waiting)
            live = list(self._live.values())
        n = sum(int(r.prompt.size) + r.max_new for r in waiting)
        n += sum(max(r.max_new - len(r.generated), 1) for r in live)
        return n

    def max_queue_fill(self) -> float:
        with self._lock:
            return len(self._waiting) / self.max_waiting

    def stats(self) -> Dict[str, Any]:
        rec = self.recorder
        out = {k: rec.counter_value(f"decode/{k}")
               for k in ("requests", "prefills", "prefill_chunks",
                         "readmissions", "steps",
                         "tokens", "finished", "shed_queue_full",
                         "shed_deadline", "recompiles", "warmup_compiles",
                         "errors")}
        steps = max(out["steps"], 1.0)
        out["occupancy"] = out["tokens"] / (steps * self.slots)
        out["kv_pool_fill"] = self.kv.fill()
        out["kv_peak_fill"] = rec.gauge_value("kv/peak_fill")
        out["evictions"] = rec.counter_value("kv/evictions")
        # what the steps' attention had to read of what a gathered
        # window holds: the live pages' share
        out["kv_pages_read_share"] = rec.counter_value("kv/pages_read") \
            / max(rec.counter_value("kv/pages_window"), 1.0)
        out["attn_route"] = self.kv.attention_path()[0]
        # which kinds of layer the cache holds, and each kind's table
        out["kv_kinds"] = {k.name: k.describe() for k in self.kv.kinds}
        if self.kv.latent_rank:
            # ... and what a row of it is, where it is not K and V
            for kind in out["kv_kinds"].values():
                kind["content"] = "latent"
        # a prompt's chunks (None: this engine takes prompts whole)
        out["chunk_attn_route"] = self.chunk_attention_path()[0]
        # the sparse route: rows the steps' attention read of the rows
        # that were live (0 over 0 for a model with no indexer)
        out["kv_rows_attended_share"] = \
            rec.counter_value("sparse/rows_attended") \
            / max(rec.counter_value("sparse/rows_live"), 1.0)
        for h, label in (("decode/ttft_ms", "ttft"),
                         ("decode/intertoken_ms", "intertoken")):
            q = rec.hist_quantiles(h, (50.0, 99.0))
            if q:
                out[f"{label}_p50_ms"] = q.get("p50")
                out[f"{label}_p99_ms"] = q.get("p99")
        return out

    def chunk_attention_path(self) -> Tuple[Optional[str], str]:
        """``(route, why)`` of a prompt chunk's attention
        (:meth:`PagedKVCache.chunk_attention_path` over this engine's
        chunk and the longest prompt's table); ``(None, why)`` for an
        engine that takes its prompts whole."""
        if not self.chunked:
            return None, (f"max_prompt {self.max_prompt} fits one prefill: "
                          "no chunk program")
        # (a window layer's ring takes the route its filled-up width
        # gives: kv.chunk_attention_path(..., layer=name))
        return self.kv.chunk_attention_path(self.prefill_chunk,
                                            self._chunk_pages)

    # -- program cache ----------------------------------------------------- #
    def _program(self, kind: str, bucket: Optional[int] = None):
        key = (kind, bucket)
        with self._lock:
            prog = self._programs.get(key)
            warmed = self._warmed
        if prog is not None:
            return prog
        if warmed:
            # post-warmup compile: the token-SLO violation the bucket
            # ladder exists to prevent — counted, never silent
            self.recorder.inc("decode/recompiles")
        from ..observability.goodput import ledger_phase
        with ledger_phase(self.recorder, "compile_warmup"):
            prog = self._compile(kind, bucket)
        with self._lock:
            self._programs[key] = prog
        return prog

    def _aval_params(self):
        snap = self.registry.get(self.model_name).snapshot
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                           getattr(a, "dtype", None)
                                           or np.asarray(a).dtype),
            snap.params)

    def _compile(self, kind: str, bucket: Optional[int]):
        """AOT jit → lower → compile (at avals, so no buffers move and
        nothing is donated at build time).  A trace or compile failure
        propagates: warmup must not report success over a program that
        does not build."""
        model, kv = self.model, self.kv
        base_key = self._base_key
        if kind == "decode":
            # which way the step's attention goes (kvcache.attend picks
            # it while tracing): 0 = gather, 1 = pallas, 2 = sparse
            self.recorder.gauge("decode/attn_route", float(
                _ATTN_ROUTES.index(kv.attention_path()[0])))
            # and a prompt's chunks (kvcache.attend_chunk): 0 = window,
            # 1 = pallas
            if self.chunked:
                self.recorder.gauge("decode/chunk_attn_route", float(
                    _CHUNK_ATTN_ROUTES.index(
                        self.chunk_attention_path()[0])))

            def fn(params, pool, tokens, lengths, tables, temps, step):
                new_pool = dict(pool)
                ctx = Ctx(state={}, training=False, rng_key=None)
                live = kv.table_of(tables)[:, 0] >= 0
                ctx.token_mask = live

                def latent_io(name, q, rows):
                    # a latent layer: the token's one row in, the absorbed
                    # query's `softmax . c` a head out
                    # (attended from the pool as it came in, beside the
                    # token's own row, and then written: the same result,
                    # and the pool is read in the layout it lies in)
                    o = kv.attend(pool[name], tables, lengths, q, rows=rows)
                    new_pool[name] = kv.write_token(pool[name], tables,
                                                    lengths, rows)
                    ctx.count("mla/rows_live",
                              jnp.where(live, lengths + 1, 0).sum())
                    return o

                def kv_io(name, q, k_new, v_new, index=None):
                    # each layer its kind's table (the one table of a
                    # model of one kind of layer)
                    tab = kv.table_of(tables, name)
                    if index is None:
                        new_pool[name] = kv.write_token(
                            new_pool[name], tab, lengths, k_new, v_new,
                            layer=name)
                        if kv.windowed:
                            # the rows the step's queries may see, by kind
                            rows = jnp.where(live, lengths + 1, 0)
                            window = kv.kind_of(name).window
                            ctx.count("attn/rows_live", rows.sum())
                            ctx.count(
                                "attn/rows_attended_window" if window
                                else "attn/rows_attended_global",
                                (jnp.minimum(rows, window) if window
                                 else rows).sum())
                        return kv.attend(new_pool[name], tab, lengths, q,
                                         layer=name)
                    qi, ki, w = index
                    new_pool[name] = kv.write_token(
                        new_pool[name], tab, lengths, k_new, v_new,
                        ki[:, 0])
                    rows = jnp.where(live, lengths + 1, 0)
                    ctx.count("sparse/rows_live", rows.sum())
                    ctx.count("sparse/rows_scored", rows.sum())
                    ctx.count("sparse/rows_attended",
                              jnp.minimum(rows, kv.index_top_k).sum())
                    return kv.attend(new_pool[name], tab, lengths, q,
                                     (qi[:, 0], w[:, 0]))

                logits = model.decode_tokens(
                    params, tokens, lengths,
                    latent_io if kv.latent_rank else kv_io, ctx)
                tok = _select_tokens(logits, temps, step, base_key)
                # poisoned-weights sentinel: argmax of NaN logits is a
                # VALID token id, so without this a poisoned publish
                # would stream plausible garbage; per-slot flags let
                # the engine fail exactly the affected requests (and a
                # canary golden-decode reject the publication)
                bad = ~jnp.isfinite(logits).all(axis=-1)
                return (tok, bad, new_pool) + self._counts(kind, ctx)

            args = (self._aval_params(), self._pool_avals,
                    jax.ShapeDtypeStruct((self.slots,), jnp.int32),
                    jax.ShapeDtypeStruct((self.slots,), jnp.int32),
                    kv.pack([jax.ShapeDtypeStruct(
                        (self.slots, k.width), jnp.int32)
                        for k in kv.kinds]),
                    jax.ShapeDtypeStruct((self.slots,), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "chunk":
            chunk, n_pages = self.prefill_chunk, self._chunk_pages

            def prefill_chunk(params, pool, tokens, start, n_valid,
                              table, temp, step):
                new_pool = dict(pool)
                ctx = Ctx(state={}, training=False, rng_key=None)
                # the chunk's own pages, of each kind of table
                pages = {k.name: kv.chunk_pages(
                    kv.table_of(table, k.layers[0]), start, chunk,
                    k.layers[0]) for k in kv.kinds}

                def latent_io(name, q, rows, up):
                    # a latent layer: the chunk's rows in, its un-absorbed
                    # queries and the up-projection over to the cache
                    new_pool[name] = kv.write_chunk(new_pool[name],
                                                    pages[kv.kinds[0].name],
                                                    rows)
                    # keys the chunk's valid queries may see, and the
                    # slot's rows up to the chunk's end
                    ctx.count("mla/chunk_rows_visible",
                              n_valid * start + n_valid * (n_valid + 1) // 2)
                    ctx.count("mla/chunk_rows_live", start + n_valid)
                    return kv.attend_chunk(new_pool[name], table, start, q,
                                           up=up)

                def kv_io(name, q, k, v, index=None):
                    qi, ki, w = index or (None, None, None)
                    new_pool[name] = kv.write_chunk(
                        new_pool[name], pages[kv.kind_of(name).name], k, v,
                        ki)
                    return kv.attend_chunk(
                        new_pool[name], kv.table_of(table, name), start, q,
                        None if index is None else (qi, w), layer=name)

                last = model.prefill_chunk(
                    params, tokens, start, n_valid,
                    latent_io if kv.latent_rank else kv_io, ctx)
                tok = _select_tokens(last[None, :], temp[None], step,
                                     base_key)[0]
                bad = ~jnp.isfinite(last).all()
                return (tok, bad, new_pool) + self._counts(kind, ctx)

            # (a name without `fn`: a reader of the device trace tells the
            # decode step as the most frequent module with `fn` in it, and
            # a window that opens on a long prompt runs more chunks)
            fn = prefill_chunk
            args = (self._aval_params(), self._pool_avals,
                    jax.ShapeDtypeStruct((1, chunk), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    kv.pack([jax.ShapeDtypeStruct(
                        (k.width if k.ring else n_pages,), jnp.int32)
                        for k in kv.kinds]),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32))
        else:
            n_pages = -(-bucket // kv.page_size)
            cache_dtype = kv.dtype if not kv.int8 \
                else jnp.dtype(model.cfg.dtype)

            def fn(params, pool, tokens, true_len, table, temp, step):
                cache = model.init_cache(1, dtype=cache_dtype,
                                         cache_len=bucket)
                logits, cache = model.apply_with_cache(
                    params, tokens, cache, 0)
                new_pool = dict(pool)
                for name in kv.layer_names:
                    new_pool[name] = kv.write_prefill(
                        new_pool[name], table, cache[name]["k"],
                        cache[name]["v"], cache[name].get("ki"))
                last = jnp.take(logits[0], true_len - 1, axis=0)
                tok = _select_tokens(last[None, :], temp[None], step,
                                     base_key)[0]
                bad = ~jnp.isfinite(last).all()
                return tok, bad, new_pool

            args = (self._aval_params(), self._pool_avals,
                    jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((n_pages,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32))
        with self.recorder.span("decode.compile"):
            prog = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        if not self._warmed:
            self.recorder.inc("decode/warmup_compiles")
        return prog

    def _counts(self, kind, ctx):
        """What a program's layers counted (``Ctx.count``), as the tail of
        the program's outputs: ONE float32 vector beside its tokens, so it
        comes to the host in the step's own sync, or nothing for a model
        that counts nothing (its programs are what they were).  The names
        go by program kind, noted while tracing."""
        self._count_names[kind] = sorted(ctx.counters)
        return (jnp.stack([jnp.asarray(ctx.counters[k], jnp.float32)
                           for k in self._count_names[kind]]),) \
            if ctx.counters else ()

    def _count_in(self, kind, counts, prefix=""):
        for name, value in zip(self._count_names[kind],
                               np.asarray(counts[0]) if counts else ()):
            head, _, leaf = name.partition("/")
            # (a name that says `chunk_` is a chunk's own already)
            self.recorder.inc(name if leaf.startswith("chunk_")
                              else f"{head}/{prefix}{leaf}", float(value))

    def _params_for_step(self, entry):
        """Device-placed params of the CURRENT snapshot, cached per
        snapshot object: a hot-swap/canary publish lands at the next
        step without re-placing every step."""
        snap = entry.snapshot
        if snap is not self._cached_snap:
            self._cached_params = jax.device_put(snap.params)
            self._cached_snap = snap
        return self._cached_params

    # -- decode loop ------------------------------------------------------- #
    def _ensure_loop_locked(self):
        if self._thread is None or not self._thread.is_alive():
            # the thread holds the engine weakly so a dropped engine is
            # collectable; _decode_loop fails stranded requests then
            t = threading.Thread(
                target=_decode_loop,
                args=(weakref.ref(self), self._lock, self._waiting,
                      self._live, self.trace_ring),
                daemon=True, name=f"decode-{self.model_name}")
            self._thread = t
            t.start()

    def _tick(self) -> bool:
        """One scheduling round; returns False when the loop should
        exit (closed and nothing left to do)."""
        with self._lock:
            has_work = bool(self._waiting) or bool(self._live)
            closed, drain = self._closed, self._drain
            if closed and not drain:
                stranded = list(self._waiting) + list(self._live.values())
                self._waiting[:] = []
                live_slots = list(self._live)
                self._live.clear()
            elif not has_work:
                if closed:
                    return False
                # zero the load gauges while parked: occupancy is only
                # written from live steps, so without this an idle
                # engine scrapes its LAST in-flight value forever — a
                # phantom load that wedges the autoscaler's
                # calm/scale-down detection (same reasoning as the
                # queue_depth gauge in _admit)
                self.recorder.gauge("decode/live_slots", 0)
                self.recorder.gauge("decode/occupancy", 0.0)
                led = self.recorder.get_ledger()
                if led is not None:
                    # parked time folds to the background phase (idle,
                    # or whatever a producer declared) instead of being
                    # smeared into the next step's occupancy split
                    led.note_step_begin()
                self._lock.wait(0.1)
                return True
        if closed and not drain:
            exc = EngineClosedError("engine shut down before this "
                                    "request finished")
            for slot in live_slots:
                self.kv.free_slot(slot)
            for req in stranded:
                self._finish(req, exc=exc, cause="closed")
            self.recorder.gauge("decode/queue_depth", 0)
            return False
        rec = self.recorder
        # the tick's timeline: `decode.tick` groups six leaves that cover
        # it in order (admit, schedule, stage, dispatch, sync, emit).  The
        # leaves go onto the profiler's timeline and the parent does not:
        # a gap in the device's work then lands on the phase that filled
        # it, not on a span that covers every gap of the tick
        with rec.span("decode.tick", annotate=False) as tick:
            step = None
            try:
                with rec.span("decode.admit"):
                    self._admit()
                step = self._step_live()
            except Exception as e:   # the decode loop must survive
                rec.inc("decode/errors")
                self._recover_pool(e)
            if step is None:
                tick.discard()       # only a tick that stepped is one
            else:
                tick.set(step=step)
        return True

    def _admit(self):
        """Move waiting requests into free slots (expired ones shed);
        each admission is one bucketed prefill.  On the chunked route a
        tick runs at most ONE chunk of ONE request, first come first
        served: the next chunk of the prompt that is part-way, else the
        first chunk of the queue's head."""
        if self.chunked:
            part = next((s for s, r in self._live.items()
                         if r.prefilled is not None), None)
            if part is not None:
                self._try_prefill(part, self._live[part])
                return
        while True:
            with self._lock:
                if not self._waiting:
                    return
                free = [s for s in range(self.slots)
                        if s not in self._live]
                if not free:
                    return
                req = self._waiting[0]
                now = time.monotonic()
                if req.expired(now):
                    self._waiting.pop(0)
                    shed = True
                else:
                    prompt = req.prompt
                    if not self.kv.can_fit(prompt.size):
                        # pool-exhaustion backpressure: admissions NEVER
                        # evict (an admission that evicts a live slot
                        # invites eviction ping-pong — the live set must
                        # shrink through completions, not grow through
                        # preemption); the request waits for pages, and
                        # sustained saturation surfaces to clients as
                        # queue growth, then queue_full sheds
                        return
                    self._waiting.pop(0)
                    shed = False
                # gauge tracks the queue as it DRAINS too, or an idle
                # engine scrapes a phantom backlog forever
                self.recorder.gauge("decode/queue_depth",
                                    len(self._waiting))
            if shed:
                self._shed_deadline(req, at="queue")
                continue
            slot = free[0]
            if not self.kv.alloc_for(slot, prompt.size):
                with self._lock:        # raced below can_fit: wait
                    self._waiting.insert(0, req)
                    depth = len(self._waiting)
                self.recorder.gauge("decode/queue_depth", depth)
                return
            self._try_prefill(slot, req)
            if self.chunked:
                return

    def _try_prefill(self, slot: int, req: _DecodeRequest):
        try:
            if self.chunked:
                self._prefill_chunk(slot, req)
            else:
                self._prefill(slot, req, req.prompt)
        except Exception as e:
            self.recorder.inc("decode/errors")
            self._live.pop(slot, None)
            self.kv.free_slot(slot)
            self._finish(req, exc=e)
            self._recover_pool(e)

    def _evict_for(self, needy_slot: int, n_tokens: int) -> bool:
        """Evict slots YOUNGER than ``needy_slot`` (most recent
        admission first) until it can hold ``n_tokens``; the victims
        re-queue and re-prefill + replay on readmission.  Returns False
        when no younger victim remains — the needy slot then yields
        itself.

        Why youngest-first and never anyone older: the oldest live
        admission must NEVER lose its pages, so it always runs to
        completion — a strictly-decreasing potential that makes the
        eviction dance livelock-free.  (The obvious opposite — evict
        the least-recently-admitted — deadlocks a tight pool: each
        fresh admission's first page growth steals the pages of a
        mid-replay victim, whose replay then restarts from zero,
        forever.  Measured: 8.7k evictions, zero completions.)"""
        while not self.kv.alloc_for(needy_slot, n_tokens):
            victims = [s for s in self._live
                       if s != needy_slot
                       and self._admitted_at[s]
                       > self._admitted_at[needy_slot]]
            if not victims:
                return False
            victim = max(victims, key=lambda s: self._admitted_at[s])
            self._evict(victim)
        return True

    def _evict(self, slot: int):
        req = self._live.pop(slot)
        self.kv.free_slot(slot, evict=True)
        req.slot = None
        req.prefilled = None     # part-way through its chunks: again
        req.evictions += 1
        if req.trace is not None:
            req.trace.meta["evictions"] = req.evictions
        with self._lock:
            self._waiting.append(req)
            depth = len(self._waiting)
        # the gauge must see evicted re-queues too: saturation is when
        # the runbook reads it
        self.recorder.gauge("decode/queue_depth", depth)

    def _prefill_begins(self, req: _DecodeRequest, t0: float):
        """The request leaves the queue: its trace's spans, and the
        `decode.queue` span under its own trace id, like its prefill's,
        so a reader can join the two.  Returns the trace id."""
        trace_id = None
        if req.trace is not None:
            req.trace.close("queue", t0)
            req.trace.open("prefill", t0)
            trace_id = req.trace.trace_id
        if not req.evictions:
            self.recorder.add_span("decode.queue", t0 - req.arrival,
                                   trace_id=trace_id)
        return trace_id

    def _prefill_chunk(self, slot: int, req: _DecodeRequest):
        """One chunk of ``req``'s prompt in ``slot``.  From its first
        chunk the request holds the slot (in ``_live``, ``prefilled``
        set) and sits out the decode steps; its first token follows its
        last chunk."""
        rec = self.recorder
        t0 = time.monotonic()
        chunk, prompt = self.prefill_chunk, req.prompt
        if req.prefilled is None:
            self._prefill_begins(req, t0)
            req.prefilled = 0
            req.slot = slot
            # _live and the slot arrays are decode-thread-only (single
            # mutator: see the note in _admitted)
            self._live[slot] = req           # graftlint: disable=GL003
            self._lengths[slot] = 0          # graftlint: disable=GL003
            self._admitted_at[slot] = t0
        trace_id = req.trace.trace_id if req.trace is not None else None
        start = req.prefilled
        n = min(chunk, prompt.size - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[start:start + n]
        table = [self._chunk_table(k, slot) for k in self.kv.kinds]
        entry = self.registry.get(self.model_name)
        prog = self._program("chunk")
        with rec.span("decode.prefill", trace_id=trace_id, slot=slot,
                      offset=start):
            tok, bad, self._pool, *counts = prog(
                self._params_for_step(entry), self._pool,
                jnp.asarray(toks), jnp.int32(start), jnp.int32(n),
                self.kv.pack([jnp.asarray(t) for t in table]),
                jnp.float32(req.temperature), jnp.int32(self._steps))
            token = int(tok)
            # what the layers counted, under a prefill's own names: a
            # chunk's pairs are not a decode step's
            self._count_in("chunk", counts, prefix="prefill_")
        rec.inc("decode/prefill_chunks")
        if bool(bad):
            self._live.pop(slot, None)
            self._prefill_poisoned(slot, req, entry, chunk)
            return
        if start + n < prompt.size:
            req.prefilled = start + n
            return
        req.prefilled = None
        self._admitted(slot, req, token, chunk)

    def _chunk_table(self, kind, slot: int) -> np.ndarray:
        """What a chunk program takes of ``slot``'s table of ``kind``: the
        pages of the longest prompt (``-1`` past the table's width), or a
        window layer's whole ring."""
        if kind.ring:
            return kind.tables[slot].copy()
        table = np.full(self._chunk_pages, -1, np.int32)
        m = min(self._chunk_pages, kind.width)
        table[:m] = kind.tables[slot, :m]
        return table

    def _prefill_poisoned(self, slot, req, entry, bucket):
        # poisoned-weights sentinel: the program call SUCCEEDED
        # (self._pool was reassigned), so this is one request's
        # failure, not a donation hazard — fail it alone; the other
        # live slots' KV is intact and must survive (_recover_pool
        # would collaterally error every in-flight request)
        self.recorder.inc("decode/nonfinite")
        req.prefilled = None
        if req.trace is not None:
            req.trace.close("prefill", time.monotonic(), bucket=bucket)
        self.kv.free_slot(slot)
        self._finish(req, exc=RuntimeError(
            f"non-finite prefill logits serving "
            f"{entry.snapshot.version} — poisoned weights?"),
            cause="nonfinite")

    def _prefill(self, slot: int, req: _DecodeRequest, prompt: np.ndarray):
        rec = self.recorder
        trace_id = self._prefill_begins(req, time.monotonic())
        bucket = self.ladder.bucket_for(prompt.size)
        prog = self._program("prefill", bucket)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :prompt.size] = prompt
        # the prompt bucket may round up past max_context, so its page
        # span can exceed the slot's table row: pad with -1 (dropped
        # writes of padding-only pages)
        n_pages = -(-bucket // self.kv.page_size)
        table = np.full(n_pages, -1, np.int32)
        m = min(n_pages, self.kv.max_pages_per_slot)
        table[:m] = self.kv.tables[slot, :m]
        entry = self.registry.get(self.model_name)
        with rec.span("decode.prefill", trace_id=trace_id, bucket=bucket):
            tok, bad, self._pool = prog(
                self._params_for_step(entry), self._pool,
                jnp.asarray(toks), jnp.int32(prompt.size),
                jnp.asarray(table), jnp.float32(req.temperature),
                jnp.int32(self._steps))
            token = int(tok)
        if bool(bad):
            self._prefill_poisoned(slot, req, entry, bucket)
            return
        self._admitted(slot, req, token, bucket)

    def _admitted(self, slot: int, req: _DecodeRequest, token: int,
                  bucket: int):
        """The prompt is cached: the slot joins the decode steps."""
        rec = self.recorder
        prompt = req.prompt
        now = time.monotonic()
        rec.inc("decode/prefills")
        led = rec.get_ledger()
        if led is not None:
            # a prefill is productive single-sequence compute
            led.fold_split({"goodput": 1.0})
        req.slot = slot
        self._live[slot] = req
        # slot arrays (_lengths/_last_tokens/_admitted_at) are decode-
        # thread-only by construction (single mutator: every writer
        # runs on the decode loop); cross-thread reads go through
        # stats()/pending_rows(), which read queue/live under the lock
        self._lengths[slot] = prompt.size   # graftlint: disable=GL003
        self._admitted_at[slot] = now
        if req.trace is not None:
            req.trace.close("prefill", now, bucket=bucket,
                            prompt_rows=int(prompt.size))
        if req.generated:
            # READMISSION: the prompt prefill above is the same program
            # at the same bucket as the original admission, so its KV
            # (and the token it re-predicts, which we discard) are
            # bitwise the originals.  The recorded generated tokens now
            # REPLAY through the decode program — the exact program
            # that wrote their KV the first time — so the rebuilt cache
            # is bitwise identical and greedy decode continues exactly
            # where the eviction cut it off.  (Re-prefilling
            # prompt+generated instead would recompute the generated
            # rows' KV through a different batched-matmul program,
            # whose last-ulp drift can flip a later argmax.)
            rec.inc("decode/readmissions")
            req.replay_i = 1
            # decode-thread-only slot array (see _lengths note above)
            self._last_tokens[slot] = req.generated[0]  # graftlint: disable=GL003
        else:
            self._emit_token(slot, req, token, now)

    def _step_live(self) -> Optional[int]:
        """One fixed-shape decode step over every live slot; returns the
        step's index, or None when no step ran."""
        if all(r.prefilled is not None for r in self._live.values()):
            return None            # nobody, or prompts still in chunks
        rec = self.recorder
        with rec.span("decode.schedule"):
            now = time.monotonic()
            # deadline sheds + page growth happen BEFORE the step so the
            # step's inputs are consistent
            for slot in list(self._live):
                req = self._live.get(slot)
                if req is None or req.prefilled is not None:
                    continue        # evicted by an earlier slot's growth,
                    # or part-way through its chunks: not in this step
                if req.expired(now):
                    self._live.pop(slot)
                    self.kv.free_slot(slot)
                    self._shed_deadline(req, at="decode")
                    continue
                if not self.kv.alloc_for(slot, int(self._lengths[slot]) + 1):
                    if not self._evict_for(slot,
                                           int(self._lengths[slot]) + 1):
                        # nothing else to evict: this slot itself yields
                        self._evict(slot)
            live_slots = sorted(s for s, r in self._live.items()
                                if r.prefilled is None)
            if not live_slots:
                return None
            tokens = self._last_tokens.copy()
            lengths = self._lengths.copy()
            kinds = self.kv.kinds
            tables = [k.tables for k in kinds]
            temps = np.zeros(self.slots, np.float32)
            for s in live_slots:
                temps[s] = self._live[s].temperature
            for s in range(self.slots):
                if s in live_slots:
                    continue
                tokens[s] = 0
                lengths[s] = 0
                if s in self._live:
                    # held for a prompt still in chunks: the step must
                    # neither write its pages nor count it live
                    for i, k in enumerate(kinds):
                        if tables[i] is k.tables:
                            tables[i] = k.tables.copy()
                        tables[i][s] = -1
            # the pages this step's attention has to read (each live
            # slot's, the new token's included) of those a gathered
            # window holds
            top = lengths[live_slots] // self.kv.page_size + 1
            rec.inc("kv/pages_read", int(sum(
                (np.minimum(top, k.width) if k.ring else top).sum()
                for k in kinds)))
            rec.inc("kv/pages_window", self.slots * sum(
                k.width for k in kinds))
            entry = self.registry.get(self.model_name)
            prog = self._program("decode")
            # chaos seam: delay = a wedged decode step (the replica wedge
            # verdict's shape), err = the step fails and live requests
            # complete exceptionally (a ReplicaSet fails them over)
            faultplane.inject("serving.decode_step", rec)
        with rec.span("decode.stage"):
            params = self._params_for_step(entry)
            inputs = (jnp.asarray(tokens), jnp.asarray(lengths),
                      self.kv.pack([jnp.asarray(t) for t in tables]),
                      jnp.asarray(temps), jnp.int32(self._steps))
        with rec.span("decode.dispatch"):
            tok, bad, self._pool, *counts = prog(params, self._pool,
                                                 *inputs)
            del inputs
        with rec.span("decode.sync"):
            toks = np.asarray(tok)     # the per-step host sync — the
            # serving contract: every emitted token crosses to the host
            bads = np.asarray(bad)
            # what the layers counted in the step, ready with the tokens
            self._count_in("decode", counts)
        with rec.span("decode.emit"):
            step = self._emit_step(entry, live_slots, toks, bads)
            # the step's device handles go once the tokens are out, as
            # they did at this frame's teardown, but inside the leaf.
            # Dropping them lets go of the interpreter, and that is where
            # the clients' reader threads take their tokens: dropped
            # before the tokens went out, the readers ran inside the next
            # admission's prefill (`decode.prefill` 0.42 ms longer, TTFT
            # p50 1.4 ms, in ten pairs of ten; PERF.md section 6, PR 25)
            del tok, bad
            return step

    def _emit_step(self, entry, live_slots, toks, bads) -> Optional[int]:
        """What follows a step's sync: fail the slots whose logits were
        not finite, count, fold the ledger, hand every live slot its
        token.  Returns the step's index (None when no slot survived)."""
        rec = self.recorder
        now = time.monotonic()
        for slot in live_slots:
            if slot in self._live and bads[slot]:
                rec.inc("decode/nonfinite")
                req = self._live.pop(slot)
                self.kv.free_slot(slot)
                self._finish(req, exc=RuntimeError(
                    f"non-finite decode logits serving "
                    f"{entry.snapshot.version} — poisoned weights?"),
                    cause="nonfinite")
        live_slots = [s for s in live_slots if s in self._live]
        if not live_slots:
            return None
        step = self._steps
        self._steps += 1
        n_live = len(live_slots)
        rec.inc("decode/steps")
        rec.inc("decode/tokens", n_live)
        rec.inc("serving.rows", n_live)   # per-token progress: replica
        # health must see a long generation as work, not a wedge
        rec.gauge("decode/live_slots", n_live)
        rec.gauge("decode/occupancy", n_live / self.slots)
        led = rec.get_ledger()
        if led is not None:
            # the goodput fold: this step's interval splits by slot
            # occupancy — live slots are goodput, spare slots backed by
            # queued work are queue_wait (capacity idling while admitted
            # work waits on pages), the rest is honest idle
            with self._lock:
                depth = len(self._waiting)
            spare = self.slots - n_live
            led.fold_split({"goodput": n_live,
                            "queue_wait": min(spare, depth),
                            "idle": max(spare - depth, 0)})
        for slot in live_slots:
            self._lengths[slot] += 1
            req = self._live[slot]
            if req.replay_i and req.replay_i < len(req.generated):
                # replaying a readmitted slot: this step's prediction
                # was already emitted before the eviction — feed the
                # recorded token onward, emit nothing
                self._last_tokens[slot] = req.generated[req.replay_i]
                req.replay_i += 1
                rec.inc("decode/replayed_tokens")
                continue
            if req.replay_i:
                req.replay_i = 0       # caught up: prediction is fresh
            self._emit_token(slot, req, int(toks[slot]), now)
        if self.report_every and self._steps % self.report_every == 0:
            self._emit_decode_event()
        return step

    def _emit_token(self, slot: int, req: _DecodeRequest, token: int,
                    now: float):
        rec = self.recorder
        req.generated.append(token)
        self._last_tokens[slot] = token
        if req.first_token_at is None:
            req.first_token_at = now
            rec.observe("decode/ttft_ms", (now - req.arrival) * 1e3)
        elif req.last_token_at is not None:
            rec.observe("decode/intertoken_ms",
                        (now - req.last_token_at) * 1e3)
        if req.trace is not None:
            # one span per token batch this request took part in
            req.trace.add_span("token",
                               req.last_token_at or req.first_token_at,
                               now)
        req.last_token_at = now
        req.stream._q.put(token)
        done = len(req.generated) >= req.max_new \
            or (req.eos_id is not None and token == req.eos_id)
        if done:
            self._live.pop(slot, None)
            self.kv.free_slot(slot)
            self._finish(req, result=np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)]))

    def _finish(self, req: _DecodeRequest, result=None,
                exc: Optional[BaseException] = None,
                cause: Optional[str] = None):
        rec = self.recorder
        now = time.monotonic()
        tr = req.trace
        ring = self.trace_ring
        if tr is not None and ring is not None:
            # finish the trace BEFORE completing the future (the
            # ServingEngine contract): a client unblocked by .result()
            # that immediately scrapes /trace must see its request
            if exc is None:
                tr.meta["tokens"] = len(req.generated)
                ring.finish(tr)
            else:
                tr.terminal(cause or type(exc).__name__, now)
                ring.finish(tr)
        # future resolves BEFORE the stream's end marker: a consumer
        # whose tokens() iterator just ended may immediately call
        # result(0) and must not race the completion
        if exc is None:
            rec.inc("decode/finished")
            lat = (now - req.arrival) * 1e3
            rec.observe("decode/request_ms", lat)
            rec.observe("serving.latency_ms", lat)
            req.stream.future.set_result(result)
        else:
            req.stream.future.set_exception(exc)
        req.stream._q.put(_END)

    def _shed_deadline(self, req: _DecodeRequest, at: str):
        """Deadline shed: the terminal ``deadline`` span lands before
        the future fails — on the decode path exactly as at the queue
        pop (the ServingEngine shed-at-pop contract)."""
        self.recorder.inc("decode/shed_deadline")
        self._finish(req, exc=LoadShedError(
            "deadline", f"expired during {at}"), cause="deadline")

    def _fail_live(self, exc: BaseException):
        for slot in list(self._live):
            req = self._live.pop(slot)
            self.kv.free_slot(slot)
            self._finish(req, exc=exc)

    def _recover_pool(self, exc: BaseException):
        """After a prefill/decode program call fails: the pool args were
        DONATED, so on a donating backend ``self._pool`` may now point
        at deleted buffers — every later call would fail forever.  Live
        requests' KV is unrecoverable either way: fail them, release
        their pages, and rebuild a fresh zeroed pool so the engine (and
        its replica, via probe readmission) recovers from a transient
        step failure instead of black-holing 100% of traffic."""
        self._fail_live(exc)
        self._pool = self.kv.init_pool()

    def _emit_decode_event(self):
        rec = self.recorder
        counters = {k: rec.counter_value(k) for k in (
            "decode/requests", "decode/prefills", "decode/readmissions",
            "decode/steps", "decode/tokens", "decode/finished",
            "decode/shed_deadline", "decode/shed_queue_full",
            "decode/recompiles", "kv/page_allocs", "kv/page_frees",
            "kv/evictions")}
        with self._lock:
            depth = len(self._waiting)
        rec.emit_record(
            "decode_event", step=self._steps, live=len(self._live),
            slots=self.slots, occupancy=len(self._live) / self.slots,
            kv_fill=self.kv.fill(), queue_depth=depth,
            ttft=rec.hist_quantiles("decode/ttft_ms", (50.0, 99.0)),
            intertoken=rec.hist_quantiles("decode/intertoken_ms",
                                          (50.0, 99.0)),
            counters=counters)


_ATTN_ROUTES = ("gather", "pallas", "sparse", "latent")
_CHUNK_ATTN_ROUTES = ("window", "pallas", "latent")


def _select_tokens(logits, temps, step, base_key):
    """Greedy argmax (temperature 0 — deterministic, the golden-decode
    path) or softmax sampling at per-slot temperature off a
    step-folded key."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    key = jax.random.fold_in(base_key, step)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temps, 1e-6)[:, None],
        axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _decode_loop(engine_ref, cond, waiting, live, ring):
    """The decode thread.  Holds the engine weakly so a dropped,
    never-shut-down engine stays collectable; stranded requests then
    fail instead of hanging their clients forever."""
    while True:
        eng = engine_ref()
        if eng is None:
            exc = EngineClosedError(
                "decode engine was garbage-collected before this "
                "request ran")
            with cond:
                stranded = list(waiting) + list(live.values())
                waiting[:] = []
                live.clear()
            for req in stranded:
                if ring is not None and req.trace is not None:
                    req.trace.terminal("engine_closed", time.monotonic(),
                                       name="closed")
                    ring.finish(req.trace)
                if not req.stream.future.done():
                    req.stream.future.set_exception(exc)
                req.stream._q.put(_END)
            return
        try:
            alive = eng._tick()
        except Exception:
            alive = True           # _tick already contains per-request
            # failure handling; a bug here must not kill the loop
        finally:
            del eng                # never hold the engine across waits
        if not alive:
            return


def build_decode_replica_set(model, n: int, *, name: str = "lm",
                             probe_prompt=None,
                             engine_kw: Optional[Dict[str, Any]] = None,
                             **rs_kw):
    """N decode replicas behind one :class:`ReplicaSet`: one registry +
    DecodeEngine + Recorder per replica, all serving ``name``; the
    golden probe defaults to a short fixed prompt so ejected replicas
    can re-admit.  CanaryPublisher over the returned set golden-decode
    validates weight publications."""
    from .replicas import ReplicaSet
    engine_kw = dict(engine_kw or {})
    engine_kw.pop("recorder", None)
    engines = []
    for _ in range(int(n)):
        reg = ModelRegistry()
        reg.register(name, model)
        engines.append(DecodeEngine(reg, name, recorder=Recorder(),
                                    **engine_kw))
    rs = ReplicaSet(engines, **rs_kw)
    probe = probe_prompt if probe_prompt is not None \
        else np.arange(1, 5, dtype=np.int32)
    rs.set_probe(name, probe)
    return rs


__all__ = ["DecodeEngine", "DecodeStream", "build_decode_replica_set"]
