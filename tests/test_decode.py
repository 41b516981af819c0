"""Continuous-batching decode engine + paged KV cache + weight stream.

The load-bearing claims, each pinned here:

  * paged-KV gather/scatter correctness — incl. the regression for the
    jax negative-index WRAP hazard (a raw ``-1`` table entry aliases
    the pool's LAST page instead of dropping/filling: a dead slot's
    write clobbered whichever request owned it)
  * paged decode logits equal to the contiguous ``init_cache`` path at
    a matched attention window, to float32 rounding (the two programs
    order their sums differently)
  * int8 KV drift bounded (and only bounded — never silently hidden)
  * eviction → readmission (re-prefill + replay) EXACT: a contended
    run with forced evictions produces bitwise the tokens of an
    uncontended run of the same engine config
  * pool-exhaustion admission backpressure + queue sheds
  * deadline sheds finish the trace with a terminal ``deadline`` span
    before the future fails (the ServingEngine contract on the decode
    path)
  * Trigger-fired weight streaming: owning snapshots, canary-gated
    publication into a decode replica set, bit-identical rollback on a
    poisoned publish
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import faults
from bigdl_tpu.models import transformer as T
from bigdl_tpu.observability import Recorder, critical_path
from bigdl_tpu.observability.tracing import Tracer, set_tracer
from bigdl_tpu.serving import (CanaryPublisher, CanaryRejectedError,
                               DecodeEngine, LoadShedError,
                               ModelRegistry, PagePoolError, PagedKVCache,
                               WeightStreamPublisher,
                               build_decode_replica_set)


@pytest.fixture(scope="module")
def lm():
    model = T.build("tiny", dropout=0.0, n_layers=2, max_len=128)
    model.ensure_initialized()
    return model


@pytest.fixture(scope="module")
def eng64(lm):
    reg = ModelRegistry()
    reg.register("lm", lm)
    eng = DecodeEngine(reg, "lm", slots=4, page_size=8, max_context=64,
                       max_prompt=16, max_new_tokens=8).warmup()
    yield eng
    eng.shutdown()


def small_engine(lm, **kw):
    reg = ModelRegistry()
    reg.register("lm", lm)
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 32)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("max_new_tokens", 12)
    return DecodeEngine(reg, "lm", **kw).warmup()


# --------------------------------------------------------------------- #
# page allocator                                                         #
# --------------------------------------------------------------------- #
def _alloc(n_pages=8, n_slots=3, page=4, ctx=16):
    return PagedKVCache(["a"], n_heads=1, head_dim=2, n_pages=n_pages,
                        page_size=page, n_slots=n_slots, max_context=ctx)


def test_allocator_alloc_free_invariants():
    kv = _alloc()
    assert kv.alloc_for(0, 5)            # 2 pages
    assert kv.alloc_for(1, 9)            # 3 pages
    assert kv.pages_in_use() == 5
    assert kv.fill() == 5 / 8
    kv.check_invariants()
    # growth is incremental, idempotent below the watermark
    assert kv.alloc_for(0, 5)
    assert kv.pages_in_use() == 5
    assert kv.free_slot(0) == 2
    assert kv.pages_in_use() == 3
    assert np.all(kv.tables[0] == -1)
    kv.check_invariants()


def test_allocator_exhaustion_all_or_nothing():
    kv = _alloc(n_pages=4)
    assert kv.alloc_for(0, 12)           # 3 pages
    assert not kv.alloc_for(1, 8)        # needs 2, only 1 free
    # failed alloc took NOTHING (all-or-nothing)
    assert kv.pages_in_use() == 3
    assert kv.alloc_for(1, 4)            # 1 page fits
    assert not kv.can_fit(4)
    kv.check_invariants()


def test_allocator_double_free_raises():
    kv = _alloc()
    kv.alloc_for(0, 4)
    page = kv.tables[0, 0]
    kv.free_slot(0)
    kv._owned[0] = [int(page)]           # corrupt the ledger on purpose
    with pytest.raises(PagePoolError):
        kv.free_slot(0)


def test_allocator_oversized_request_rejected():
    kv = _alloc(ctx=16, page=4)
    with pytest.raises(ValueError):
        kv.alloc_for(0, 17)              # > max_pages_per_slot


# --------------------------------------------------------------------- #
# gather/scatter                                                         #
# --------------------------------------------------------------------- #
def test_gather_window_orders_pages_and_fills_zero():
    kv = _alloc(n_pages=6, n_slots=2, page=4, ctx=16)
    k = np.zeros((6, 4, 1, 2), np.float32)
    for p in range(6):
        for o in range(4):
            k[p, o] = p * 10 + o
    pool = {"k": jnp.asarray(k), "v": jnp.asarray(k.copy())}
    tables = jnp.asarray(np.array([[5, 2, -1, -1], [-1, -1, -1, -1]],
                                  np.int32))
    kw, vw = kv.gather_window(pool, tables)
    w = np.asarray(kw)[0, 0, :, 0]
    assert list(w[:4]) == [50, 51, 52, 53]       # page 5 first
    assert list(w[4:8]) == [20, 21, 22, 23]      # then page 2
    assert np.all(w[8:] == 0)                    # -1 entries fill zero
    assert np.all(np.asarray(kw)[1] == 0)        # dead slot all zero


def test_negative_table_entries_never_alias_the_last_page():
    """Regression: jax wraps negative scatter/gather indices BEFORE the
    bounds check, so a raw -1 aliased page n_pages-1 — a dead slot's
    write clobbered whichever live request owned that page."""
    kv = _alloc(n_pages=6, n_slots=4, page=8, ctx=32)
    k = np.arange(6 * 8 * 1 * 2, dtype=np.float32).reshape(6, 8, 1, 2)
    pool = {"k": jnp.asarray(k), "v": jnp.asarray(k.copy())}
    tables = jnp.asarray(np.array(
        [[-1, -1, -1, -1], [1, 2, -1, -1], [3, 4, -1, -1], [5, 0, -1, -1]],
        np.int32))
    lengths = jnp.asarray(np.array([0, 10, 14, 8], np.int32))
    new = jnp.asarray(np.full((4, 1, 1, 2), -1000.0, np.float32))
    out = kv.write_token(pool, tables, lengths, new, new)
    kp = np.asarray(out["k"])
    # page 5 row 0 (slot 3's FIRST prompt row) must be untouched by the
    # dead slot 0's dropped write
    assert np.array_equal(kp[5, 0], k[5, 0])
    # the live writes landed where the tables say
    assert np.all(kp[2, 2] == -1000.0)           # slot 1: len 10
    assert np.all(kp[4, 6] == -1000.0)           # slot 2: len 14
    assert np.all(kp[0, 0] == -1000.0)           # slot 3: len 8
    # gather side: -1 fills zeros, never the last page's data
    tb = jnp.asarray(np.full((4, 4), -1, np.int32))
    kw, _ = kv.gather_window(out, tb)
    assert np.all(np.asarray(kw) == 0)


def _paged_reference(model, params, prompt, new_tokens, kv, slot):
    """Greedy decode through the paged path, eagerly (prefill bucket =
    next pow2, per-step write+attend) — returns per-step logits."""
    L = prompt.shape[1]
    bucket = 1 << max(L - 1, 0).bit_length() if L > 1 else 1
    pool = kv.init_pool()
    assert kv.alloc_for(slot, L)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :L] = prompt[0]
    pc = model.init_cache(1, dtype=kv.dtype, cache_len=bucket)
    lgp, pc = model.apply_with_cache(params, jnp.asarray(toks), pc, 0)
    n_pages = -(-bucket // kv.page_size)
    table = np.full(n_pages, -1, np.int32)
    m = min(n_pages, kv.max_pages_per_slot)
    table[:m] = kv.tables[slot, :m]
    for name in kv.layer_names:
        pool[name] = kv.write_prefill(pool[name], jnp.asarray(table),
                                      pc[name]["k"], pc[name]["v"])
    logits = [np.asarray(lgp[0, L - 1])]
    lengths = np.zeros(kv.n_slots, np.int32)
    lengths[slot] = L
    last = np.zeros(kv.n_slots, np.int32)
    last[slot] = int(np.argmax(logits[0]))
    for _ in range(new_tokens - 1):
        kv.alloc_for(slot, int(lengths[slot]) + 1)
        tb = jnp.asarray(kv.tables)
        ln = jnp.asarray(lengths)

        def kv_io(name, q, k, v, _tb=tb, _ln=ln):
            pool[name] = kv.write_token(pool[name], _tb, _ln, k, v)
            return kv.attend(pool[name], _tb, _ln, q)

        lg = model.decode_tokens(params, jnp.asarray(last), ln, kv_io)
        logits.append(np.asarray(lg[slot]))
        last[slot] = int(np.argmax(lg[slot]))
        lengths[slot] += 1
    return logits


def test_paged_decode_matches_contiguous_cache(lm):
    """The gather-window path produces the logits of the contiguous
    init_cache path at a matched attention window.  Not bitwise: the
    slot-batched decode program and the batch-1 cached one round their
    sums in another order (about 1e-6 from step 0 on CPU)."""
    params = lm._params
    prompt = np.random.RandomState(1).randint(0, 256, (1, 5)) \
        .astype(np.int32)
    L, NEW = 5, 6
    kv = PagedKVCache([b.attn.name for b in lm.blocks],
                      n_heads=lm.cfg.n_heads, head_dim=lm.cfg.head_dim,
                      n_pages=24, page_size=8, n_slots=3, max_context=64)
    # contiguous reference at cache_len == the paged window
    cache = lm.init_cache(1, cache_len=kv.window)
    lg, cache = lm.apply_with_cache(params, jnp.asarray(prompt), cache, 0)
    ref = [np.asarray(lg[0, L - 1])]
    tok = jnp.argmax(lg[:, L - 1], -1).astype(jnp.int32)
    pos = L
    for _ in range(NEW - 1):
        lg, cache = lm.apply_with_cache(params, tok[:, None], cache, pos)
        ref.append(np.asarray(lg[0, 0]))
        tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
        pos += 1
    got = _paged_reference(lm, params, prompt, NEW, kv, slot=1)
    for i, (a, b) in enumerate(zip(ref, got)):
        assert np.allclose(a, b, rtol=0, atol=2e-5), \
            f"step {i}: {np.abs(a - b).max()}"


def test_int8_kv_drift_bounded_and_not_hidden(lm):
    """int8 KV is lossy BY DESIGN: the per-channel quantizer bounds the
    drift; this pins the measured envelope (documented in
    docs/serving.md) instead of asserting fake exactness."""
    params = lm._params
    prompt = np.random.RandomState(2).randint(0, 256, (1, 7)) \
        .astype(np.int32)
    mk = lambda int8: PagedKVCache(
        [b.attn.name for b in lm.blocks], n_heads=lm.cfg.n_heads,
        head_dim=lm.cfg.head_dim, n_pages=16, page_size=8, n_slots=2,
        max_context=64, int8=int8)
    fp = _paged_reference(lm, params, prompt, 5, mk(False), slot=0)
    q8 = _paged_reference(lm, params, prompt, 5, mk(True), slot=0)
    drift = max(float(np.max(np.abs(a - b))) for a, b in zip(fp, q8))
    scale = max(float(np.max(np.abs(a))) for a in fp)
    assert drift > 0.0                   # it IS lossy — never pretend
    assert drift / scale < 0.05, \
        f"int8 KV relative logit drift {drift / scale:.4f} out of the " \
        "documented envelope"


# --------------------------------------------------------------------- #
# engine                                                                 #
# --------------------------------------------------------------------- #
def test_engine_mixed_lengths_zero_recompiles_and_deterministic(eng64):
    rng = np.random.RandomState(0)
    reqs = [rng.randint(0, 256, rng.randint(1, 17)).astype(np.int32)
            for _ in range(10)]
    base = eng64.recorder.counter_value("decode/recompiles")
    futs = [eng64.submit("lm", p, max_new_tokens=6) for p in reqs]
    first = [f.result(60) for f in futs]
    again = [eng64.submit("lm", p, max_new_tokens=6).result(60)
             for p in reqs]
    for o, p in zip(first, reqs):
        assert o.shape == (len(p) + 6,)
        assert np.array_equal(o[:len(p)], p)
    for a, b in zip(first, again):
        assert np.array_equal(a, b)      # concurrent == sequential
    assert eng64.recorder.counter_value("decode/recompiles") == base
    eng64.kv.check_invariants()


def test_engine_stream_iterator_and_stats(eng64):
    p = np.arange(1, 6, dtype=np.int32)
    stream = eng64.stream("lm", p, max_new_tokens=5)
    toks = list(stream.tokens())
    out = stream.result(10)
    assert len(toks) == 5
    assert np.array_equal(out, np.concatenate([p, np.asarray(toks)]))
    st = eng64.stats()
    assert st["finished"] >= 1 and st["tokens"] > 0
    assert 0 < st["occupancy"] <= 1


def test_eviction_readmission_replay_exact(lm):
    """Forced evictions (pool 6 pages << working set) produce BITWISE
    the tokens of the same engine config without contention — the
    re-prefill + deterministic-replay readmission."""
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 256, (l,)) for l in (6, 10, 14, 8)]
    e = small_engine(lm, pool_pages=6)
    solo = [e.submit("lm", p, max_new_tokens=12).result(120)
            for p in prompts]
    assert e.recorder.counter_value("kv/evictions") == 0
    e.shutdown()
    e = small_engine(lm, pool_pages=6)
    futs = [e.submit("lm", p, max_new_tokens=12) for p in prompts]
    outs = [f.result(120) for f in futs]
    ev = e.recorder.counter_value("kv/evictions")
    re = e.recorder.counter_value("decode/readmissions")
    e.kv.check_invariants()
    e.shutdown()
    assert ev > 0 and re > 0, "pool pressure must actually evict"
    for a, b in zip(solo, outs):
        assert np.array_equal(a, b)


def test_pool_exhaustion_backpressure(lm):
    e = small_engine(lm, slots=2, pool_pages=3, max_waiting=2,
                     max_new_tokens=8)
    # each request needs up to 2 pages at full length -> the pool only
    # runs a couple at once; the rest wait in the bounded queue, which
    # sheds at the door once full.  The first three must all land, but
    # the 2-deep queue can shed them if the decode thread hasn't popped
    # one yet (single-CPU scheduling), so retry those — the sustained
    # oversubmission below still has to shed
    long = []
    deadline = time.time() + 30.0
    while len(long) < 3:
        try:
            long.append(e.submit("lm", np.arange(8, dtype=np.int32) + 1,
                                 max_new_tokens=8))
        except LoadShedError:
            assert time.time() < deadline, "admission never drained"
            time.sleep(0.01)
    with pytest.raises(LoadShedError):
        for _ in range(8):
            long.append(e.submit("lm", np.arange(8, dtype=np.int32) + 1,
                                 max_new_tokens=8))
    assert e.recorder.counter_value("decode/shed_queue_full") >= 1
    for f in long:
        f.result(120)                    # backpressured work still lands
    # a request the whole pool cannot hold is rejected loudly
    with pytest.raises(ValueError):
        e.submit("lm", np.arange(16, dtype=np.int32) + 1,
                 max_new_tokens=16)      # 4 pages > the 3-page pool
    e.shutdown()


def test_deadline_shed_finishes_trace_before_future(eng64):
    fut = eng64.submit("lm", np.arange(1, 7, dtype=np.int32),
                       deadline_ms=0.0, max_new_tokens=4)
    with pytest.raises(LoadShedError):
        fut.result(30)
    # the trace finished WITH a terminal deadline span (visible on
    # /trace) — the ServingEngine shed-at-pop contract on decode
    traces = eng64.trace_ring.traces()
    assert any(t.meta.get("cause") == "deadline" for t in traces)
    # the streaming iterator surfaces the failure too — a truncated
    # stream must never read as a short success
    stream = eng64.stream("lm", np.arange(1, 7, dtype=np.int32),
                          deadline_ms=0.0, max_new_tokens=4)
    with pytest.raises(LoadShedError):
        for _ in stream.tokens():
            pass


def test_poisoned_weights_fail_loudly_and_hot_swap_back(lm):
    e = small_engine(lm, slots=2)
    good = np.asarray(e.predict("lm", np.arange(1, 5, dtype=np.int32),
                                timeout=60, max_new_tokens=4))
    reg = e.registry
    snap = reg.get("lm").snapshot
    poison = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32) * np.nan, snap.params)
    reg.swap_weights("lm", poison, version="poison")
    with pytest.raises(RuntimeError, match="non-finite"):
        e.predict("lm", np.arange(1, 5, dtype=np.int32), timeout=60,
                  max_new_tokens=4)
    assert e.recorder.counter_value("decode/nonfinite") >= 1
    reg.swap_weights("lm", snap.params, version="restored")
    back = np.asarray(e.predict("lm", np.arange(1, 5, dtype=np.int32),
                                timeout=60, max_new_tokens=4))
    assert np.array_equal(good, back)    # hot-swap restore is bitwise
    e.shutdown()


def test_metrics_scrape_has_per_token_slo(eng64):
    import urllib.request
    eng64.predict("lm", np.arange(1, 5, dtype=np.int32), timeout=60,
                  max_new_tokens=4)
    server = eng64.serve_metrics(port=0)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=10
    ).read().decode()
    for fam in ("decode_ttft_ms", "decode_tokens", "kv_pool_fill"):
        assert fam in body


# --------------------------------------------------------------------- #
# weight streaming                                                       #
# --------------------------------------------------------------------- #
def test_weight_stream_trigger_gating_and_owning_snapshot(lm):
    reg = ModelRegistry()
    reg.register("lm", lm)
    rec_versions = []
    target = lambda name, params, version: rec_versions.append(
        (version, params))
    wsp = WeightStreamPublisher(target, "lm", every_steps=2, sync=True)
    src = {k: {kk: np.array(vv, np.float32) for kk, vv in v.items()}
           for k, v in
           jax.tree_util.tree_map(np.asarray, lm._params).items()}
    assert not wsp.maybe_publish(src, step=1)
    assert wsp.maybe_publish(src, step=2)
    assert wsp.recorder.counter_value("stream/snapshots") == 1
    version, published = rec_versions[0]
    leaf = next(iter(next(iter(src.values())).values()))
    before = next(iter(next(iter(published.values())).values())).copy()
    leaf += 999.0                        # trainer scribbles on its buffers
    after = next(iter(next(iter(published.values())).values()))
    assert np.array_equal(before, after), \
        "published snapshot must OWN its memory (PR-3 rule)"


def test_weight_stream_skips_while_busy():
    import threading
    release = threading.Event()
    started = threading.Event()

    def slow_target(name, params, version):
        started.set()
        release.wait(10)

    wsp = WeightStreamPublisher(slow_target, "m", every_steps=1)
    params = {"a": {"w": np.zeros(4, np.float32)}}
    assert wsp.maybe_publish(params, step=1)
    started.wait(10)
    assert not wsp.maybe_publish(params, step=2)     # one in flight
    assert wsp.recorder.counter_value("stream/skipped_busy") == 1
    release.set()
    wsp.wait(10)
    assert wsp.recorder.counter_value("stream/published") == 1


def test_weight_stream_rejects_exactly_one_of_trigger_every():
    with pytest.raises(ValueError):
        WeightStreamPublisher(lambda *a: None, "m")
    with pytest.raises(ValueError):
        from bigdl_tpu.optim.trigger import Trigger
        WeightStreamPublisher(lambda *a: None, "m",
                              trigger=Trigger.several_iteration(1),
                              every_steps=2)


@pytest.mark.slow
def test_decode_replica_canary_publish_and_bitwise_rollback(lm):
    golden = np.random.RandomState(0).randint(0, 256, (6,)) \
        .astype(np.int32)
    rs = build_decode_replica_set(
        lm, 2, name="lm", probe_prompt=golden,
        engine_kw=dict(slots=2, page_size=8, max_context=32,
                       max_prompt=16, max_new_tokens=6))
    rs.warmup()
    # default drift bounds: integer (token-id) golden outputs skip the
    # magnitude gate — a legit update may change every token; the
    # poison gate is the finite-logits failure of the golden decode
    pub = CanaryPublisher(rs, {"lm": golden}, quiesce_timeout=30.0)
    before = np.asarray(rs.predict("lm", golden, timeout=60))
    new = jax.tree_util.tree_map(np.asarray, lm._params)
    new = {k: dict(v) for k, v in new.items()}
    emb = [k for k in new if k.endswith("embed")][0]
    new[emb] = {"weight": new[emb]["weight"]
                + 0.05 * np.sign(new[emb]["weight"])}
    pub.publish("lm", new)
    after = np.asarray(rs.predict("lm", golden, timeout=60))
    assert not np.array_equal(before, after)
    poison = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32) * np.nan, new)
    with pytest.raises(CanaryRejectedError):
        pub.publish("lm", poison)
    rolled = np.asarray(rs.predict("lm", golden, timeout=60))
    assert np.array_equal(after, rolled), "rollback must be bitwise"
    assert rs.recorder.counter_value("serving/canary_rejected") == 1
    rs.shutdown()


@pytest.mark.slow
def test_replica_predict_never_splits_a_prompt(lm):
    """A decode 'row' is one token of a SEQUENCE: ReplicaSet.predict
    must reject an over-long prompt loudly instead of slicing it into
    independent requests and concatenating unrelated decodes."""
    rs = build_decode_replica_set(
        lm, 1, name="lm",
        engine_kw=dict(slots=2, page_size=8, max_context=32,
                       max_prompt=8, max_new_tokens=4))
    rs.warmup()
    with pytest.raises(ValueError, match="max_prompt"):
        rs.predict("lm", np.arange(1, 25, dtype=np.int32), timeout=30)
    ok = rs.predict("lm", np.arange(1, 7, dtype=np.int32), timeout=60)
    assert ok.shape == (10,)
    rs.shutdown()


# --------------------------------------------------------------------- #
# the tick's timeline (decode.tick and its six leaves in the SpanStore)  #
# --------------------------------------------------------------------- #
LEAVES = ["decode.admit", "decode.schedule", "decode.stage",
          "decode.dispatch", "decode.sync", "decode.emit"]
PREFILL_LEAVES = ["prefill.stage", "prefill.dispatch", "prefill.sync"]


@pytest.fixture
def span_store():
    """A tracer of the test's own as the process default, so the spans
    of other tests' engines stay out of the count."""
    tracer = Tracer(capacity=20000)
    prev = set_tracer(tracer)
    yield tracer.store
    set_tracer(prev)


def hand_driven(lm, **kw):
    """An engine whose decode thread never starts: the test calls
    `_tick()` itself, so what a tick leaves can be counted exactly."""
    eng = small_engine(lm, **kw)
    eng._ensure_loop_locked = lambda: None
    return eng


def drive(eng):
    ticks = 0
    while eng.pending_rows():
        eng._tick()
        ticks += 1
        assert ticks < 1000
    return ticks


def tick_rows(store):
    """[(tick span, its children in order of their start)]"""
    spans = store.spans()
    kids = {}
    for s in spans:
        kids.setdefault(s.context.parent_span_id, []).append(s)
    return [(t, sorted(kids.get(t.context.span_id, []), key=lambda s: s.t0))
            for t in spans if t.name == "decode.tick"]


def test_every_stepped_tick_leaves_six_ordered_leaves_that_cover_it(
        lm, span_store):
    # a step of the toy model takes 2 ms, of which the spans' own
    # bookkeeping is 3%: the fault plane's delay (it fires inside
    # `decode.schedule`) makes a tick as long as one on the chip
    faults.arm("serving.decode_step:delay:15")
    eng = small_engine(lm)
    try:
        streams = [eng.stream("lm", np.arange(1, 5 + i, dtype=np.int32),
                              max_new_tokens=6) for i in range(6)]
        for st in streams:
            assert len(list(st.tokens())) == 6
    finally:
        faults.disarm()
        eng.shutdown()
    assert span_store.dropped == 0
    rows = tick_rows(span_store)
    assert len(rows) == eng.recorder.counter_value("decode/steps") > 0
    assert [t.args["step"] for t, _ in rows] == list(range(len(rows)))
    covered = total = 0.0
    coverage = []
    for tick, kids in rows:
        assert [k.name for k in kids] == LEAVES
        assert {k.trace_id for k in kids} == {tick.trace_id}
        assert tick.t0 <= kids[0].t0 and kids[-1].t1 <= tick.t1
        for a, b in zip(kids, kids[1:]):
            assert a.t0 <= a.t1 <= b.t0          # disjoint, in order
        coverage.append(
            critical_path([(k.name, k.t0, k.t1) for k in kids])["coverage"])
        covered += sum(k.duration() for k in kids)
        total += tick.duration()
    # over the median tick and over all of them (one tick may be cut in
    # two by the scheduler of a busy test machine)
    assert sorted(coverage)[len(coverage) // 2] >= 0.98
    assert covered / total >= 0.98
    # the parent stays off the profiler's timeline, the leaves go onto it
    assert eng.recorder.annotate


def test_request_queue_and_prefill_share_its_trace_id(lm, span_store):
    eng = small_engine(lm)
    try:
        st = eng.stream("lm", np.arange(1, 9, dtype=np.int32),
                        max_new_tokens=3)
        assert len(list(st.tokens())) == 3
        st.result(5.0)
    finally:
        eng.shutdown()
    (tr,) = eng.trace_ring.traces()
    mine = span_store.by_trace(tr.trace_id)
    assert sorted(s.name for s in mine) == ["decode.prefill", "decode.queue",
                                            *sorted(PREFILL_LEAVES)]
    (queue,) = [s for s in mine if s.name == "decode.queue"]
    (prefill,) = [s for s in mine if s.name == "decode.prefill"]
    assert prefill.args == {"bucket": 8}
    by_name = {name: (t0, t1) for name, t0, t1, _ in tr.spans
               if name in ("queue", "prefill")}
    # the queue span runs from the request's arrival to the start of its
    # prefill, and the prefill ends where the first token is stamped
    assert queue.t1 == pytest.approx(by_name["queue"][1], abs=2e-3)
    assert queue.t1 <= prefill.t0
    first_token = min(t0 for name, t0, _, _ in tr.spans if name == "token")
    assert prefill.t1 == pytest.approx(first_token, abs=2e-3)
    # both lie inside the admit leaf of the tick that took the request in
    admit = [s for s in span_store.spans()
             if s.context.span_id == prefill.context.parent_span_id]
    assert [s.name for s in admit] == ["decode.admit"]
    # the runner's reading of the prefill span is the sum of its intervals
    assert eng.recorder.span_value("decode.prefill") == \
        pytest.approx(prefill.duration())


def test_tick_path_mints_no_uuid_and_a_fixed_count_of_spans(
        lm, span_store, monkeypatch):
    import uuid
    calls = []
    real = uuid.uuid4

    def counting():
        calls.append(1)
        return real()

    monkeypatch.setattr(uuid, "uuid4", counting)
    counts = []
    for _ in range(2):          # the count repeats exactly
        eng = hand_driven(lm)
        span_store.clear()      # the warm-up's compile spans
        try:
            streams = [eng.stream("lm", np.arange(1, 6, dtype=np.int32),
                                  max_new_tokens=5) for _ in range(3)]
            assert calls            # a request's trace id is a uuid ...
            del calls[:]
            ticks = drive(eng)
            assert not calls        # ... and nothing on the tick path is
            for st in streams:
                assert len(st.result(0)) == 10
        finally:
            eng.shutdown()
        # all three are admitted by the first tick and finish together:
        # every tick steps, and leaves its parent and six leaves; every
        # request leaves its queue and prefill spans, and the prefill's
        # three leaves
        assert ticks == 4
        assert len(span_store) == 7 * ticks + 5 * len(streams)
        counts.append(len(span_store))
    assert counts[0] == counts[1]


def test_a_tick_that_runs_no_step_leaves_no_tick_span(lm, span_store):
    eng = hand_driven(lm)
    span_store.clear()          # the warm-up's compile spans
    try:
        st = eng.stream("lm", np.arange(1, 6, dtype=np.int32),
                        max_new_tokens=1)
        assert drive(eng) == 1      # the prefill's token finishes it
        assert len(st.result(0)) == 6
    finally:
        eng.shutdown()
    names = sorted(s.name for s in span_store.spans())
    assert names == ["decode.admit", "decode.prefill", "decode.queue",
                     *sorted(PREFILL_LEAVES)]


@pytest.mark.parametrize("route", ["ladder", "chunk"])
def test_every_prefill_leaves_three_ordered_leaves_that_cover_it(
        lm, span_store, route):
    """Inside each `decode.prefill`, a ladder bucket's or a chunk's: the
    uploads, the call and the wait, in order, with the span's own
    arguments and trace id; the span still ends on the token's sync."""
    chunked = route == "chunk"
    eng = hand_driven(lm, **({"max_prompt": 24, "prefill_chunk": 8}
                             if chunked else {}))
    assert eng.chunked == chunked
    span_store.clear()          # the warm-up's spans
    lengths = (5, 19) if chunked else (5, 13)
    try:
        streams = [eng.stream("lm", np.arange(1, n + 1, dtype=np.int32),
                              max_new_tokens=2) for n in lengths]
        drive(eng)
        for n, st in zip(lengths, streams):
            assert len(st.result(0)) == n + 2
    finally:
        eng.shutdown()
    spans = span_store.spans()
    kids = {}
    for s in spans:
        kids.setdefault(s.context.parent_span_id, []).append(s)
    prefills = [s for s in spans if s.name == "decode.prefill"]
    # a chunk a program: 1 + 3 of 8 tokens; a bucket a prompt
    assert len(prefills) == (4 if chunked else 2)
    coverage = []
    for p in prefills:
        leaves = sorted(kids[p.context.span_id], key=lambda s: s.t0)
        assert [k.name for k in leaves] == PREFILL_LEAVES
        assert {k.trace_id for k in leaves} == {p.trace_id}
        assert set(p.args) == ({"slot", "offset"} if chunked else {"bucket"})
        assert p.t0 <= leaves[0].t0 and leaves[-1].t1 <= p.t1
        for a, b in zip(leaves, leaves[1:]):
            assert a.t0 <= a.t1 <= b.t0          # disjoint, in order
        coverage.append(sum(k.duration() for k in leaves) / p.duration())
    assert sorted(coverage)[len(coverage) // 2] >= 0.95
    if chunked:
        assert sorted(p.args["offset"] for p in prefills) == [0, 0, 8, 16]
    # the leaves are the prefill's: the runner's reading of `decode.prefill`
    # is still the sum of its spans, and no tick gained a leaf
    assert eng.recorder.span_value("decode.prefill") == \
        pytest.approx(sum(p.duration() for p in prefills))
    assert all([k.name for k in kids] == LEAVES
               for _, kids in tick_rows(span_store))


def test_each_program_is_named_for_what_it_runs(lm):
    """A device trace names a module by its program: `jit_fn` is the
    decode step alone, a ladder bucket's is `jit_prefill_<bucket>` and
    the chunk program's `jit_prefill_chunk`."""
    import re

    def names(eng):
        try:
            return {key: re.match(r"HloModule (\w+)", prog.as_text())[1]
                    for key, prog in eng._programs.items()}
        finally:
            eng.shutdown()

    ladder = names(small_engine(lm))
    buckets = sorted(b for kind, b in ladder if kind == "prefill")
    assert buckets and ladder == {("decode", None): "jit_fn", **{
        ("prefill", b): f"jit_prefill_{b}" for b in buckets}}
    chunked = names(small_engine(lm, max_prompt=24, prefill_chunk=8))
    assert chunked == {("decode", None): "jit_fn",
                       ("chunk", None): "jit_prefill_chunk"}


def test_disabled_recorder_engine_leaves_no_spans(lm, span_store):
    eng = hand_driven(lm, recorder=Recorder(enabled=False))
    try:
        st = eng.stream("lm", np.arange(1, 6, dtype=np.int32),
                        max_new_tokens=3)
        drive(eng)
        assert len(st.result(0)) == 8
    finally:
        eng.shutdown()
    assert len(span_store) == 0


def test_replica_engines_annotate_their_leaves(lm):
    rs = build_decode_replica_set(lm, 2, engine_kw=dict(
        slots=2, page_size=8, max_context=32, max_prompt=16))
    try:
        assert all(r.engine.recorder.annotate for r in rs.replicas)
    finally:
        rs.shutdown()


def test_trace_summary_decode_table():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "trace_summary.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)
    events = [("t.jsonl", {"type": "decode_event", "time": 10.0 + i,
                           "step": 16 * (i + 1), "live": 3 + i,
                           "slots": 4, "occupancy": (3 + i) / 4.0,
                           "kv_fill": 0.25 * (i + 1), "queue_depth": i,
                           "ttft": {"p50": 4.0, "p99": 12.0},
                           "intertoken": {"p50": 1.2, "p99": 3.4}})
              for i in range(2)]
    counters = {"decode/tokens": 96.0, "decode/requests": 7.0,
                "kv/evictions": 2.0, "decode/prefills": 9.0}
    lines = []
    ts.summarize_serving(events, counters, out=lines.append)
    text = "\n".join(lines)
    assert "per-token SLO" in text
    assert "occupancy timeline" in text
    assert "ttft" in text and "inter-token" in text
    assert "decode/tokens" in text
