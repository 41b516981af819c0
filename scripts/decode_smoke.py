"""CI smoke for the continuous-batching decode engine (CPU).

Four legs, all on a tiny TransformerLM with the real serving stack:

1. **churn** — mixed prompt lengths and join/leave churn through one
   DecodeEngine: every request completes, and after warmup the mixed
   stream compiles NOTHING (``decode/recompiles == 0`` — the token-SLO
   invariant the bucket ladder + fixed-shape step exist for).
2. **throughput** — continuous batching vs static batching, all else
   equal: the SAME engine serves the SAME seeded workload twice, once
   with requests submitted in waves that wait for the slowest member
   (static batch semantics — slots idle on stragglers) and once all at
   once (slot-granularity join/leave).  Mixed output lengths; gate:
   continuous tokens/s >= 1.5x static — a CPU timing, good for the
   ordering of the two schedules and nothing else; never a device
   number.
3. **metrics** — per-token SLO accounting is live on /metrics:
   ``decode/ttft_ms`` / ``decode/intertoken_ms`` summaries and the
   ``kv/*`` pool gauges scrape from the engine's introspection server.
4. **stream** — live train->serve weight streaming: an SpmdTrainer
   fits the LM while a WeightStreamPublisher (Trigger-fired) streams
   snapshots through a CanaryPublisher into a 2-replica decode set
   under client load.  Asserts: publishes happened; post-publish decode
   output is BITWISE what an independent decode of the trainer's
   published snapshot produces; a NaN-poisoned publish is canary-
   rejected and rolls back bit-identically with ZERO client errors.

Emits one machine-parseable JSON line (the driver parses the LAST
line): ``{"metric": "decode_smoke", "ok": ..., ...}``.
"""
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np                                         # noqa: E402
import jax                                                 # noqa: E402

from bigdl_tpu.models import transformer as T              # noqa: E402
from bigdl_tpu.optim.optim_method import SGD               # noqa: E402
from bigdl_tpu.parallel import mesh as mesh_lib            # noqa: E402
from bigdl_tpu.parallel.spmd import SpmdTrainer            # noqa: E402
from bigdl_tpu.serving import (CanaryPublisher,            # noqa: E402
                               CanaryRejectedError, DecodeEngine,
                               ModelRegistry, WeightStreamPublisher,
                               build_decode_replica_set)

FAILURES = []


def check(ok, msg):
    print(f"# {'ok' if ok else 'FAIL'}: {msg}", flush=True)
    if not ok:
        FAILURES.append(msg)
    return ok


def build_engine(model, **kw):
    reg = ModelRegistry()
    reg.register("lm", model)
    kw.setdefault("slots", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_prompt", 24)
    kw.setdefault("max_new_tokens", 32)
    return DecodeEngine(reg, "lm", **kw)


def leg_churn(model):
    rng = np.random.RandomState(0)
    eng = build_engine(model, slots=6)
    eng.warmup()
    reqs = [(rng.randint(0, 256, rng.randint(1, 25)).astype(np.int32),
             int(rng.randint(2, 25))) for _ in range(30)]
    futs = []
    for i, (p, n) in enumerate(reqs):
        futs.append(eng.submit("lm", p, max_new_tokens=n))
        if i % 7 == 3:
            time.sleep(0.01)        # stagger: genuine join/leave churn
    outs = [f.result(180) for f in futs]
    rec = eng.recorder
    check(all(len(o) == len(p) + n for o, (p, n) in zip(outs, reqs)),
          "churn: all 30 mixed-length requests completed at full length")
    check(rec.counter_value("decode/recompiles") == 0,
          "churn: zero post-warmup recompiles under mixed prompts + churn")
    check(rec.counter_value("decode/warmup_compiles") > 0,
          "churn: warmup actually compiled the ladder")
    stats = eng.stats()
    eng.shutdown()
    return stats


def leg_throughput(model):
    """Static waves vs continuous stream over the same seeded workload,
    same engine.  Mixed output lengths: most replies short, some long
    (the production mix that makes static batching idle on stragglers).
    """
    rng = np.random.RandomState(1)
    slots, waves = 8, 4
    reqs = []
    for _ in range(slots * waves):
        out = 2 if rng.rand() < 0.75 else int(rng.randint(40, 49))
        reqs.append((rng.randint(0, 256, rng.randint(4, 17))
                     .astype(np.int32), out))
    tokens_total = sum(n for _, n in reqs)
    eng = build_engine(model, slots=slots, max_context=64)
    eng.warmup()

    def run_static():
        t0 = time.perf_counter()
        for w in range(waves):
            futs = [eng.submit("lm", p, max_new_tokens=n)
                    for p, n in reqs[w * slots:(w + 1) * slots]]
            for f in futs:          # static semantics: the whole wave
                f.result(180)       # waits for its slowest member
        return time.perf_counter() - t0

    def run_continuous():
        t0 = time.perf_counter()
        futs = [eng.submit("lm", p, max_new_tokens=n) for p, n in reqs]
        for f in futs:
            f.result(180)
        return time.perf_counter() - t0

    # interleave the protocols twice to cancel cache-warmth drift
    s1 = run_static(); c1 = run_continuous()
    s2 = run_static(); c2 = run_continuous()
    static_s, cont_s = min(s1, s2), min(c1, c2)
    static_tps = tokens_total / static_s
    cont_tps = tokens_total / cont_s
    ratio = cont_tps / static_tps
    check(eng.recorder.counter_value("decode/recompiles") == 0,
          "throughput: zero recompiles across both protocols")
    check(ratio >= 1.5,
          f"throughput: continuous {cont_tps:.0f} tok/s >= 1.5x static "
          f"{static_tps:.0f} tok/s (ratio {ratio:.2f})")
    stats = eng.stats()
    eng.shutdown()
    return {
        "recompiles": int(stats["recompiles"]),
        "requests": len(reqs), "tokens": tokens_total,
        "static_wall_s": round(static_s, 3),
        "continuous_wall_s": round(cont_s, 3),
        "static_tokens_per_s": round(static_tps, 1),
        "continuous_tokens_per_s": round(cont_tps, 1),
        "speedup": round(ratio, 3),
        "occupancy_mean": round(stats["occupancy"], 4),
        "ttft_p99_ms": stats.get("ttft_p99_ms"),
        "intertoken_p99_ms": stats.get("intertoken_p99_ms"),
    }


def leg_metrics(model):
    eng = build_engine(model, slots=4)
    eng.warmup()
    rng = np.random.RandomState(2)
    futs = [eng.submit("lm", rng.randint(0, 256, 6).astype(np.int32),
                       max_new_tokens=8) for _ in range(6)]
    for f in futs:
        f.result(60)
    server = eng.serve_metrics(port=0)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=10
    ).read().decode()
    for family in ("decode_ttft_ms", "decode_intertoken_ms",
                   "decode_tokens", "decode_steps", "kv_pool_fill",
                   "kv_page_allocs"):
        check(family in body,
              f"metrics: per-token SLO family {family} on /metrics")
    recompiles = int(eng.recorder.counter_value("decode/recompiles"))
    eng.shutdown()
    return recompiles


def leg_weight_stream():
    mesh = mesh_lib.create_mesh({"dp": 1})
    model = T.build("tiny", dropout=0.0, n_layers=2, max_len=128)
    trainer = SpmdTrainer(model, SGD(learning_rate=0.05),
                          mesh=mesh).init()
    golden = np.random.RandomState(3).randint(0, 256, (6,)) \
        .astype(np.int32)
    rs = build_decode_replica_set(
        model, 2, name="lm", probe_prompt=golden,
        engine_kw=dict(slots=2, page_size=8, max_context=48,
                       max_prompt=16, max_new_tokens=8))
    rs.warmup()
    # default drift config: integer golden outputs (token ids) skip the
    # magnitude-drift gate — validation for decode canaries is the
    # finite-logits gate (a poisoned model FAILS the golden decode)
    pub = CanaryPublisher(rs, {"lm": golden}, quiesce_timeout=30.0)
    wsp = WeightStreamPublisher(pub, "lm", every_steps=4, sync=True)
    trainer.set_weight_stream(wsp)

    errors = []
    stop = threading.Event()

    def client():
        rng = np.random.RandomState(4)
        while not stop.is_set():
            p = rng.randint(0, 256, rng.randint(2, 10)).astype(np.int32)
            try:
                # through the SET's rotation: a quiesced canary is out
                # of rotation, so clients never see a staged snapshot
                rs.predict("lm", p, timeout=60)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

    th = threading.Thread(target=client, daemon=True)
    th.start()

    rng = np.random.RandomState(5)

    def batches(n):
        for _ in range(n):
            toks = rng.randint(0, 256, (4, 17)).astype(np.int32)
            yield toks[:, :-1], toks[:, 1:]

    trainer.fit(batches(13), steps=13)
    wsp.wait(60)
    published = wsp.recorder.counter_value("stream/published")
    check(published >= 2, f"stream: {published:.0f} Trigger-fired "
                          "publishes from the live trainer")
    check(wsp.last_published is not None, "stream: snapshot recorded")

    # BITWISE: what the replica set decodes now == an independent
    # decode engine loaded with the trainer's published snapshot
    version, snap_params = wsp.last_published
    served = np.asarray(rs.replicas[0].engine.predict(
        "lm", golden, timeout=60))
    vreg = ModelRegistry()
    vreg.register("lm", model)
    vreg.swap_weights("lm", snap_params, version=version)
    ver = DecodeEngine(vreg, "lm", slots=2, page_size=8, max_context=48,
                       max_prompt=16, max_new_tokens=8).warmup()
    independent = np.asarray(ver.predict("lm", golden, timeout=60))
    ver.shutdown()
    check(np.array_equal(served, independent),
          f"stream: post-publish decode output bitwise matches the "
          f"trainer's snapshot ({version})")

    # poisoned publish: canary-rejected, bit-identical rollback, zero
    # client errors throughout
    poison = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32) * np.nan, snap_params)
    rejected = False
    try:
        pub.publish("lm", poison)
    except CanaryRejectedError:
        rejected = True
    check(rejected, "stream: NaN-poisoned publish canary-rejected")
    rolled = np.asarray(rs.replicas[0].engine.predict(
        "lm", golden, timeout=60))
    check(np.array_equal(served, rolled),
          "stream: rollback is bit-identical (same snapshot serving)")
    stop.set()
    th.join(30)
    check(not errors,
          f"stream: zero client errors through publishes + poisoned "
          f"rollback ({len(errors)} seen)" +
          (f" first: {errors[0]}" if errors else ""))
    recompiles = sum(int(r.engine.recorder.counter_value(
        "decode/recompiles")) for r in rs.replicas)
    rs.shutdown()
    return {"published": int(published),
            "canary_rejected": int(rs.recorder.counter_value(
                "serving/canary_rejected")),
            "client_errors": len(errors),
            "recompiles": recompiles}


def main():
    t0 = time.time()
    model = T.build("tiny", dropout=0.0, n_layers=2, max_len=128)
    model.ensure_initialized()
    churn_stats = leg_churn(model)
    bench = leg_throughput(model)
    metrics_recompiles = leg_metrics(model)
    stream = leg_weight_stream()
    # MEASURED across every leg's engines — a hardcoded 0 would make
    # CI's zero-recompile assert vacuous
    recompiles_total = (int(churn_stats["recompiles"])
                        + bench["recompiles"] + metrics_recompiles
                        + stream["recompiles"])
    check(recompiles_total == 0,
          f"all legs: zero post-warmup recompiles ({recompiles_total})")

    summary = {
        "metric": "decode_smoke",
        "platform": "cpu",
        "ok": not FAILURES,
        "failures": FAILURES,
        "speedup": bench["speedup"],
        "decode_throughput": bench,
        "churn": {k: churn_stats.get(k) for k in
                  ("requests", "steps", "tokens", "occupancy")},
        "recompiles": recompiles_total,
        "published": stream["published"],
        "canary_rejected": stream["canary_rejected"],
        "client_errors": stream["client_errors"],
        "wall_s": round(time.time() - t0, 1),
    }
    print(json.dumps(summary), flush=True)
    sys.exit(0 if not FAILURES else 1)


if __name__ == "__main__":
    main()
