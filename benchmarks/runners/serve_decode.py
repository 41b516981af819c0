"""Runner `serve_decode`: ModelRegistry -> DecodeEngine.warmup() ->
DecodeEngine.stream() per request, from an open loop at a fixed rate.

The benchmark is the client: time to first token and the gaps between
tokens are taken where `stream.tokens()` hands them over, each request
timed from when it was due.  Afterwards a seeded sample of the finished
requests, the longest among them, is run once through the plain float32
reference: prompt plus served tokens, and for every served token how far
its reference logit lies below the reference's best.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import harness, loadgen
from benchmarks.reference import lm_ref
from benchmarks.runners import lm_program


class Runner:
    def __init__(self, cell, seed, seconds, devices, probe, scale):
        self.cfg = dict(cell.config, **scale.get("config", {}))
        self.tr = dict(cell.traffic, **scale.get("traffic", {}))
        self.seed, self.seconds = seed, seconds
        self.devices, self.probe = devices, probe

    def build_engine(self):
        from bigdl_tpu.serving import DecodeEngine, ModelRegistry
        cfg = self.cfg
        self.key = harness.seed_key(self.seed)
        model = lm_program.build_model(cfg)
        dtype = jnp.dtype(cfg["param_dtype"])
        plain = jax.jit(lambda k: lm_ref.make_weights(cfg, k, dtype))(self.key)
        model.set_params(lm_program.to_program_tree(plain, model), {})
        del plain
        reg = ModelRegistry()
        reg.register("lm", model)
        self.probe.mark("weights")
        self.engine = DecodeEngine(reg, "lm", **self.tr["engine"])
        self.engine.warmup()
        self.probe.mark("engine_warmup")
        return self.engine

    def adopt_engine(self, engine):
        """tools/readings.py only: this seed's weights into an engine that
        is already warm (the registry's own hot swap), so a dozen seeds
        cost one warm-up."""
        cfg = self.cfg
        self.key = harness.seed_key(self.seed)
        dtype = jnp.dtype(cfg["param_dtype"])
        plain = jax.jit(lambda k: lm_ref.make_weights(cfg, k, dtype))(self.key)
        engine.registry.swap_weights("lm", params=lm_program.to_program_tree(
            plain, engine.model))
        self.engine = engine

    def start_stream(self, prompt, max_new):
        return self.engine.stream("lm", prompt, max_new_tokens=max_new,
                                  temperature=self.tr["temperature"])

    def _counters(self):
        rec = self.engine.recorder
        return {"prefill_s": rec.span_value("decode.prefill"),
                "prefills": rec.counter_value("decode/prefills"),
                "recompiles": rec.counter_value("decode/recompiles"),
                "steps": rec.counter_value("decode/steps"),
                "tokens": rec.counter_value("decode/tokens")}

    def run(self):
        self.build_engine()
        self.drive()

    def warm(self):
        """Through the whole client path before the window, once for every
        prefill bucket the mix can reach: the first stream starts the
        decode thread, and a program's first execution is slower than its
        later ones (a run that compiled read TTFT p95 180 ms against 44
        with one warm request only; my chip runs, PR 24)."""
        lo, hi = self.tr["prompt_len"]["min"], self.tr["prompt_len"]["max"]
        rng = np.random.default_rng([self.seed, 3])
        for bucket in self.engine.ladder:
            if lo <= bucket <= hi or bucket >= hi > bucket // 2:
                prompt = rng.integers(0, self.cfg["vocab_size"],
                                      min(bucket, hi), dtype=np.int32)
                warm = list(self.start_stream(prompt, 4).tokens())
                if len(warm) != 4:
                    raise RuntimeError(f"warm-up at bucket {bucket} gave "
                                       f"{len(warm)} tokens")

    def drive(self):
        eng = self.engine
        schedule = loadgen.make_schedule(self.tr, self.seed, self.seconds,
                                         self.cfg["vocab_size"])
        self.warm()
        before = self._counters()
        t0 = self.probe.window_open()
        with jax.profiler.TraceAnnotation("bench.open_loop"):
            self.reqs = loadgen.run_open_loop(
                schedule, self.start_stream, t0, self.tr["drain_s"])
        self.probe.window_close()
        after = self._counters()
        self.delta = {k: after[k] - before[k] for k in after}
        self.stats = eng.stats()

    def results(self):
        reqs, t_close = self.reqs, self.probe.t_close
        failed = [r for r in reqs if not r.ok]
        # a request that failed or never finished misses every limit: it
        # waits, for the tail's purposes, until the window closed
        ttft = [((r.stamps[0] if r.stamps else t_close) - r.due) * 1e3
                for r in reqs]
        gaps = [(b - a) * 1e3 for r in reqs
                for a, b in zip(r.stamps, r.stamps[1:])] \
            or [(t_close - self.probe.t_open) * 1e3]     # nothing served
        late = [(r.sent - r.due) * 1e3 for r in reqs]
        span = max((r.stamps[-1] for r in reqs if r.stamps),
                   default=t_close) - self.probe.t_open
        served = [(len(r.prompt), len(r.tokens)) for r in reqs]
        return {"end_to_end": {"ttft_ms_p95": loadgen.percentile(ttft, 95),
                               "tpot_ms_p95": loadgen.percentile(gaps, 95)},
                "attempted": len(reqs), "failed": len(failed),
                "facts": {"tpot_ms_p50": loadgen.percentile(gaps, 50),
                          "ttft_ms_p50": loadgen.percentile(ttft, 50),
                          "ttft_ms_p95": loadgen.percentile(ttft, 95),
                          "gen_late_ms_p95": loadgen.percentile(late, 95),
                          "served": served, "busy_span_s": span,
                          "engine": self.tr["engine"],
                          "mean_live_slots": self.delta["tokens"]
                          / max(self.delta["steps"], 1.0),
                          "errors": [r.error for r in failed][:5],
                          "config": self.cfg, **self.delta}}

    def release(self):
        self.engine.shutdown(drain=False, timeout=60)
        self.engine.registry.get("lm").model._params = None
        self.engine = None

    def sample(self):
        """The finished requests that are checked: the longest, and a
        seeded draw of the rest."""
        done = [r for r in self.reqs if r.ok]
        if not done:
            return []
        longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed, 7])
        n = min(self.tr["checked_requests"] - 1, len(rest))
        picks = [rest[i] for i in rng.choice(len(rest), n, replace=False)]
        return [longest] + picks

    def check(self, quant=None):
        picks = self.sample()
        never = sum(1 for r in self.reqs if r.error == "never finished")
        weights = jax.jit(lambda k: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), lm_ref.make_weights(
                self.cfg, k, jnp.dtype(self.cfg["param_dtype"]))))(self.key)
        worst, n_tok = 0.0, 0
        for r in picks:
            seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            gaps = lm_ref.token_gaps(weights, seq, len(r.prompt), self.cfg,
                                     quant)
            worst = max(worst, float(np.max(gaps)))
            n_tok += len(gaps)
        if not picks:
            worst = float("inf")
        lim = self.tr["limits"]
        return [{"name": "logit_gap_max", "value": worst,
                 "limit": float(lim.get("logit_gap_max", 0.0)),
                 "tokens": n_tok},
                {"name": "never_finished", "value": float(never),
                 "limit": 0.0}]
