"""Runner `serve_decode_sparse_moe`: `serve_decode.Runner`'s window, load
generator and results for a model of grouped KV heads, a sparse-attention
indexer and routed experts.  What differs: the model and its weights (a
layer at a time, `sparse_moe_program`), the reference the served tokens
are judged by (`sparse_moe_ref`, walking the layers), the warm-up (one
chunk program, whatever the prompt), and the counters the new layers
bring.
"""
import bisect
import threading
import time

import numpy as np
import jax

from benchmarks import harness, loadgen
from benchmarks.metrics import _sparse_moe, _ticks
from benchmarks.reference import sparse_moe_ref as ref
from benchmarks.runners import serve_decode, sparse_moe_program

COUNTERS = {"prefills": "decode/prefills",
            "prefill_chunks": "decode/prefill_chunks",
            "recompiles": "decode/recompiles", "steps": "decode/steps",
            "tokens": "decode/tokens", "moe_pairs": "moe/pairs",
            "moe_experts_touched": "moe/experts_touched",
            "moe_expert_load_max": "moe/expert_load_max",
            "sparse_rows_live": "sparse/rows_live",
            "sparse_rows_scored": "sparse/rows_scored",
            "sparse_rows_attended": "sparse/rows_attended"}
SAMPLE_EVERY_S = 0.1
TRACE_LEAD_CHUNKS = 24        # 1.7 s of chunks before the first token


class Runner(serve_decode.Runner):
    def _weights(self):
        self.key = harness.seed_key(self.seed)
        return sparse_moe_program.program_tree(self.cfg, self.key, self.model)

    def build_engine(self):
        from bigdl_tpu.serving import DecodeEngine, ModelRegistry
        self.model = sparse_moe_program.build_model(self.cfg)
        self.model.set_params(self._weights(), {})
        reg = ModelRegistry()
        reg.register("lm", self.model)
        self.probe.mark("weights")
        self.engine = DecodeEngine(reg, "lm", **self.tr["engine"])
        self.engine.warmup()
        self.engine.bench_key = self.key
        self.probe.mark("engine_warmup")
        return self.engine

    def adopt_engine(self, engine):
        """tools/calibrate.py, tools/controls_sparse_moe.py: a warm engine
        as it is.  Its weights stay
        (a second set does not fit beside the first on the chip), so the
        key that the comparison makes the reference's from is theirs."""
        self.model, self.key = engine.model, engine.bench_key
        self.engine = engine

    def warm(self):
        """Through the whole client path before the window: the longest
        prompt the mix can send and the shortest (one chunk program,
        whatever the prompt)."""
        rng = np.random.default_rng([self.seed, 3])
        for n in (self.tr["prompt_len"]["max"], self.tr["prompt_len"]["min"]):
            prompt = rng.integers(0, self.cfg["vocab_size"], n, dtype=np.int32)
            if len(list(self.start_stream(prompt, 4).tokens())) != 4:
                raise RuntimeError(f"warm-up at {n} tokens fell short")

    def _counters(self):
        rec = self.engine.recorder
        out = {k: rec.counter_value(name) for k, name in COUNTERS.items()}
        out["prefill_s"] = rec.span_value("decode.prefill")
        return out

    def _trace_on_first_reply(self, schedule, before):
        """Traced runs only.  The harness traces seconds 2 to 5 of the
        window, and at a fraction of a request a second the first document
        may be due later than that, or still be in its chunks: a trace with
        no decode step in it gives the readers nothing.  The traffic stays
        as `loadgen.make_schedule` draws it; the trace waits instead, until
        the first document (first come, first served, a chunk a tick) is
        within `TRACE_LEAD_CHUNKS` chunks of its first token, and then
        takes the harness's own three seconds: the document's last chunks,
        then its reply's steps (64 tokens at the least)."""
        chunk = self.tr["engine"]["prefill_chunk"]
        first = -(-len(schedule[0][1]) // chunk)
        wait_for = before["prefill_chunks"] + max(first - TRACE_LEAD_CHUNKS, 1)
        rec, probe, traced = self.engine.recorder, self.probe, \
            self.probe._trace_some

        def later():
            while rec.counter_value(COUNTERS["prefill_chunks"]) < wait_for \
                    and probe.t_close is None:
                time.sleep(0.005)
            harness.TRACE_START_S = 0.0
            traced()
        probe._trace_some = later

    def drive(self):
        """`serve_decode.Runner.drive`, and in a traced run (only there: an
        untraced run's tails are the cell's end-to-end metrics) the trace
        waits for the first reply and the counters are sampled ten times a
        second, so that the device-trace readers can set the traced
        seconds against the steps, experts and rows of those seconds."""
        schedule = loadgen.make_schedule(self.tr, self.seed, self.seconds,
                                         self.cfg["vocab_size"])
        self.warm()
        self.samples, stop = [], threading.Event()

        def sample():
            while not stop.wait(SAMPLE_EVERY_S):
                self.samples.append((time.perf_counter(), self._counters()))
        before = self._counters()
        if self.probe.trace and schedule:
            self._trace_on_first_reply(schedule, before)
            threading.Thread(target=sample, daemon=True).start()
        t0 = self.probe.window_open()
        with jax.profiler.TraceAnnotation("bench.open_loop"):
            self.reqs = loadgen.run_open_loop(
                schedule, self.start_stream, t0, self.tr["drain_s"])
        self.probe.window_close()
        stop.set()
        after = self._counters()
        self.delta = {k: after[k] - before[k] for k in after}
        self.stats = self.engine.stats()

    def results(self):
        out = super().results()
        f = out["facts"]
        f["chunk_ms_mean"] = 1e3 * f["prefill_s"] / max(f["prefill_chunks"], 1)
        f["gaps_with_chunk_share"] = self._gaps_with_chunk()
        f["attn_route"] = self.stats["attn_route"]
        # on every run's line as `observed` (check): TTFT's tail over ten
        # requests is its second longest and the chunks' share of the gaps
        # says where the 95th percentile sits, so neither is a metric
        self.observed = {k: float(f[k]) for k in (
            "gaps_with_chunk_share", "ttft_ms_p50", "ttft_ms_p95")}
        if self.probe.trace:
            f["counter_samples"] = self.samples
            f["op_scopes"] = _sparse_moe.op_scopes(
                self.engine._programs[("decode", None)].as_text())
        return out

    def _gaps_with_chunk(self):
        """The share of gaps between two tokens of a request in which a
        prefill chunk ran (by the chunk's midpoint): near 5% the 95th
        percentile sits on the edge between two kinds of gap."""
        off = _ticks.clock_offset()
        mids = sorted((s.t0 + s.t1) / 2.0 - off
                      for s in _ticks.default_store().spans()
                      if s.name == "decode.prefill")
        gaps = [(a, b) for r in self.reqs
                for a, b in zip(r.stamps, r.stamps[1:])]
        hit = sum(1 for a, b in gaps
                  if bisect.bisect_right(mids, b) > bisect.bisect_left(mids, a))
        return hit / max(len(gaps), 1)

    def gap_table(self, variants=None, controls_on=None):
        """For each sampled request (shortest first), `ref.choice_gaps`:
        the served tokens' gaps under "served", the reference's own first
        choices' through bfloat16 under "bf16", and each of `variants`
        {name: Variant} on the `controls_on` shortest (all by default; a
        variant's forward costs as much as the reference's)."""
        picks = sorted(self.sample(), key=lambda r: len(r.prompt))
        table = []
        for i, r in enumerate(picks):
            seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            extra = variants if variants and (
                controls_on is None or i < controls_on) else {}
            table.append(ref.choice_gaps(
                self.cfg, self.key, seq, len(r.prompt),
                dict(extra, bf16=ref.OWN_PRECISION),
                self.tr["reference_pad_to"]))
        return table

    @staticmethod
    def gap_over_bf16(table, name="served"):
        """Mean gap of `name`'s tokens over the mean gap of the bfloat16
        reference's, on the requests of `table` that hold both."""
        rows = [t for t in table if name in t]
        if not rows:
            return float("inf")
        own = float(np.concatenate([t["bf16"] for t in rows]).mean())
        got = float(np.concatenate([t[name] for t in rows]).mean())
        return got / own if own > 0 else (0.0 if got == 0 else float("inf"))

    def check(self, variant=None):
        """The served tokens of the sampled requests against the plain
        reference.  A model of seeded weights is sure of little: the
        float32 reference's best token leads its second by a seventh of a
        logit at the median, and top-k selections (2,048 keys of
        thousands, 8 experts of 128) turn bfloat16's rounding into
        discrete changes, so a SOUND bfloat16 program serves a token other
        than the reference's best at an eighth or more of its positions,
        and more the longer the context.  `logit_gap_max` alone, the chat
        cell's number, cannot tell that from a fault.  What is compared is
        therefore set against the same sequence's own bfloat16 noise:
        the mean gap of the served tokens over the mean gap of the tokens
        the reference itself puts first when its matmul operands are
        rounded to bfloat16, `logit_gap_over_bf16`.  A sound program reads
        about 1 whatever the seed and the context; a precision below, a
        selection left out or drawn at random, and gates not renormalised
        each read several times that (PERF.md section 6, PR 28).
        `logit_gap_max` is held too, against a gross fault (a token drawn
        at random lies about 4 under the best).  `variant` (the controls:
        tests and tools/controls_sparse_moe.py) judges that Variant's own
        first choices in the served tokens' place."""
        never = sum(1 for r in self.reqs if r.error == "never finished")
        name = "served" if variant is None else "control"
        table = self.gap_table(None if variant is None else {name: variant})
        return self.compared(table, name, never)

    def compared(self, table, name, never):
        lim = self.tr["limits"]
        gaps = np.concatenate([t[name] for t in table if name in t]) \
            if any(name in t for t in table) else np.asarray([np.inf])
        return [{"name": "logit_gap_over_bf16",
                 "value": self.gap_over_bf16(table, name),
                 "limit": float(lim["logit_gap_over_bf16"]),
                 "tokens": int(gaps.size)},
                {"name": "logit_gap_max", "value": float(gaps.max()),
                 "limit": float(lim["logit_gap_max"])},
                {"name": "never_finished", "value": float(never),
                 "limit": 0.0},
                {"name": "logit_gap_mean", "value": float(gaps.mean()),
                 "limit": None}] \
            + [{"name": k, "value": v, "limit": None}
               for k, v in getattr(self, "observed", {}).items()]
