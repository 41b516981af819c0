"""Runner `serve_decode_mla_moe`: the window, load generator, chunk facts
and comparison of `serve_decode_sparse_moe.Runner` (itself
`serve_decode.Runner`'s) for a model of latent attention and routed
experts of which the chip holds a share.  What differs: the model and its
weights (a layer at a time, `mla_moe_program`), the reference the served
tokens are judged by (`mla_moe_ref`), which requests are judged (the
longest three first: a scale, a rope or a cached row computed wrongly
shows most over thousands of keys), where the reference's own rounding
noise is read (`NOISE_ROWS` positions a request, not the served ones
alone), the counters the latent cache and the held share bring, and when
a traced run's trace starts (mid-window, at the queue's own occupancy).
"""
import numpy as np

from benchmarks import harness
from benchmarks.metrics import _mla_moe
from benchmarks.reference import mla_moe_ref as ref
from benchmarks.runners import (mla_moe_program, serve_decode_sparse_moe,
                                serve_decode_window_moe)

COUNTERS = {"prefills": "decode/prefills",
            "prefill_chunks": "decode/prefill_chunks",
            "recompiles": "decode/recompiles", "steps": "decode/steps",
            "tokens": "decode/tokens", "moe_pairs": "moe/pairs",
            "moe_pairs_routed": "moe/pairs_routed",
            "moe_prefill_pairs": "moe/prefill_pairs",
            "moe_prefill_pairs_routed": "moe/prefill_pairs_routed",
            "moe_experts_touched": "moe/experts_touched",
            "moe_expert_load_max": "moe/expert_load_max",
            "mla_rows_live": "mla/rows_live",
            "mla_chunk_rows_visible": "mla/chunk_rows_visible",
            "mla_chunk_rows_live": "mla/chunk_rows_live"}
LONGEST_CHECKED = 3           # the longest requests, judged first
NOISE_ROWS = 512              # the longest reply the mix allows


class Runner(serve_decode_sparse_moe.Runner):
    def _weights(self):
        self.key = harness.seed_key(self.seed)
        return mla_moe_program.program_tree(self.cfg, self.key, self.model)

    def build_engine(self):
        from bigdl_tpu.serving import DecodeEngine, ModelRegistry
        self.model = mla_moe_program.build_model(self.cfg)
        self.model.set_params(self._weights(), {})
        reg = ModelRegistry()
        reg.register("lm", self.model)
        self.probe.mark("weights")
        self.engine = DecodeEngine(reg, "lm", **self.tr["engine"])
        self.engine.warmup()
        self.engine.bench_key = self.key
        self.probe.mark("engine_warmup")
        return self.engine

    def _counters(self):
        rec = self.engine.recorder
        out = {k: rec.counter_value(name) for k, name in COUNTERS.items()}
        out["prefill_s"] = rec.span_value("decode.prefill")
        return out

    # traced runs only: the harness's three traced seconds start half-way
    # through the window (the mixed cell's rule, for its reason: the window
    # opens on an empty engine, and a request is a second or more of chunks
    # before its first token), so that they hold decode steps at the
    # queue's own occupancy and the chunks between them
    _trace_on_first_reply = \
        serve_decode_window_moe.Runner._trace_on_first_reply

    def results(self):
        out = super().results()
        f = out["facts"]
        f["kv_kinds"] = self.stats["kv_kinds"]
        f["chunk_attn_route"] = self.stats["chunk_attn_route"]
        if self.probe.trace:
            programs = self.engine._programs
            f["op_scopes"], f["op_scopes_ambiguous"] = _mla_moe.op_scopes(
                programs[("decode", None)].as_text(),
                programs[("chunk", None)].as_text())
        return out

    def sample(self):
        """The finished requests that are judged: the `LONGEST_CHECKED`
        longest, and a seeded draw of the rest."""
        done = sorted((r for r in self.reqs if r.ok),
                      key=lambda r: -(len(r.prompt) + len(r.tokens)))
        longest, rest = done[:LONGEST_CHECKED], done[LONGEST_CHECKED:]
        rng = np.random.default_rng([self.seed, 7])
        n = max(min(self.tr["checked_requests"] - len(longest), len(rest)), 0)
        return longest + [rest[i] for i in
                          rng.choice(len(rest), n, replace=False)]

    def gap_table(self, variants=None, controls_on=None):
        """For each judged request, longest first, `ref.choice_gaps`: the
        served tokens' gaps under "served"; under "bf16" the first
        choices' of the reference in the configuration's own precision,
        at the last `NOISE_ROWS` positions of the sequence (teacher-forced:
        the served positions and, where the reply is shorter, the
        prompt's end before them); and each of `variants` {name: Variant}
        on the `controls_on` longest (all by default; a variant's forward
        costs as much as the reference's), at the served positions.  Each
        row also says how long the request was (`n_tokens`)."""
        picks = sorted(self.sample(),
                       key=lambda r: -(len(r.prompt) + len(r.tokens)))
        table = []
        for i, r in enumerate(picks):
            seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            extra = variants if variants and (
                controls_on is None or i < controls_on) else {}
            row = ref.choice_gaps(
                self.cfg, self.key, seq, len(r.prompt),
                dict(extra, bf16=ref.OWN_PRECISION),
                self.tr["reference_pad_to"], ("bf16",), NOISE_ROWS)
            row["n_tokens"] = len(seq)
            table.append(row)
        return table

    def controls(self):
        return ref.controls()
