"""Runner `serve_decode_window_moe`: the window, load generator, chunk
facts and comparison of `serve_decode_sparse_moe.Runner` (itself
`serve_decode.Runner`'s) for a model whose layers are of two kinds,
sliding-window and global attention over grouped KV heads, with routed
ReGLU experts.  What differs: the model and its weights (a layer at a
time, `window_moe_program`), the reference the served tokens are judged
by (`window_moe_ref`), which requests are judged (the longest three first:
a window, a ring and its stale rows only show past 4,096 tokens), where
the reference's own rounding noise is read (`NOISE_ROWS` positions a
request, not the served ones alone), the
counters and gauges the two kinds of layer bring, and when a traced run's
trace starts (mid-window, at the queue's own occupancy).
"""
import time

import numpy as np

from benchmarks import harness
from benchmarks.metrics import _window_moe
from benchmarks.reference import window_moe_ref as ref
from benchmarks.runners import serve_decode_sparse_moe, window_moe_program

COUNTERS = {"prefills": "decode/prefills",
            "prefill_chunks": "decode/prefill_chunks",
            "recompiles": "decode/recompiles", "steps": "decode/steps",
            "tokens": "decode/tokens", "moe_pairs": "moe/pairs",
            "moe_experts_touched": "moe/experts_touched",
            "moe_expert_load_max": "moe/expert_load_max",
            "attn_rows_live": "attn/rows_live",
            "attn_rows_attended_window": "attn/rows_attended_window",
            "attn_rows_attended_global": "attn/rows_attended_global",
            "kv_pages_recycled": "kv/pages_recycled"}
GAUGES = {"kv_pages_in_use_window": "kv/pages_in_use_window",
          "kv_pages_in_use_global": "kv/pages_in_use_global"}
PAST_WINDOW_CHECKED = 3       # the longest requests, judged first
PAST_WINDOW_NEEDED = 2        # judged requests past the ring, at the least
NOISE_ROWS = 1024             # the longest reply the mix allows


class Runner(serve_decode_sparse_moe.Runner):
    def _weights(self):
        self.key = harness.seed_key(self.seed)
        return window_moe_program.program_tree(self.cfg, self.key, self.model)

    def build_engine(self):
        from bigdl_tpu.serving import DecodeEngine, ModelRegistry
        self.model = window_moe_program.build_model(self.cfg)
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
        """The counters, and with them the two kinds' page gauges: levels,
        which the traced run's sampler wants and `results` takes out of
        the window's deltas again."""
        rec = self.engine.recorder
        out = {k: rec.counter_value(name) for k, name in COUNTERS.items()}
        out.update((k, rec.gauge_value(name)) for k, name in GAUGES.items())
        out["prefill_s"] = rec.span_value("decode.prefill")
        return out

    def _trace_on_first_reply(self, schedule, before):
        """Traced runs only.  The window opens on an empty engine and the
        queue takes most of it to reach its own occupancy (a reply stays
        some twenty seconds), so the harness's three traced seconds start
        half-way through the window, not two seconds in: the decode steps
        they hold then carry the slots, experts and rows that the cell's
        tails are made of.  The traffic stays as `loadgen.make_schedule`
        draws it."""
        probe, traced = self.probe, self.probe._trace_some
        start_in = self.seconds / 2.0

        def later():
            while probe.t_open is None or (
                    time.perf_counter() < probe.t_open + start_in
                    and probe.t_close is None):
                time.sleep(0.005)
            harness.TRACE_START_S = 0.0
            traced()
        probe._trace_some = later

    def results(self):
        out = super().results()
        f = out["facts"]
        f["kv_kinds"] = self.stats["kv_kinds"]
        f["chunk_attn_route"] = self.stats["chunk_attn_route"]
        for k in GAUGES:                  # levels: their delta says nothing
            f.pop(k, None)
        if self.probe.trace:
            f["op_scopes"] = _window_moe.op_scopes(
                self.engine._programs[("decode", None)].as_text())
        return out

    def sample(self):
        """The finished requests that are judged: the `PAST_WINDOW_CHECKED`
        longest (what tells a window from none, and a ring's stale rows
        from its live ones, lies past the window), and a seeded draw of
        the rest."""
        done = sorted((r for r in self.reqs if r.ok),
                      key=lambda r: -(len(r.prompt) + len(r.tokens)))
        longest, rest = done[:PAST_WINDOW_CHECKED], done[PAST_WINDOW_CHECKED:]
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
        prompt's end before them, so that the noise a reply of 145 tokens
        is set against is not itself a mean of nine gaps); and each of
        `variants` {name: Variant} on the `controls_on` longest (all by
        default; a variant's forward costs as much as the reference's),
        at the served positions.  Each row also says how long the request
        was (`n_tokens`)."""
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
        """`ref.controls` at the served engine's page size and ring."""
        eng = self.tr["engine"]
        ring = self.stats["kv_kinds"].get("window", {}).get("pages_per_slot")
        return ref.controls(eng["page_size"], ring or 1)

    def compared(self, table, name, never):
        """The parent's numbers, and `logit_gap_over_bf16` once more over
        the requests past the window alone (the ring has wrapped there:
        `ring x page` tokens), where a control that only shows there is
        not diluted by the short requests' tokens; fewer than
        `PAST_WINDOW_NEEDED` such requests judged is itself a failure."""
        out = super().compared(table, name, never)
        kinds = self.stats["kv_kinds"]
        ring_rows = kinds["window"]["pages_per_slot"] \
            * self.tr["engine"]["page_size"] if "window" in kinds else 0
        past = [t for t in table if t["n_tokens"] > ring_rows]
        lim = self.tr["limits"]
        return out[:3] + [
            {"name": "logit_gap_over_bf16_past_window",
             "value": self.gap_over_bf16(past, name),
             "limit": float(lim["logit_gap_over_bf16_past_window"]),
             "requests": len(past)},
            {"name": "past_window_unjudged",
             "value": float(max(PAST_WINDOW_NEEDED - len(past), 0)),
             "limit": 0.0}] + out[3:]
