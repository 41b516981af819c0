"""The two cells of PR 28 at toy sizes on the CPU: the sparse-attention /
routed-expert serving cell through its own runner (`correct` true; false
with the selection bypassed underneath, and with the gates left
unnormalised), the owed four-chip FSDP cell on four virtual devices, the
FLOP and byte counts against a hand count, and the new readers on a
synthetic trace."""
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.flops import sparse_moe
from benchmarks.metrics import _sparse_moe
from _bench_common import SCALE, over

KEYE, FSDP = "keye30b-l6-serve-longdoc", "olmo1b-l8-train-fsdp4"
TOY_CFG = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
               max_position_embeddings=256, init_qk_norm_gain=1.4,
               init_embed_gain=1.0, init_router_gain=1.0,
               sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                              indexer_num_kv_heads=1, topk=8),
               # float32, so that the sound toy run reads a gap of zero
               activation_dtype="float32", param_dtype="float32")
SCALES = {
    KEYE: {"config": TOY_CFG,
           "traffic": dict(
               engine={"slots": 2, "page_size": 4, "max_context": 64,
                       "max_prompt": 40, "prefill_chunk": 4,
                       "max_new_tokens": 8},
               arrivals={"shape": "steady", "rate_per_s": 4.0,
                         "draw_seed": 5},
               prompt_len={"median": 20, "sigma": 0.4, "min": 10, "max": 40},
               output_len={"median": 6, "sigma": 0.4, "min": 2, "max": 8},
               drain_s=120.0, checked_requests=4, reference_pad_to=8,
               limits={"logit_gap_over_bf16": 0.0, "logit_gap_max": 10.0})},
    FSDP: SCALE["olmo1b-l8-train"],
}


def run(name, seconds=1.5):
    import jax
    with jax.default_matmul_precision("highest"):
        return harness.run_cell(harness.Cell(name), 2 ** 31 + 23, seconds, 0,
                                require_chip=False, scale=SCALES[name])


@pytest.mark.parametrize("name", [KEYE, FSDP])
def test_new_cell_runs_and_is_correct(name):
    cell = harness.Cell(name)
    line = run(name)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want
    assert want == {"setup_s", {KEYE: "tpot_ms_p95",
                                FSDP: "tokens_per_s"}[name]}
    assert line["device"]["count"] == cell.chips == {KEYE: 1, FSDP: 4}[name]
    if name == KEYE:
        # TTFT and the chunks' share of the gaps: printed, not compared
        seen = line["observed"]
        assert 0.0 <= seen["gaps_with_chunk_share"] <= 1.0
        assert 0.0 < seen["ttft_ms_p50"] <= seen["ttft_ms_p95"]


def test_the_cells_files():
    cell = harness.Cell(KEYE)
    assert cell.traffic["runner"] == "serve_decode_sparse_moe"
    eng = cell.traffic["engine"]
    assert eng["max_prompt"] > eng["prefill_chunk"]        # the chunk route
    assert cell.traffic["prompt_len"]["min"] \
        >= 2 * cell.config["sa_config"]["topk"]
    names = [m["name"] for m in cell.per_layer]
    # its own nine and the three accepted readers that find something to
    # read in every traced run of it; not the `decode_tick.*` (the traced
    # window's head is one prompt's chunks and holds no step) and not
    # `serve_prefill_ms_mean` (a whole prompt's chunks, not one call)
    assert len(names) == 12 and "serve_prefill_ms_mean" not in names
    assert not [n for n in names if n.startswith("decode_tick.")]
    fsdp, one = harness.Cell(FSDP), harness.Cell("olmo1b-l8-train")
    assert fsdp.config == one.config and fsdp.traffic["mesh"] == {"fsdp": 4}
    same = ("runner", "optimizer", "limits", "setup_steps", "compared_steps",
            "seq_len", "loss_chunk")
    assert all(fsdp.traffic[k] == one.traffic[k] for k in same)
    assert [m["name"] for m in fsdp.per_layer] == ["lm_step.mfu",
                                                   "lm_compile.in_window"]


def test_selection_bypassed_underneath_is_not_correct(monkeypatch):
    """Dense attention over every live token in the decode step, where
    the model attends its top-k."""
    from bigdl_tpu.ops import paged_attention_mod as pa
    real = pa._index_topk

    def every_row(qi, w, ki_win, lengths, *, top_k):
        return real(qi, w, ki_win, lengths, top_k=ki_win.shape[1])
    monkeypatch.setattr(pa, "_index_topk", every_row)
    line = run(KEYE)
    assert line["correct"] is False \
        and over(line) == ["logit_gap_over_bf16"]


def test_gates_left_unnormalised_is_not_correct(monkeypatch):
    from jax import lax
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn import moe

    def route(self, params, xt):
        probs = jax.nn.softmax(jnp.dot(
            xt.astype(jnp.float32),
            self.own(params)["router"].astype(jnp.float32)), axis=-1)
        picked, idx = lax.top_k(probs, self.top_k)
        return idx, picked
    monkeypatch.setattr(moe.RoutedExperts, "route", route)
    line = run(KEYE)
    assert line["correct"] is False \
        and over(line) == ["logit_gap_over_bf16"]


@pytest.mark.parametrize("control", ["fp8", "dense", "random", "no_renorm"])
def test_control_in_the_programs_place_is_not_correct(control):
    """The reference computed wrongly (operands through fp8, the selection
    left out, the top-k drawn at random, gates not renormalised), judged
    through the runner's own `check` in the served tokens' place.  At this
    toy size (two layers, some twenty served tokens a run) a control puts
    another token first on most seeds, not on every one, so three are
    tried: the program is correct on each, the control is not on at least
    one.  At the cell's own size every control reads over the limit on
    every seed (PERF.md section 6, PR 28)."""
    import jax
    from benchmarks.reference import sparse_moe_ref as ref
    cell = harness.Cell(KEYE)
    failed = 0
    for seed in (7, 2 ** 31 + 23, 99):
        probe = harness.Probe(0.0, False, None)
        with jax.default_matmul_precision("highest"):
            r = cell.runner().Runner(cell, seed, 1.5, jax.devices()[:1],
                                     probe, SCALES[KEYE])
            r.run()
            r.results()
            r.release()
            assert harness.compared_ok(r.check())
            failed += not harness.compared_ok(r.check(ref.CONTROLS[control]))
    assert failed >= 1


def test_the_trace_waits_for_the_first_reply():
    """A traced run leaves the schedule as `loadgen.make_schedule` draws it
    and starts the harness's trace when the first document is within
    twenty-four chunks of its first token (here: after its first chunk)."""
    import jax
    from benchmarks import loadgen
    cell = harness.Cell(KEYE)
    probe = harness.Probe(0.0, False, None)
    r = cell.runner().Runner(cell, 7, 1.5, jax.devices()[:1], probe,
                             SCALES[KEYE])
    r.build_engine()
    started = []
    probe._trace_some = lambda: started.append(
        r.engine.recorder.counter_value("decode/prefill_chunks"))
    plan = loadgen.make_schedule(r.tr, 7, 1.5, r.cfg["vocab_size"])
    before = r._counters()
    r._trace_on_first_reply(plan, before)
    waiter = __import__("threading").Thread(target=probe._trace_some)
    waiter.start()
    waiter.join(0.2)
    assert waiter.is_alive() and not started       # nothing has run yet
    assert len(list(r.start_stream(plan[0][1], 2).tokens())) == 2
    waiter.join(30)
    r.release()
    assert started and started[0] >= before["prefill_chunks"] + 1
    assert harness.TRACE_START_S == 0.0
    harness.TRACE_START_S = 2.0


def test_runner_facts_and_counters():
    import jax
    cell = harness.Cell(KEYE)
    probe = harness.Probe(0.0, False, None)
    with jax.default_matmul_precision("highest"):
        r = cell.runner().Runner(cell, 7, 1.5, jax.devices()[:1], probe,
                                 SCALES[KEYE])
        r.run()
        f = r.results()["facts"]
        r.release()
    assert f["attn_route"] == "sparse" and f["recompiles"] == 0
    chunks = sum(-(-n // 4) for n, _ in f["served"])
    assert f["prefill_chunks"] == chunks > f["prefills"] > 0
    assert 0.0 <= f["gaps_with_chunk_share"] <= 1.0
    assert f["chunk_ms_mean"] > 0
    assert f["moe_pairs"] == f["tokens"] * 2 * 2       # top-2, two layers
    assert f["sparse_rows_attended"] < f["sparse_rows_live"]
    assert "counter_samples" not in f                  # traced runs only
    ctx = {"facts": f}
    share = cell.reader("kv_rows_attended_share").read(ctx)
    assert share == 100.0 * f["sparse_rows_attended"] / f["sparse_rows_live"]
    touched = cell.reader("moe_experts_touched_share").read(ctx)
    assert 0 < touched <= 100.0 * 2 / 8 * 2            # two slots, top-2
    assert cell.reader("moe_expert_load_max_over_mean").read(ctx) >= 1.0
    # a program with no such counters (the parent) gives the readers nothing
    bare = {"facts": {"served": f["served"], "config": {}, "steps": 3}}
    for m in ("kv_rows_attended_share", "moe_experts_touched_share",
              "moe_expert_load_max_over_mean", "sparse_moe_step.mfu"):
        assert cell.reader(m).read(bare) is None


# --------------------------------------------------------------------- #
# counts by hand
# --------------------------------------------------------------------- #
def test_flops_and_bytes_against_a_hand_count():
    cfg = dict(TOY_CFG, num_hidden_layers=2)
    p = sparse_moe.layer_params(cfg)
    # wq and wo 64 x 64 each, wk and wv 64 x 32 each
    assert p["attention"] == 2 * 64 * 64 + 2 * 64 * 32 == 12288
    # index queries 64 x 16, one index key 64 x 8, a weight a head 64 x 2
    assert p["indexer"] == 64 * (16 + 8 + 2) == 1664
    assert p["router"] == 64 * 8 and p["expert"] == 3 * 64 * 32 == 6144
    assert sparse_moe.param_count(cfg) \
        == 2 * (12288 + 1664 + 512 + 8 * 6144) + 2 * 128 * 64
    per_tok = 2 * (12288 + 1664 + 512 + 2 * 6144)
    assert sparse_moe.layer_flops_per_token(cfg) == per_tok == 53504
    # a query at context 5 attends 5 keys, at 20 its top-8; the indexer
    # scores them all: (2 x 2 x 8 + 2 x 2) a key
    assert sparse_moe.attention_flops(cfg, 5) == 4 * 64 * 5 + 36 * 5
    assert sparse_moe.attention_flops(cfg, 20) == 4 * 64 * 8 + 36 * 20
    # a request of 10 prompt tokens and 3 served: 12 tokens through the
    # layers at contexts 1..12, the head 3 times
    att = sum(sparse_moe.attention_flops(cfg, c) for c in range(1, 13))
    assert sparse_moe.sequence_flops(cfg, 10, 3) \
        == 2 * (12 * per_tok + att) + 3 * 2 * 64 * 128
    assert sparse_moe.index_topk_cost(cfg, 100) == (36 * 100, 100 * 8 * 2)
    assert sparse_moe.sparse_attend_cost(cfg, 16) \
        == (4 * 64 * 16, 2 * 2 * 16 * 16 * 2)
    assert sparse_moe.moe_experts_cost(cfg, 4, 3) \
        == (2 * 6144 * 4, (3 * 6144 + 2 * 4 * 64) * 2)
    # a step that touched 5 experts, scored 60 rows and attended 24, over
    # its two layers: non-expert weights and the head once
    assert sparse_moe.decode_step_bytes(cfg, 5, 60, 24) == 2 * (
        2 * (12288 + 1664 + 512) + 64 * 128 + 5 * 6144
        + 60 * 8 + 24 * 2 * 2 * 16)


def test_published_sizes_are_the_issues():
    cell = harness.Cell(KEYE)
    p = sparse_moe.layer_params(cell.config)
    layer = p["attention"] + p["indexer"] + p["router"] + 128 * p["expert"]
    assert round(layer / 1e6, 1) == 625.4              # ISSUE 28: 625.4 M
    assert round(sparse_moe.param_count(cell.config) / 1e9, 2) == 4.37
    eng = cell.traffic["engine"]
    per_token = 6 * (2 * 4 * 128 + 64) * 2             # K, V, index key
    assert per_token == 13056
    assert eng["slots"] * eng["max_context"] * per_token == 3422552064


# --------------------------------------------------------------------- #
# the device-trace readers on a synthetic trace
# --------------------------------------------------------------------- #
def test_op_keys_join_the_program_text_and_the_trace():
    hlo = (
        '  %fusion.7 = f32[8,32768]{1,0:T(8,128)} fusion(%a, %b), kind=kLoop, '
        'metadata={op_name="jit(fn)/jit(_index_topk)/top_k"}\n'
        '  %gmm.3 = bf16[64,768]{1,0:T(8,128)(2,1)} custom-call(%x), '
        'metadata={op_name="jit(fn)/jit(_moe_experts)/jit(gmm)/pallas_call"}\n'
        '  ROOT %fusion.9 = (s32[8]{0}, pred[8]{0}) fusion(%c), '
        'metadata={op_name="jit(fn)/argmax"}\n')
    scopes = _sparse_moe.op_scopes(hlo)
    assert scopes == {"%fusion.7 = f32[8,32768]": "_index_topk",
                      "%gmm.3 = bf16[64,768]": "_moe_experts"}
    event = "%fusion.7 = f32[8,32768]{1,0:T(8,128)S(1)} fusion(f32[8,16]{1,0})"
    assert _sparse_moe.op_key(event) == "%fusion.7 = f32[8,32768]"


def test_kernel_roofline_on_a_synthetic_trace():
    cell = harness.Cell(KEYE)
    cfg = cell.config
    peaks = harness.load_peaks("TPU v5 lite")
    before = {"steps": 100.0, "sparse_rows_scored": 0.0,
              "sparse_rows_attended": 0.0, "moe_pairs": 0.0,
              "moe_experts_touched": 0.0}
    # 10 steps in the traced second; a step scores 6 layers x 80,000 rows
    after = {"steps": 110.0, "sparse_rows_scored": 10 * 6 * 80000.0,
             "sparse_rows_attended": 10 * 6 * 16384.0,
             "moe_pairs": 10 * 6 * 64.0, "moe_experts_touched": 10 * 6 * 50.0}
    facts = {"config": cfg,
             "counter_samples": [(4.9, before), (5.0, before), (6.0, after)],
             "op_scopes": {"%fusion.7 = f32[8,32768]": "_index_topk",
                           "%while.2 = f32[8]": "_index_topk"}}
    trace = {"modules": {"jit_fn(1)": [0.004] * 10,
                         "jit_prefill_chunk(2)": [0.03] * 30},
             "kernels": [("%fusion.7 = f32[8,32768]{1,0} fusion(f32[8]{0})",
                          60, 0.006),
                         ("%while.2 = f32[8]{0} while(f32[8]{0})", 60, 0.5),
                         ("%fusion.8 = f32[8]{0} fusion(f32[8]{0})", 60, 0.1)]}
    ctx = {"cell": cell, "facts": facts, "peaks": peaks, "trace": trace,
           "probe": types.SimpleNamespace(traced=(5.02, 5.98))}
    # one layer's call reads 80,000 keys of 64 bf16: 10.24 MB at 819 GB/s
    # is 12.5 us (memory-bound: 2 x 16 x 65 x 80,000 FLOPs are 0.8 us);
    # 6 layers x 10 steps of it against the 6 ms the fusion took (the
    # `while` that holds it is not counted twice)
    got = cell.reader("indexer_topk_roofline").read(ctx)
    assert got == pytest.approx(100 * (80000 * 128 / 819e9) * 60 / 0.006)
    assert 0 < got < 100
    assert cell.reader("sparse_attn_roofline").read(ctx) is None  # no such op
    # the chunk program ran more often than the step, and is not the step
    assert cell.reader("prefill_chunk.device_ms").read(ctx) == 30.0
    assert cell.reader("decode_step.device_ms").read(ctx) == 4.0
    want = sparse_moe.decode_step_bytes(cfg, 300, 480000, 98304)
    assert cell.reader("sparse_moe_step.hbm_roofline").read(ctx) \
        == pytest.approx(100 * want / 819e9 / 0.004)
    # the parent: no samples, no scopes
    ctx["facts"] = {"config": cfg}
    for m in ("indexer_topk_roofline", "moe_experts_roofline",
              "sparse_moe_step.hbm_roofline"):
        assert cell.reader(m).read(ctx) is None
    assert np.isfinite(got)
