"""The cell of PR 34 at a toy size on the CPU: latent attention and one
chip's share of sigmoid-routed, group-limited experts through its own
runner (`correct` true; false with each control put in the program's
place), its files and entries, the FLOP and byte counts against a hand
count, the published sizes, and the new readers on a synthetic trace."""
import json
import os
import types

import pytest

from benchmarks import harness
from benchmarks.flops import mla_moe
from benchmarks.metrics import _mla_moe
from _bench_common import ROOT, over

CELL = "dotsvlm1-l5-serve-docqa"
NEW_METRICS = ["mla_moe_step.mfu", "mla_moe_step.hbm_roofline",
               "latent_attn_roofline", "latent_chunk_attn_roofline",
               "mla_moe_experts_roofline", "moe_local_pairs_share"]
JOINED = ["decode_step.device_ms", "tpot_ms_p50", "serve_compile.in_window",
          "decode_tick.ms_p50", "decode_tick.host_ms_p50",
          "decode_tick.emit_ms_p50", "decode_tick.launch_ms_p50",
          "decode_tick.admit_ms_p95", "prefill_chunk.device_ms"]
TOY_CFG = dict(vocab_size=128, hidden_size=32, num_attention_heads=4,
               q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
               moe_intermediate_size=16, n_shared_experts=1,
               num_experts_per_tok=4, n_group=4,
               topk_group=2, num_hidden_layers=3, first_k_dense_replace=1,
               n_routed_experts=8, held_experts=[4, 8],
               published={"n_routed_experts": 16},
               rope_scaling={"type": "yarn", "factor": 40,
                             "original_max_position_embeddings": 16,
                             "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                             "mscale_all_dim": 1},
               max_position_embeddings=128,
               # what N(0, 0.02) is at the published width (tests/
               # test_mla_moe.py)
               initializer_range=0.15, init_q_gain=3.0, init_embed_gain=6.0,
               init_router_gain=3.0, init_router_bias_std=0.1,
               # float32, so that the sound toy run reads a gap of zero
               activation_dtype="float32", param_dtype="float32")
SCALE = {"config": TOY_CFG,
         "traffic": dict(
             engine={"slots": 3, "page_size": 4, "max_context": 96,
                     "max_prompt": 64, "prefill_chunk": 8,
                     "max_new_tokens": 12},
             arrivals={"shape": "steady", "rate_per_s": 5.0, "draw_seed": 5},
             prompt_len={"median": 24, "sigma": 0.8, "min": 3, "max": 64},
             output_len={"median": 8, "sigma": 0.4, "min": 4, "max": 12},
             drain_s=120.0, checked_requests=6, reference_pad_to=8,
             limits={"logit_gap_over_bf16": 0.0, "logit_gap_max": 10.0})}
SEED = 2 ** 31 + 29


def toy_runner(seed=SEED):
    import jax
    cell = harness.Cell(CELL)
    probe = harness.Probe(0.0, False, None)
    return cell, cell.runner().Runner(cell, seed, 1.5, jax.devices()[:1],
                                      probe, SCALE)


@pytest.fixture(scope="module")
def served():
    """One toy run, its engine released: (cell, runner, facts)."""
    import jax
    cell, r = toy_runner()
    with jax.default_matmul_precision("highest"):
        r.run()
        facts = r.results()["facts"]
        r.release()
    return cell, r, facts


def test_the_cell_runs_and_is_correct():
    import jax
    cell = harness.Cell(CELL)
    with jax.default_matmul_precision("highest"):
        line = harness.run_cell(cell, SEED, 1.5, 0, require_chip=False,
                                scale=SCALE)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "tpot_ms_p95"}
    assert line["device"]["count"] == cell.chips == 1
    assert set(line["compared"]) == {"logit_gap_over_bf16", "logit_gap_max",
                                     "never_finished"}
    assert not over(line)
    # TTFT and the chunks' share of the gaps: printed, not compared
    seen = line["observed"]
    assert 0.0 <= seen["gaps_with_chunk_share"] <= 1.0
    assert 0.0 < seen["ttft_ms_p50"] <= seen["ttft_ms_p95"]


@pytest.mark.parametrize("control", [
    "fp8", "scale_without_m2", "plain_rope", "softmax_scores",
    "bias_in_gates", "no_group_limit", "no_shared_expert", "no_latent_norm"])
def test_control_in_the_programs_place_is_not_correct(served, control):
    """The reference computed wrongly, judged through the runner's own
    `check` in the served tokens' place: the program is correct, the
    control is not, by the ratio to the reference's own noise."""
    import jax
    _, r, _ = served
    assert sorted(r.controls()) == sorted([
        "fp8", "scale_without_m2", "plain_rope", "softmax_scores",
        "bias_in_gates", "no_group_limit", "no_shared_expert",
        "no_latent_norm"])
    with jax.default_matmul_precision("highest"):
        assert harness.compared_ok(r.check())
        bad = r.check(r.controls()[control])
    assert not harness.compared_ok(bad), bad
    assert {c["name"] for c in bad if c["limit"] is not None
            and not c["value"] <= c["limit"]} == {"logit_gap_over_bf16"}


def test_the_three_longest_are_judged_first(served):
    from benchmarks.runners import serve_decode_mla_moe as mod
    _, r, _ = served
    picks = r.sample()
    by_length = sorted((q for q in r.reqs if q.ok),
                       key=lambda q: -(len(q.prompt) + len(q.tokens)))
    assert picks[:3] == by_length[:3] and len(picks) == 6
    assert (mod.LONGEST_CHECKED, mod.NOISE_ROWS) == (3, 512)
    assert mod.NOISE_ROWS == harness.Cell(CELL).traffic["output_len"]["max"]


def test_the_cells_files_and_entries():
    cell = harness.Cell(CELL)
    assert cell.traffic["runner"] == "serve_decode_mla_moe"
    assert cell.workload["chips"] == 1
    assert cell.traffic["engine"] == {
        "slots": 24, "page_size": 128, "max_context": 14336,
        "max_prompt": 12288, "prefill_chunk": 512, "max_new_tokens": 512}
    assert cell.traffic["arrivals"]["shape"] == "steady"
    assert cell.traffic["prompt_len"] == {"median": 5120, "sigma": 0.6,
                                          "min": 1024, "max": 12288}
    assert cell.traffic["output_len"] == {"median": 256, "sigma": 0.5,
                                          "min": 64, "max": 512}
    assert cell.traffic["temperature"] == 0.0
    assert cell.traffic["drain_s"] == 120.0
    assert set(cell.traffic["limits"]) == set(cell.traffic["limits_why"])
    cfg = cell.config
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256}
    assert cfg["held_experts"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert {"no_vision_tower", "no_mtp", "rope_pairs", "init_q_gain",
            "init_embed_gain", "init_router_gain",
            "init_router_bias_std"} <= set(cfg["assumed"])
    # every number of the catalog row's config, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"dots.vlm1.inst"' in line)
        assert cell.config_entry["source"] == row["source_url"] \
            == cfg["source"]
        cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 16}
        for k, v in row["config"].items():
            assert cfg[k] == cut.get(k, v), k
    assert [m["name"] for m in cell.per_layer] == JOINED + NEW_METRICS
    assert [m["name"] for m in cell.end_to_end] == ["tpot_ms_p95", "setup_s"]


def test_the_rate_is_the_issues_share_and_the_draw_is_the_rules():
    """ISSUE 34's recipe: the rate is 0.7 to 0.9 of what 24 clients kept
    busy for 60 s completed, and the draw is the first from the cell's
    first on that holds what the rate and the mix expect
    (`representative_draw`: counts, never a spread)."""
    from benchmarks.tools import controls_mla_moe as tool
    cell = harness.Cell(CELL)
    arr = cell.traffic["arrivals"]
    share = arr["rate_per_s"] / cell.traffic["closed_loop_60s_requests_per_s"]
    assert 0.7 <= share <= 0.9 + 0.005        # (a rate of two figures)
    seed, holds, want = tool.representative_draw(cell.traffic, 30.0)
    assert seed == arr["draw_seed"] >= tool.FIRST_DRAW
    assert abs(holds["requests"] - arr["rate_per_s"] * 30.0) <= 1.5
    assert want["prompt_tokens"] / want["requests"] == pytest.approx(
        5700, rel=0.05)


def test_the_draw_is_replayed_and_the_slice_holds_chunks():
    """Every seed offers the draw's own timeline (the seed draws the token
    ids alone), so which chunk meets how many live slots repeats; and
    requests of at least 8 chunks are due in and just before the traced
    seconds, which start half-way through the window, so that every
    traced run reads the chunk's device metrics."""
    from benchmarks import loadgen
    cell = harness.Cell(CELL)
    assert cell.traffic["arrivals"]["replay"] is True
    seconds = float(cell.spec["run_seconds"])
    chunk = cell.traffic["engine"]["prefill_chunk"]
    timelines = [[(due, len(p), n) for due, p, n in loadgen.make_schedule(
        cell.traffic, seed, seconds, cell.config["vocab_size"])]
        for seed in (1, 2 ** 31 + 99)]
    assert timelines[0] == timelines[1]
    assert len(timelines[0]) == 14
    assert sum(p for _, p, _ in timelines[0]) == 76969
    start = seconds / 2.0
    due_in_slice = [p for due, p, _ in timelines[0]
                    if start - 0.5 <= due <= start + harness.TRACE_LENGTH_S]
    assert sum(-(-p // chunk) for p in due_in_slice) >= 8


def test_runner_facts_counters_and_the_joined_readers(served):
    cell, r, f = served
    assert f["attn_route"] == f["chunk_attn_route"] == "latent"
    assert f["recompiles"] == 0
    assert f["kv_kinds"] == {"global": {
        "layers": 3, "window": 0, "pages_per_slot": 24, "n_pages": 72,
        "content": "latent"}}
    chunks = sum(-(-n // 8) for n, _ in f["served"])
    assert f["prefill_chunks"] == chunks > f["prefills"] > 0
    # top 4 a token, two expert layers; this share holds 8 of 16
    assert f["moe_pairs_routed"] == f["tokens"] * 4 * 2
    assert 0 < f["moe_pairs"] < f["moe_pairs_routed"]
    assert f["moe_prefill_pairs_routed"] \
        == sum(n for n, _ in f["served"]) * 4 * 2
    # three layers: a prompt's chunks see n (n + 1) / 2 keys in all
    assert f["mla_chunk_rows_visible"] \
        == 3 * sum(n * (n + 1) // 2 for n, _ in f["served"])
    assert f["mla_rows_live"] > 0 and f["mla_chunk_rows_live"] > 0
    assert "counter_samples" not in f and "op_scopes" not in f
    ctx = {"facts": f, "cell": cell,
           "peaks": harness.load_peaks("TPU v5 lite")}
    share = cell.reader("moe_local_pairs_share").read(ctx)
    assert share == pytest.approx(
        100.0 * (f["moe_pairs"] + f["moe_prefill_pairs"])
        / (f["moe_pairs_routed"] + f["moe_prefill_pairs_routed"]))
    assert 25 < share < 75               # 8 of 16 held
    assert cell.reader("mla_moe_step.mfu").read(ctx) > 0
    # the accepted readers whose lists the cell joined read it unedited
    assert cell.reader("serve_compile.in_window").read(ctx) == 0
    assert cell.reader("tpot_ms_p50").read(ctx) == f["tpot_ms_p50"] > 0
    ticks = dict(ctx, probe=r.probe)
    for m in ("ms_p50", "host_ms_p50", "emit_ms_p50", "launch_ms_p50",
              "admit_ms_p95"):
        assert cell.reader(f"decode_tick.{m}").read(ticks) > 0
    # a program with no such counters (the parent) gives the readers nothing
    bare = {"facts": {"served": f["served"], "config": {}, "steps": 3},
            "cell": cell, "peaks": ctx["peaks"],
            "trace": {"modules": {}, "kernels": []},
            "probe": types.SimpleNamespace(traced=(0.0, 1.0))}
    for m in NEW_METRICS:
        assert cell.reader(m).read(bare) is None


# --------------------------------------------------------------------- #
# counts by hand
# --------------------------------------------------------------------- #
def test_flops_and_bytes_against_a_hand_count():
    cfg = TOY_CFG
    p = mla_moe.layer_params(cfg)
    # W_qa 32 x 16, its norm 16, W_qb 16 x 4 x 12, W_kva 32 x 12, its norm
    # 8, W_kvb 8 x 4 x 16, W_o 32 x 32
    assert p["attention"] == 512 + 16 + 768 + 384 + 8 + 512 + 1024 == 3224
    assert p["up_projection"] == 512 and p["norms"] == 64
    assert p["dense_mlp"] == 3 * 32 * 48 and p["expert"] == 3 * 32 * 16
    assert p["shared"] == 1536 and p["router"] == 32 * 16 + 16
    assert mla_moe.dense_layer_param_count(cfg) == 3224 + 64 + 4608
    assert mla_moe.expert_layer_param_count(cfg, 0) \
        == 3224 + 64 + 1536 + 528 == 5352
    assert mla_moe.expert_layer_param_count(cfg) == 5352 + 8 * 1536
    assert mla_moe.param_count(cfg) \
        == 7896 + 2 * 17640 + 2 * 128 * 32 + 32
    # a prompt token: everything of attention but the norms' gains and
    # W_kvb; a decoded token: W_kvb too (absorption and the values' way up)
    prompt = 2 * (3 * (3224 - 24 - 512) + 4608 + 2 * (1536 + 512))
    assert mla_moe.token_flops(cfg, False) == prompt == 33536
    assert mla_moe.token_flops(cfg, True) == prompt + 2 * 3 * 512
    assert mla_moe.head_flops(cfg) == 2 * 32 * 128
    # absorbed: a head scores 8 + 4 columns of a row and sums 8
    assert mla_moe.decode_attention_flops(cfg, 100) == 2 * 4 * 20 * 100
    assert mla_moe.latent_attend_cost(cfg, 100) == (16000, 100 * 12 * 2)
    # up-projected: a row through W_kvb, then 8 + 4 + 8 a head a pair
    assert mla_moe.chunk_attention_flops(cfg, 50, 700) \
        == 2 * 512 * 50 + 2 * 4 * 20 * 700
    assert mla_moe.moe_experts_cost(cfg, 6, 5) \
        == (2 * 1536 * 6, (5 * 1536 + 2 * 6 * 32) * 2)
    # a step that touched 9 experts and saw 300 rows, over its layers
    assert mla_moe.decode_step_bytes(cfg, 9, 300) == 2 * (
        7896 + 2 * 5352 + 32 * 128 + 32 + 9 * 1536) + 300 * 24
    # a window: one request of 10 prompt tokens and 3 served
    counts = {"mla_rows_live": 70, "mla_chunk_rows_live": 36,
              "mla_chunk_rows_visible": 165, "moe_pairs": 5,
              "moe_prefill_pairs": 40}
    assert mla_moe.window_flops(cfg, [(10, 3)], counts) \
        == 10 * prompt + 2 * (prompt + 3072) + 3 * 8192 \
        + 160 * 70 + 1024 * 36 + 160 * 165 + 2 * 1536 * 45
    eng = {"slots": 3, "page_size": 4, "max_context": 96}
    assert mla_moe.pool_bytes(cfg, eng) == 3 * 96 * 24 * 3


def test_published_sizes_are_the_issues():
    cell = harness.Cell(CELL)
    cfg, eng = cell.config, cell.traffic["engine"]
    p = mla_moe.layer_params(cfg)
    assert p["attention"] == 187_107_328 == cfg["published"][
        "attention_parameters"]
    assert mla_moe.dense_layer_param_count(cfg) == 583_483_392 \
        == cfg["published"]["dense_layer_parameters"]
    assert p["expert"] == 44_040_192
    assert mla_moe.expert_layer_param_count(cfg, 0) == 232_997_120
    assert mla_moe.expert_layer_param_count(cfg) == 937_640_192 \
        == cfg["published"]["expert_layer_parameters_held"]
    assert mla_moe.param_count(cfg) == 6_187_409_408 \
        == cfg["published"]["parameters_held"]
    assert mla_moe.latent_row_bytes(cfg) == 1152
    assert mla_moe.pool_bytes(cfg, eng) == 1_981_808_640
    # per-head K and V would be 81,920 B a token a layer
    m = mla_moe.dims(cfg)
    assert m["h"] * (m["nope"] + m["rope"] + m["v"]) * 2 == 81_920
    # the decode step's weights: 3.03 GB outside the routed experts, the
    # head 1.85, a touched expert 88 MB
    assert round((mla_moe.decode_step_bytes(cfg, 0, 0)
                  - 2 * cfg["hidden_size"] * cfg["vocab_size"]) / 1e9, 2) \
        == 3.03
    assert round(2 * cfg["hidden_size"] * cfg["vocab_size"] / 1e9, 2) == 1.85
    assert round(2 * p["expert"] / 1e6) == 88
    # a chunk against the longest prompt's table, five layers: 4.6 TFLOP
    keys = eng["max_prompt"]
    assert round(mla_moe.chunk_attention_flops(
        cfg, 5 * keys, 5 * 512 * keys) / 1e12, 1) == 4.6
    assert round(512 * mla_moe.token_flops(cfg, False) / 1e12, 2) == 1.47


# --------------------------------------------------------------------- #
# the device-trace readers on a synthetic trace
# --------------------------------------------------------------------- #
DECODE_HLO = (
    '  %fusion.7 = f32[128,14336]{1,0:T(8,128)} fusion(%a, %b), '
    'kind=kLoop, metadata={op_name="jit(fn)/jit(_latent_attend)/exp"}\n'
    '  %gmm.3 = bf16[192,2048]{1,0:T(8,128)(2,1)} custom-call(%x), '
    'metadata={op_name="jit(fn)/jit(_moe_experts)/jit(gmm)/pallas_call"}\n'
    '  %fusion.2 = f32[24]{0} fusion(%c), '
    'metadata={op_name="jit(fn)/argmax"}\n')
CHUNK_HLO = (
    '  %fusion.9 = f32[4,512,12288]{2,1,0:T(8,128)} fusion(%a), kind=kLoop, '
    'metadata={op_name="jit(prefill_chunk)/jit(_latent_chunk_attend)/exp"}\n'
    '  %gmm.3 = bf16[4096,2048]{1,0:T(8,128)(2,1)} custom-call(%x), '
    'metadata={op_name="jit(prefill_chunk)/jit(_moe_experts)/jit(gmm)"}\n'
    '  %fusion.2 = f32[24]{0} fusion(%c), metadata={op_name='
    '"jit(prefill_chunk)/jit(_latent_chunk_attend)/reduce"}\n')


def test_op_scopes_name_the_three_pieces():
    scopes, ambiguous = _mla_moe.op_scopes(DECODE_HLO, CHUNK_HLO)
    assert scopes == {
        "%fusion.7 = f32[128,14336]": "_latent_attend",
        "%gmm.3 = bf16[192,2048]": "_moe_experts",
        "%fusion.9 = f32[4,512,12288]": "_latent_chunk_attend"}
    # `%fusion.2 = f32[24]` is no piece's in the step and the chunk
    # attention's in the chunk program: left out, and counted
    assert ambiguous == 1


def test_the_new_readers_on_a_synthetic_trace():
    cell = harness.Cell(CELL)
    cfg = cell.config
    peaks = harness.load_peaks("TPU v5 lite")
    zero = {k: 0.0 for k in (
        "moe_pairs", "moe_experts_touched", "mla_rows_live",
        "mla_chunk_rows_live", "mla_chunk_rows_visible")}
    zero.update(steps=100.0, prefill_chunks=40.0)
    # the traced second: 10 steps, each with 8 slots live at context 6,000
    # over five layers, 32 held pairs over 6 experts in each of the four
    # expert layers; 4 chunks, each at offset 5,120 of its prompt, all 512
    # of its queries valid
    visible = 512 * 5120 + 512 * 513 // 2
    after = dict(zero, steps=110.0, prefill_chunks=44.0,
                 moe_pairs=10 * 4 * 32.0, moe_experts_touched=10 * 4 * 6.0,
                 mla_rows_live=10 * 5 * 48000.0,
                 mla_chunk_rows_live=4 * 5 * 5632.0,
                 mla_chunk_rows_visible=4 * 5.0 * visible)
    scopes, _ = _mla_moe.op_scopes(DECODE_HLO, CHUNK_HLO)
    facts = {"config": cfg, "op_scopes": scopes,
             "counter_samples": [(4.9, zero), (5.0, zero), (5.5, zero),
                                 (6.0, after)]}
    trace = {"modules": {"jit_fn(1)": [0.02] * 10,
                         "jit_prefill_chunk(2)": [0.07] * 4},
             "kernels": [("%fusion.7 = f32[128,14336]{1,0} "
                          "fusion(f32[8]{0})", 1200, 0.03),
                         ("%gmm.3 = bf16[192,2048]{1,0} "
                          "custom-call(f32[8]{0})", 120, 0.008),
                         ("%fusion.9 = f32[4,512,12288]{2,1,0} "
                          "fusion(f32[8]{0})", 640, 0.16),
                         ("%gmm.3 = bf16[4096,2048]{1,0} "
                          "custom-call(f32[8]{0})", 48, 0.004),
                         ("%fusion.8 = f32[8]{0} fusion(f32[8]{0})", 60,
                          0.1)]}
    ctx = {"cell": cell, "facts": facts, "peaks": peaks, "trace": trace,
           "probe": types.SimpleNamespace(traced=(5.02, 5.98))}
    # a step's queries may see 240,000 rows of 1,152 B: 0.34 ms at 819
    # GB/s, against 240,000 x 128 x 2,176 FLOPs: 0.34 ms at the peak
    rows = 5 * 48000
    least = max(rows * 1152 / 819e9, rows * 128 * 2176 / 197e12)
    got = cell.reader("latent_attn_roofline").read(ctx)
    assert got == pytest.approx(100 * least * 10 / 0.03)
    assert 0 < got < 100
    # a chunk: 5 x 5,632 rows through W_kvb and 5 x `visible` pairs of
    # 128 heads x 320, at the peak; four chunks over 0.16 s
    flops = 5 * 5632 * 2 * 512 * 32768 + 5 * visible * 2 * 128 * 320
    got = cell.reader("latent_chunk_attn_roofline").read(ctx)
    assert got == pytest.approx(100 * (flops / 197e12) * 4 / 0.16)
    assert 0 < got < 100
    # the experts: 24 touched in a step's four layers, three matrices of
    # 7168 x 2048 bf16 each, 128 pairs' rows in and out; ten steps
    nbytes = (24 * 3 * 7168 * 2048 + 2 * 128 * 7168) * 2
    assert cell.reader("mla_moe_experts_roofline").read(ctx) \
        == pytest.approx(100 * (nbytes / 819e9) * 10 / 0.008)
    want = mla_moe.decode_step_bytes(cfg, 24, rows)
    got = cell.reader("mla_moe_step.hbm_roofline").read(ctx)
    assert got == pytest.approx(100 * want / 819e9 / 0.02)
    assert 0 < got < 100
    # the readers whose lists the cell joined
    assert cell.reader("prefill_chunk.device_ms").read(ctx) == 70.0
    assert cell.reader("decode_step.device_ms").read(ctx) == 20.0
    # no chunk between the samples: the chunk's reader has nothing
    facts["counter_samples"][-1] = (6.0, dict(after, prefill_chunks=40.0))
    assert cell.reader("latent_chunk_attn_roofline").read(ctx) is None
    assert cell.reader("latent_attn_roofline").read(ctx) is not None
    # the parent: no samples, no scopes
    ctx["facts"] = {"config": cfg}
    for m in NEW_METRICS[1:5]:
        assert cell.reader(m).read(ctx) is None


def test_a_traced_run_starts_its_trace_mid_window():
    import threading
    import time
    _, r = toy_runner()
    r.engine = types.SimpleNamespace()
    started = []
    r.probe._trace_some = lambda: started.append(time.perf_counter())
    r._trace_on_first_reply([(0.0, [1, 2], 2)], {})
    r.probe.t_open = time.perf_counter()
    waiter = threading.Thread(target=r.probe._trace_some)
    waiter.start()
    waiter.join(0.3)
    assert waiter.is_alive() and not started       # 0.75 s have not passed
    waiter.join(10)
    assert started and started[0] - r.probe.t_open >= 0.75
    assert harness.TRACE_START_S == 0.0
    harness.TRACE_START_S = 2.0
