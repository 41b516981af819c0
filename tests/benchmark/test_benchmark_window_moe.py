"""The cell of PR 32 at a toy size on the CPU: the window / global
attention, routed-expert serving cell through its own runner (`correct`
true; false with each control put in the program's place, and with the
ring's stale rows attended underneath), its files and entries, the FLOP
and byte counts against a hand count, the published sizes, and the new
readers on a synthetic trace."""
import json
import os
import subprocess
import types

import pytest

from benchmarks import harness
from benchmarks.flops import window_moe
from benchmarks.metrics import _window_moe
from _bench_common import ROOT, over

CELL = "smallthinker21b-l8-serve-mixed"
PARENT = "3fcef601b63210d6e7278b190bd730fe4e039d1a"
TOY_CFG = dict(vocab_size=128, hidden_size=32, num_attention_heads=7,
               num_key_value_heads=1, head_dim=8, num_hidden_layers=8,
               moe_num_primary_experts=8, moe_num_active_primary_experts=2,
               moe_ffn_hidden_size=16, max_position_embeddings=128,
               sliding_window_size=8, sliding_window_layout=[0, 1, 1, 1] * 2,
               rope_layout=[0, 1, 1, 1] * 2,
               # what N(0, 0.02) is at the published width (tests/
               # test_window_moe.py)
               initializer_range=0.15, init_qk_gain=1.5, init_embed_gain=6.0,
               init_router_gain=2.5,
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
             drain_s=120.0, checked_requests=4, reference_pad_to=8,
             limits={"logit_gap_over_bf16": 0.0,
                     "logit_gap_over_bf16_past_window": 0.0,
                     "logit_gap_max": 10.0})}
SEED = 2 ** 31 + 23


def run(seconds=1.5):
    import jax
    with jax.default_matmul_precision("highest"):
        return harness.run_cell(harness.Cell(CELL), SEED, seconds, 0,
                                require_chip=False, scale=SCALE)


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
    cell = harness.Cell(CELL)
    line = run()
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "tpot_ms_p95"}
    assert line["device"]["count"] == cell.chips == 1
    assert set(line["compared"]) == {
        "logit_gap_over_bf16", "logit_gap_over_bf16_past_window",
        "logit_gap_max", "never_finished", "past_window_unjudged"}
    # TTFT and the chunks' share of the gaps: printed, not compared
    seen = line["observed"]
    assert 0.0 <= seen["gaps_with_chunk_share"] <= 1.0
    assert 0.0 < seen["ttft_ms_p50"] <= seen["ttft_ms_p95"]


@pytest.mark.parametrize("control", ["fp8", "no_window", "rope_on_global",
                                     "router_reads_u", "silu", "stale_rows"])
def test_control_in_the_programs_place_is_not_correct(served, control):
    """The reference computed wrongly, judged through the runner's own
    `check` in the served tokens' place: the program is correct, the
    control is not, by one of the cell's limits."""
    import jax
    _, r, _ = served
    with jax.default_matmul_precision("highest"):
        assert harness.compared_ok(r.check())
        bad = r.check(r.controls()[control])
    assert not harness.compared_ok(bad), bad
    assert {c["name"] for c in bad if c["limit"] is not None
            and not c["value"] <= c["limit"]} \
        <= {"logit_gap_over_bf16", "logit_gap_over_bf16_past_window"}


def test_stale_rows_attended_underneath_is_not_correct(monkeypatch):
    """The decode step's mask reaching to the END of the newest page,
    where the cache's stops at the newest row: what a recycled page still
    holds past that row is attended, and the run is not correct."""
    from bigdl_tpu.serving import kvcache
    real = kvcache._window_attend

    def to_the_pages_end(q, k_pool, v_pool, tables, lengths, *, window):
        return real(q, k_pool, v_pool, tables,
                    lengths | (k_pool.shape[1] - 1), window=window)
    monkeypatch.setattr(kvcache, "_window_attend", to_the_pages_end)
    line = run()
    assert line["correct"] is False and over(line), line["compared"]


def test_the_references_own_noise_is_read_at_more_rows_than_the_reply(
        monkeypatch):
    """`choice_gaps` reads a `noise` variant at the sequence's last
    `noise_rows` positions (all of a shorter one), every other variant and
    the served tokens at the served positions, each row judged by the
    sound reference's logits of that row (the forward stubbed by a table
    of logits a variant); and the precision's rounding is one a compiler
    cannot drop, with bfloat16's values."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.reference import window_moe_ref as ref
    rng = np.random.default_rng(3)
    seq = rng.integers(0, 16, 40, dtype=np.int32)
    own = {"bf16": ref.OWN_PRECISION, "fp8": ref.Variant(quant=ref.fp8)}
    logits = {v: jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
              for v in (ref.SOUND, *own.values())}
    monkeypatch.setattr(
        ref, "served_logits",
        lambda cfg, key, seq, lo, hi, variant=ref.SOUND:
        logits[variant][lo:hi])
    sound = np.asarray(logits[ref.SOUND])

    def gaps(tokens, rows):
        return sound[rows].max(-1) - sound[rows, tokens]
    rows = np.arange(32, 39)              # row i predicts token i + 1
    want = {"served": gaps(seq[33:40], rows), **{
        name: gaps(np.asarray(logits[v]).argmax(-1)[rows], rows)
        for name, v in own.items()}}
    for noise_rows, n in ((0, 7), (3, 7), (20, 20), (1024, 39)):
        got = ref.choice_gaps({}, None, seq, 33, own, 8,
                              ("bf16",) if noise_rows else (), noise_rows)
        assert {k: len(v) for k, v in got.items()} == {
            "served": 7, "bf16": n, "fp8": 7}
        for name in want:
            np.testing.assert_allclose(got[name][-7:], want[name])
        wide = np.arange(39 - n, 39)
        np.testing.assert_allclose(got["bf16"], gaps(
            np.asarray(logits[ref.OWN_PRECISION]).argmax(-1)[wide], wide))
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 3.0
    assert (ref.bf16(x) == x.astype(jnp.bfloat16).astype(jnp.float32)).all()
    assert "reduce_precision" in str(jax.make_jaxpr(ref.bf16)(x))
    assert ref.OWN_PRECISION.quant is ref.OWN_PRECISION.stored is ref.bf16


def test_the_three_longest_are_judged_and_two_past_the_ring_are_needed(
        served):
    from benchmarks.runners import serve_decode_window_moe as mod
    _, r, _ = served
    picks = r.sample()
    by_length = sorted((q for q in r.reqs if q.ok),
                       key=lambda q: -(len(q.prompt) + len(q.tokens)))
    assert picks[:3] == by_length[:3] and len(picks) == 4
    assert (mod.PAST_WINDOW_CHECKED, mod.PAST_WINDOW_NEEDED,
            mod.NOISE_ROWS) == (3, 2, 1024)
    assert mod.NOISE_ROWS == harness.Cell(CELL).traffic["output_len"]["max"]
    # one judged request past the ring alone is a failure of its own
    row = {"served": [0.0], "bf16": [1.0]}
    ring = r.stats["kv_kinds"]["window"]["pages_per_slot"] \
        * r.tr["engine"]["page_size"]
    table = [dict(row, n_tokens=ring + 1), dict(row, n_tokens=ring)]
    got = {c["name"]: c["value"] for c in r.compared(table, "served", 0)}
    assert got["past_window_unjudged"] == 1.0
    table.append(dict(row, n_tokens=ring + 2))
    got = {c["name"]: c["value"] for c in r.compared(table, "served", 0)}
    assert got["past_window_unjudged"] == 0.0


def test_the_cells_files_and_entries():
    cell = harness.Cell(CELL)
    assert cell.traffic["runner"] == "serve_decode_window_moe"
    eng = cell.traffic["engine"]
    assert eng == {"slots": 32, "page_size": 128, "max_context": 16384,
                   "max_prompt": 12288, "prefill_chunk": 512,
                   "max_new_tokens": 1024}
    assert cell.traffic["arrivals"]["shape"] == "steady"
    assert cell.traffic["prompt_len"] == {"median": 2048, "sigma": 1.2,
                                          "min": 128, "max": 12288}
    assert cell.traffic["output_len"] == {"median": 384, "sigma": 0.5,
                                          "min": 128, "max": 1024}
    assert set(cell.traffic["limits"]) == set(cell.traffic["limits_why"])
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers"] == cell.config_entry[
        "reduced"] and cfg["published"]["num_hidden_layers"] == 52
    n = cfg["num_hidden_layers"]
    assert cfg["rope_layout"][:n] == cfg["sliding_window_layout"][:n] \
        == [0, 1, 1, 1, 0, 1, 1, 1]
    # every number of the catalog row's config, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if "SmallThinker-21BA3B-Instruct" in line)
        assert cell.config_entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert cfg[k] == (8 if k == "num_hidden_layers" else v), k
    names = [m["name"] for m in cell.per_layer]
    assert names == ["decode_step.device_ms", "tpot_ms_p50",
                     "serve_compile.in_window", "decode_tick.ms_p50",
                     "decode_tick.host_ms_p50", "decode_tick.emit_ms_p50",
                     "decode_tick.launch_ms_p50", "decode_tick.admit_ms_p95",
                     "prefill_chunk.device_ms",
                     "window_moe_step.mfu", "window_moe_step.hbm_roofline",
                     "window_attn_roofline", "window_moe_experts_roofline",
                     "kv_window_rows_attended_share", "kv_pages_held_share"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_ms_p95", "setup_s"]


def test_the_rate_is_the_issues_share_and_the_draw_is_the_rules():
    """ISSUE 32's recipe: the rate is 0.7 to 0.85 of what 32 clients kept
    busy for 60 s completed, and the draw is the first from the cell's
    first on that holds what the rate and the mix expect
    (`representative_draw`: counts, never a spread)."""
    from benchmarks.tools import controls_window_moe as tool
    cell = harness.Cell(CELL)
    arr = cell.traffic["arrivals"]
    share = arr["rate_per_s"] / cell.traffic["closed_loop_60s_requests_per_s"]
    assert 0.7 <= share <= 0.85
    seed, holds, want = tool.representative_draw(
        cell.traffic, cell.config["sliding_window_size"], 30.0)
    assert seed == arr["draw_seed"] >= tool.FIRST_DRAW
    assert abs(holds["requests"] - arr["rate_per_s"] * 30.0) <= 1.5
    assert holds["past_ring"] >= 2           # what `compared` judges
    assert want["prompt_tokens"] / want["requests"] == pytest.approx(
        3400, rel=0.03)


def test_the_tick_model_on_a_schedule_counted_by_hand():
    """One prompt of two chunks and three tokens: the second chunk's tick
    ends in the first step (a gap that held a chunk), the next tick is a
    step alone."""
    import numpy as np
    from benchmarks.tools import tick_sim
    gaps, held = tick_sim.simulate(
        [(0.0, np.zeros(600, np.int32), 3)], 4, 512, 26.6, 48.4, 0.65)
    assert gaps.tolist() == pytest.approx([49.05, 49.05])
    assert held.tolist() == [True, False]
    # a second request, due during the first one's reply, puts its one
    # chunk into one of the first one's gaps
    gaps, held = tick_sim.simulate(
        [(0.0, np.zeros(600, np.int32), 4),
         (0.11, np.zeros(100, np.int32), 2)], 4, 512, 26.6, 48.4, 0.65)
    assert sorted(gaps.round(2).tolist()) == [49.05, 49.05, 49.7, 76.3]
    assert int(held.sum()) == 3


def test_nothing_the_benchmark_had_is_edited():
    """Against the parent commit: every file under the benchmark's paths
    that was there is byte for byte what it was, and BENCHMARK.json gained
    entries at the ends of its lists and members at the ends of
    `workloads` lists, nothing else."""
    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args, check=True,
                              capture_output=True, text=True).stdout
    try:
        git("cat-file", "-e", PARENT)
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("the parent commit is not in this checkout")
    changed = [line.split("\t") for line in git(
        "diff", "--name-status", PARENT, "--", "benchmarks",
        "tests/benchmark").splitlines()]
    assert [c for c in changed if c[0] != "A"] == []
    old = json.loads(git("show", f"{PARENT}:BENCHMARK.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = json.load(f)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) == len(old[key]) + 1
    for key in ("end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            lists = was.get("workloads", []), now.get("workloads", [])
            assert now == dict(was, **({"workloads": lists[1]}
                                       if lists[1] else {}))
            assert lists[1][:len(lists[0])] == lists[0]
            assert set(lists[1][len(lists[0]):]) <= {CELL}
    assert len(new["end_to_end"]) == len(old["end_to_end"])
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] == [
        "window_moe_step.mfu", "window_moe_step.hbm_roofline",
        "window_attn_roofline", "window_moe_experts_roofline",
        "kv_window_rows_attended_share", "kv_pages_held_share"]


def test_runner_facts_counters_and_the_joined_readers(served):
    cell, r, f = served
    assert f["attn_route"] == "gather" and f["recompiles"] == 0
    assert f["chunk_attn_route"] == "window"
    assert f["kv_kinds"]["window"] == {"layers": 6, "window": 8,
                                       "pages_per_slot": 5, "n_pages": 15}
    assert f["kv_kinds"]["global"]["pages_per_slot"] == 24
    chunks = sum(-(-n // 8) for n, _ in f["served"])
    assert f["prefill_chunks"] == chunks > f["prefills"] > 0
    assert f["moe_pairs"] == f["tokens"] * 2 * 8       # top-2, eight layers
    assert f["attn_rows_attended_global"] == f["attn_rows_live"] * 2 / 8
    assert 0 < f["attn_rows_attended_window"] < f["attn_rows_live"] * 6 / 8
    assert f["kv_pages_recycled"] > 0
    assert "counter_samples" not in f and "kv_pages_in_use_window" not in f
    ctx = {"facts": f, "cell": cell,
           "peaks": harness.load_peaks("TPU v5 lite")}
    share = cell.reader("kv_window_rows_attended_share").read(ctx)
    assert share == pytest.approx(100.0 * f["attn_rows_attended_window"]
                                  / (f["attn_rows_live"] * 6 / 8))
    assert 0 < share < 100
    assert cell.reader("window_moe_step.mfu").read(ctx) > 0
    # the accepted readers whose lists the cell joined read it unedited
    assert cell.reader("serve_compile.in_window").read(ctx) == 0
    assert cell.reader("tpot_ms_p50").read(ctx) == f["tpot_ms_p50"] > 0
    ticks = dict(ctx, probe=r.probe)
    for m in ("ms_p50", "host_ms_p50", "emit_ms_p50", "launch_ms_p50",
              "admit_ms_p95"):
        assert cell.reader(f"decode_tick.{m}").read(ticks) > 0
    assert cell.reader("decode_tick.ms_p50").read(ticks) \
        > cell.reader("decode_tick.host_ms_p50").read(ticks)
    # a program with no such counters (the parent) gives the readers nothing
    bare = {"facts": {"served": f["served"], "config": {}, "steps": 3},
            "cell": cell, "peaks": ctx["peaks"],
            "probe": types.SimpleNamespace(traced=(0.0, 1.0))}
    for m in ("kv_window_rows_attended_share", "kv_pages_held_share",
              "window_moe_step.mfu", "window_moe_step.hbm_roofline",
              "window_attn_roofline", "window_moe_experts_roofline"):
        assert cell.reader(m).read(bare) is None


# --------------------------------------------------------------------- #
# counts by hand
# --------------------------------------------------------------------- #
def test_flops_and_bytes_against_a_hand_count():
    cfg = TOY_CFG
    p = window_moe.layer_params(cfg)
    # wq and wo 32 x 56 each, wk and wv 32 x 8 each
    assert p["attention"] == 2 * 32 * 56 + 2 * 32 * 8 == 4096
    assert p["router"] == 32 * 8 and p["expert"] == 3 * 32 * 16 == 1536
    assert window_moe.layer_param_count(cfg) == 4096 + 256 + 8 * 1536 + 64
    assert window_moe.param_count(cfg) \
        == 8 * 16704 + 2 * 128 * 32 + 32
    per_tok = 2 * (4096 + 256 + 2 * 1536)
    assert window_moe.layer_flops_per_token(cfg) == per_tok == 14848
    # a query at context 20 attends 8 keys in a window layer, 20 in a
    # global one: 4 x 7 heads x 8 a key
    assert window_moe.attention_flops(cfg, 20, True) == 224 * 8
    assert window_moe.attention_flops(cfg, 20, False) == 224 * 20
    assert window_moe.attention_flops(cfg, 5, True) == 224 * 5
    # a request of 10 prompt tokens and 3 served: 12 tokens through the
    # eight layers at contexts 1..12, the head 3 times
    att = sum(2 * window_moe.attention_flops(cfg, c, False)
              + 6 * window_moe.attention_flops(cfg, c, True)
              for c in range(1, 13))
    assert window_moe.sequence_flops(cfg, 10, 3) \
        == 8 * 12 * per_tok + att + 3 * 2 * 32 * 128
    assert window_moe.attend_cost(cfg, 100) == (224 * 100, 2 * 8 * 100 * 2)
    assert window_moe.moe_experts_cost(cfg, 4, 3) \
        == (2 * 1536 * 4, (3 * 1536 + 2 * 4 * 32) * 2)
    # a step that touched 30 experts and attended 500 rows, over its eight
    # layers: non-expert weights, norms and the head once
    assert window_moe.decode_step_bytes(cfg, 30, 500) == 2 * (
        8 * (4096 + 256 + 64) + 32 * 128 + 32 + 30 * 1536 + 500 * 2 * 8)


def test_published_sizes_are_the_issues():
    cell = harness.Cell(CELL)
    cfg, eng = cell.config, cell.traffic["engine"]
    assert window_moe.layer_param_count(cfg) == 398_627_840
    assert window_moe.param_count(cfg) == 3_966_937_600
    assert window_moe.kv_bytes_per_token(cfg) == 2048
    glob, ring = window_moe.pages_per_slot(cfg, eng)
    assert (glob, ring) == (128, 37)
    # a slot at max_context: two global layers' tables and six rings
    slot = (2 * glob + 6 * ring) * eng["page_size"] * 2048
    assert slot == 125_304_832 and eng["slots"] * slot == 4_009_754_624
    # all eight layers global: 268 MB a slot, 8.59 GB, which does not fit
    assert eng["slots"] * 8 * glob * eng["page_size"] * 2048 == 8_589_934_592
    # the decode step's weights: non-expert 0.34 GB, the head 0.78, all 64
    # experts of every layer 6.04
    p = window_moe.layer_params(cfg)
    assert round(8 * (p["attention"] + p["router"]) * 2 / 1e9, 2) == 0.34
    assert round(cfg["hidden_size"] * cfg["vocab_size"] * 2 / 1e9, 2) == 0.78
    assert round(8 * 64 * p["expert"] * 2 / 1e9, 2) == 6.04


# --------------------------------------------------------------------- #
# the device-trace readers on a synthetic trace
# --------------------------------------------------------------------- #
def test_op_scopes_name_the_two_pieces():
    hlo = (
        '  %fusion.7 = f32[32,4,7,4736]{3,2,1,0:T(8,128)} fusion(%a, %b), '
        'kind=kLoop, metadata={op_name="jit(fn)/jit(_window_attend)/exp"}\n'
        '  %gmm.3 = bf16[192,768]{1,0:T(8,128)(2,1)} custom-call(%x), '
        'metadata={op_name="jit(fn)/jit(_moe_experts)/jit(gmm)/pallas_call"}\n'
        '  ROOT %fusion.9 = (s32[32]{0}, pred[32]{0}) fusion(%c), '
        'metadata={op_name="jit(fn)/argmax"}\n')
    assert _window_moe.op_scopes(hlo) == {
        "%fusion.7 = f32[32,4,7,4736]": "_window_attend",
        "%gmm.3 = bf16[192,768]": "_moe_experts"}


def test_the_new_readers_on_a_synthetic_trace():
    cell = harness.Cell(CELL)
    cfg = cell.config
    peaks = harness.load_peaks("TPU v5 lite")
    zero = {"steps": 100.0, "moe_pairs": 0.0, "moe_experts_touched": 0.0,
            "attn_rows_live": 0.0, "attn_rows_attended_window": 0.0,
            "attn_rows_attended_global": 0.0,
            "kv_pages_in_use_global": 600.0, "kv_pages_in_use_window": 500.0}
    # 10 steps in the traced second; a step has 20 slots live at context
    # 6,000: 8 x 120,000 rows live, 6 x 20 x 4,096 attended in the window
    # layers and 2 x 120,000 in the global ones; 120 pairs a layer over 55
    # experts
    after = dict(zero, steps=110.0, moe_pairs=10 * 8 * 120.0,
                 moe_experts_touched=10 * 8 * 55.0,
                 attn_rows_live=10 * 8 * 120000.0,
                 attn_rows_attended_window=10 * 6 * 81920.0,
                 attn_rows_attended_global=10 * 2 * 120000.0)
    facts = {"config": cfg, "steps": 10.0,
             "attn_rows_live": after["attn_rows_live"],
             "attn_rows_attended_window": after["attn_rows_attended_window"],
             "counter_samples": [(4.9, zero), (5.0, zero), (5.5, zero),
                                 (6.0, after)],
             "op_scopes": {"%fusion.7 = f32[32,4,7,4736]": "_window_attend",
                           "%gmm.3 = bf16[192,768]": "_moe_experts"}}
    trace = {"modules": {"jit_fn(1)": [0.05] * 10,
                         "jit_prefill_chunk(2)": [0.04] * 30},
             "kernels": [("%fusion.7 = f32[32,4,7,4736]{3,2,1,0} "
                          "fusion(f32[8]{0})", 60, 0.2),
                         ("%gmm.3 = bf16[192,768]{1,0} custom-call(f32[8]{0})",
                          240, 0.08),
                         ("%fusion.8 = f32[8]{0} fusion(f32[8]{0})", 60, 0.1)]}
    ctx = {"cell": cell, "facts": facts, "peaks": peaks, "trace": trace,
           "probe": types.SimpleNamespace(traced=(5.02, 5.98))}
    # a step attends 491,520 + 240,000 rows of 2,048 B: 1.498 GB at 819
    # GB/s is 1.83 ms (memory-bound); ten steps of it against 0.2 s
    rows = 6 * 81920 + 2 * 120000
    got = cell.reader("window_attn_roofline").read(ctx)
    assert got == pytest.approx(100 * (rows * 2048 / 819e9) * 10 / 0.2)
    assert 0 < got < 100
    # the experts: 55 of 64 a layer, three matrices of 2560 x 768 bf16
    # each and the rows in and out, eight layers, ten steps, over 0.08 s
    layer_bytes = (55 * 3 * 2560 * 768 + 2 * 120 * 2560) * 2
    assert cell.reader("window_moe_experts_roofline").read(ctx) \
        == pytest.approx(100 * (8 * layer_bytes / 819e9) * 10 / 0.08)
    want = window_moe.decode_step_bytes(cfg, 8 * 55, rows)
    got = cell.reader("window_moe_step.hbm_roofline").read(ctx)
    assert got == pytest.approx(100 * want / 819e9 / 0.05)
    assert 0 < got < 100
    assert cell.reader("kv_window_rows_attended_share").read(ctx) \
        == pytest.approx(100 * 81920 / 120000)
    # 600 pages a global layer and 500 a window layer, against 600 a layer
    assert cell.reader("kv_pages_held_share").read(ctx) \
        == pytest.approx(100 * (600 * 2 + 500 * 6) / (600 * 8))
    # the readers whose lists the cell joined: the chunk program ran more
    # often than the step, and is not the step
    assert cell.reader("prefill_chunk.device_ms").read(ctx) == 40.0
    assert cell.reader("decode_step.device_ms").read(ctx) == 50.0
    # the parent: no samples, no scopes
    ctx["facts"] = {"config": cfg}
    for m in ("window_attn_roofline", "window_moe_experts_roofline",
              "window_moe_step.hbm_roofline", "kv_pages_held_share"):
        assert cell.reader(m).read(ctx) is None


def test_a_traced_run_starts_its_trace_mid_window():
    """A traced run leaves the schedule as `loadgen.make_schedule` draws
    it and starts the harness's trace half-way through the window."""
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
