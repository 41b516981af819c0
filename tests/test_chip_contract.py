"""The contract of the measured path (ISSUE 21): ``chip_smoke.py`` and
``bench.py`` run on the chip or not at all, the compile cache can be
placed from outside, and the attention route is a decision one can ask
about.  All on the CPU, in seconds; the chip itself is exercised by
``chip_smoke.py`` through the chip tool."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import attention_path
from bigdl_tpu.utils import engine

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("argv", [["chip_smoke.py"], ["bench.py", "lenet"]],
                         ids=["chip_smoke", "bench"])
def test_measured_entry_points_refuse_the_cpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable] + argv, cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():     # no result, no metric line
        if line.startswith("{"):
            doc = json.loads(line)
            assert "ok" not in doc and "metric" not in doc, line


def test_chip_smoke_result_line_has_the_contract_keys_only():
    """The driver refuses a last line with any other key (the stage
    list, seconds and ``claim`` go on the ``summary`` line before it)."""
    sys.path.insert(0, _REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(_REPO)
    doc = json.loads(chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}))
    assert doc == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    assert type(doc["device"]["count"]) is int


@pytest.fixture
def restore_cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set the helper names no directory
    in code — not even the one passed in."""
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert engine.enable_compile_cache() == outside
    assert engine.enable_compile_cache(str(tmp_path / "mine")) == outside
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_compile_cache_defaults_into_the_checkout(monkeypatch, tmp_path,
                                                  restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_REPO, ".jax_cache")
    assert engine.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert want == os.path.normpath(want) and os.path.isabs(want)
    # a caller's directory is normalised the same way
    spelled = os.path.join(str(tmp_path), "a", os.pardir, "cache")
    assert engine.enable_compile_cache(spelled) \
        == os.path.join(str(tmp_path), "cache")


def test_attention_path_names_route_and_reason():
    bench = (8, 8, 2048, 128)
    assert attention_path(bench, bench, jnp.bfloat16,
                          backend="tpu")[0] == "pallas"
    tiny = (2, 2, 256, 64)                    # PRESETS["tiny"]'s head_dim
    path, why = attention_path(tiny, tiny, jnp.float32, backend="tpu")
    assert path == "blockwise" and "head_dim 64" in why
    path, why = attention_path(bench, bench, jnp.bfloat16, backend="cpu")
    assert path == "blockwise" and "cpu" in why
    # past what the kernel compiles at (measured on a v5e): visible, too
    long = (1, 8, 16384, 128)
    path, why = attention_path(long, long, jnp.bfloat16, backend="tpu")
    assert path == "blockwise" and "VMEM" in why
    ok = (1, 8, 15360, 128)
    assert attention_path(ok, ok, jnp.bfloat16, backend="tpu")[0] == "pallas"


@pytest.mark.slow
def test_chip_smoke_stages_tiny_on_cpu():
    """Every chip_smoke stage but the device gate, at toy sizes, Pallas
    in interpret mode: how to debug the smoke before spending chip time."""
    sys.path.insert(0, _REPO)
    import chip_smoke
    from bigdl_tpu.ops import flash_attention_mod as fa
    tiny = dict(
        conv=dict(depth=18, class_num=10, batch=4, image=224, iters=3),
        lm=dict(vocab_size=128, d_model=256, n_heads=2, n_layers=2,
                d_ff=256, max_len=256, dtype="bfloat16"),
        lm_batch=4, lm_seq=128, lm_steps=3,
        serve=dict(slots=4, max_context=128, max_prompt=16,
                   prompts=(3, 9, 16), new_tokens=(4, 8, 6),
                   sparse=dict(
                       lm=dict(vocab_size=128, d_model=64, n_heads=4,
                               n_kv_heads=2, head_dim=16, n_layers=2,
                               d_ff=32, moe_experts=8, moe_top_k=2,
                               moe_capacity_factor=None, qk_norm=True,
                               index_heads=2, index_dim=8, index_top_k=8,
                               max_len=128, dtype="bfloat16"),
                       slots=2, max_context=64, max_prompt=40,
                       prefill_chunk=16, prompts=(20, 40),
                       new_tokens=(4, 4)),
                   windowed=dict(
                       lm=dict(vocab_size=128, d_model=64, n_heads=7,
                               n_kv_heads=1, head_dim=16, n_layers=4,
                               d_ff=32, moe_experts=8, moe_top_k=2,
                               moe_capacity_factor=None,
                               moe_activation="relu",
                               moe_router_pre_attention=True,
                               windows=(0, 8, 8, 8),
                               rope_layers=(False, True, True, True),
                               max_len=128, dtype="bfloat16"),
                       window=8, page_size=4, slots=3, max_context=96,
                       max_prompt=64, prefill_chunk=8, prompts=(3, 30, 60),
                       new_tokens=(4, 4, 4)),
                   latent=dict(
                       lm=dict(vocab_size=128, d_model=64, n_heads=4,
                               n_layers=3, d_ff=32, q_lora_rank=32,
                               kv_lora_rank=16, qk_nope_dim=16,
                               qk_rope_dim=8, v_head_dim=16,
                               rope_scaling=dict(
                                   factor=40, beta_fast=32, beta_slow=1,
                                   original_max_position_embeddings=16,
                                   mscale=1, mscale_all_dim=1),
                               dense_layers=1, dense_d_ff=96,
                               moe_experts=16, moe_top_k=4,
                               moe_capacity_factor=None,
                               moe_scoring="sigmoid", moe_groups=4,
                               moe_top_groups=2, moe_routed_scale=2.5,
                               moe_router_bias=True, moe_held=(4, 8),
                               moe_shared_d_ff=32, max_len=128,
                               dtype="bfloat16"),
                       page_size=4, slots=3, max_context=96,
                       max_prompt=64, prefill_chunk=8, prompts=(3, 30, 60),
                       new_tokens=(4, 4, 4))),
        flash_shape=(1, 2, 256, 128), optim_leaf=(300, 130),
        four_conv_batch=8, four_conv_iters=3)
    old = fa._INTERPRET
    fa._INTERPRET = True
    try:
        ctx = chip_smoke.Ctx(tiny, native=False)
        results = chip_smoke.run(
            ["conv", "transformer", "serve", "kernels", "four_chips"], ctx)
    finally:
        fa._INTERPRET = old
        sys.path.remove(_REPO)
    assert "skipped" not in results["four_chips"]
    assert results["serve"]["warmup_compiles"] == 6
    assert results["serve"]["sparse"]["attn_route"] == "sparse"
    assert results["serve"]["sparse"]["chunk_attn_route"] == "window"
    windowed = results["serve"]["windowed"]
    assert windowed["kv_kinds"]["window"]["pages_per_slot"] == 5
    assert windowed["kv_kinds"]["global"]["pages_per_slot"] == 24
    assert windowed["pages_recycled"] > 0
    latent = results["serve"]["latent"]
    assert latent["pool"] == {"latent": (72, 4, 24)}
    assert latent["attn_route"] == latent["chunk_attn_route"] == "latent"
    assert 0 < latent["local_pairs_share"] < 1
