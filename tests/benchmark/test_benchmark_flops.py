"""FLOP and byte counts against hand-worked values at one shape each."""
from benchmarks.flops import flash, lm, resnet

OLMO_L8 = dict(hidden_size=2048, intermediate_size=8192, num_hidden_layers=8,
               vocab_size=50304, tie_word_embeddings=True)


def test_lm_counts():
    # per layer: 4 x 2048^2 + 3 x 2048 x 8192 = 67,108,864 (+ 2 norms)
    assert lm.param_count(OLMO_L8) == 8 * (67108864 + 4096) \
        + 50304 * 2048 + 2048
    per_tok = 2 * (8 * 67108864 + 2048 * 50304)
    assert lm.matmul_flops_per_token(OLMO_L8) == per_tok == 1279787008
    # one query over 100 keys: QK^T and PV, 2 x 2 x 2048 x 100 a layer
    assert lm.attention_flops(OLMO_L8, 100) == 4 * 2048 * 100 * 8
    seq = 2048 * per_tok + 4 * 2048 * 8 * (2048 * 2049 // 2)
    assert lm.forward_flops_sequence(OLMO_L8, 2048) == seq
    assert abs(lm.train_flops_per_token(OLMO_L8, 2048)
               - 3 * seq / 2048) < 1e-3
    assert lm.decode_flops(OLMO_L8, 300) == per_tok + 4 * 2048 * 300 * 8
    # K and V of one token: 2 x 2048 x 8 layers x 2 bytes
    assert lm.kv_bytes_per_token(OLMO_L8) == 65536
    assert lm.decode_step_bytes(OLMO_L8, 1000) == \
        lm.param_count(OLMO_L8) * 2 + 1000 * 65536


def test_resnet50_counts():
    layers = resnet.conv_layers(50)
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    # the stem: 3 -> 64, 7x7, output 112 x 112
    assert layers[0][1:] == (3, 64, 7, 2, 112)
    assert 3 * 64 * 49 * 112 * 112 == 118013952
    # the published 4.09 GMACs of ResNet-50 v1.5 at 224 x 224
    assert resnet.forward_macs(50) == 4089184256
    assert resnet.train_flops_per_image(50) == 6 * 4089184256


def test_flash_counts():
    # 128 batch-heads, T 2048, head 128, causal: 2048 x 2049 / 2 pairs
    pairs = 2048 * 2049 // 2
    f, b = flash.fwd(128, 2048, 128)
    assert f == 4 * 128 * 128 * pairs
    assert b == 4 * 128 * 2048 * 128 * 2 + 128 * 2048 * 4
    f2, b2 = flash.bwd(128, 2048, 128)
    assert f2 == 10 * 128 * 128 * pairs
    assert b2 == 7 * 128 * 2048 * 128 * 2 + 2 * 128 * 2048 * 4
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    secs, bound = flash.roofline_seconds(f, b, peak)
    assert bound == "compute" and abs(secs - f / 197e12) < 1e-12
    assert flash.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")
