import pytest

from chipbench import flops


def test_causal_pairs_by_hand():
    # 4 queries over 4 keys: 1 + 2 + 3 + 4
    assert flops.causal_pairs(4, 4) == 10
    # the last 2 of 5 positions: query 3 sees 4 keys, query 4 sees 5
    assert flops.causal_pairs(2, 5) == 9


def test_flash_forward_ops_and_bytes_by_hand():
    # b=1, s=4, 2 q heads on 1 kv head, d=8, causal: 10 pairs
    w = flops.flash_fwd(b=1, sq=4, sk=4, hq=2, hkv=1, d=8, causal=True)
    # QK^T: 10 pairs x 8 mul-adds x 2 ops; PV the same; 2 heads
    assert w["ops"] == 2 * (10 * 8 * 2) * 2
    # Q and O: 4x2x8 each; K and V: 4x1x8 each; bf16
    assert w["bytes"] == 2 * (2 * 4 * 2 * 8 + 2 * 4 * 1 * 8)
    full = flops.flash_fwd(b=1, sq=4, sk=4, hq=2, hkv=1, d=8, causal=False)
    assert full["ops"] == 2 * (16 * 8 * 2) * 2


def test_flash_backward_is_twice_the_forward_matmuls():
    f = flops.flash_fwd(b=2, sq=128, sk=128, hq=4, hkv=2, d=16)
    b = flops.flash_bwd(b=2, sq=128, sk=128, hq=4, hkv=2, d=16)
    assert b["ops"] == 2 * f["ops"]
    assert b["bytes"] == 2 * f["bytes"]


def test_roofline_says_which_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    r = flops.roofline_seconds({"ops": 1000.0, "bytes": 10.0}, peaks)
    assert r["bound"] == "compute" and r["seconds"] == 10.0
    r = flops.roofline_seconds({"ops": 10.0, "bytes": 1000.0}, peaks)
    assert r["bound"] == "memory" and r["seconds"] == 100.0


def test_mistral_7b_parameter_count():
    cfg = dict(hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_attention_heads=32,
               num_key_value_heads=8, head_dim=128, vocab_size=32768)
    p = flops.dense_decoder_params(cfg)
    assert p["layer"] == 218_112_000 + 0  # 41.9 M attn + 176.2 M mlp + norms
    total = p["layers"] + p["embed"] + p["head"] + p["final_norm"]
    assert total == 7_248_023_552        # the published 7.25 B
    per_tok = flops.train_ops_per_token(dict(cfg, num_hidden_layers=4), 2048)
    matmul = 4 * p["layer"] + p["head"]
    attn = 6 * 2 * 128 * 32 * 1024.5 * 4
    assert per_tok == pytest.approx(6 * matmul + attn)
