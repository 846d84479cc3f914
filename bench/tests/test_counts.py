"""Hand counts for the FLOP and byte functions. Run: python -m pytest bench/tests"""
from bench.lib import counts

# Qwen2.5-3B's published widths (hf:Qwen/Qwen2.5-3B config.json), depth cut
# from 36 to 10 layers
CFG = {"hidden_size": 2048, "intermediate_size": 11008,
       "num_attention_heads": 16, "num_key_value_heads": 2,
       "num_hidden_layers": 10, "vocab_size": 151936}


def test_qwen_10_layer_cut_has_1_08_b_params():
    p = counts.qwen2_params(CFG)
    # embed 151936*2048; per layer: q,o 2048^2 each, k,v 2048*256 each,
    # biases 2048+2*256, MLP 3*2048*11008, two norms 2048 each
    per_layer = 2 * 2048 ** 2 + 2 * 2048 * 256 + 2048 + 512 + 3 * 2048 * 11008 + 4096
    assert p["per_layer"] == per_layer == 77_076_992
    assert p["embed"] == 311_164_928
    assert p["total"] == 311_164_928 + 10 * per_layer + 2048
    assert 1.08e9 < p["total"] < 1.085e9


def test_lm_flops_per_step():
    per_tok = counts.lm_train_flops_per_token(CFG, 512)
    p = counts.qwen2_params(CFG)
    assert per_tok == 6 * p["matmul"] + 3 * 2 * 2 * 2048 * 513 / 2 * 10
    # about 1.33e13 per 2048-token step
    assert 1.33e13 < per_tok * 2048 < 1.35e13


def test_ssca_update_bytes():
    # bf16 params and gradient, f32 buffer: 2+2 read/write params, 4+4 g, 2 grad
    assert counts.ssca_update_bytes(1000, 2, 2) == 14_000


def test_int8_encode_bytes():
    # one 512-float upload: 2 chunks of 256
    assert counts.int8_encode_bytes(1, 512) == 512 * 13 + 2 * 4
    assert counts.int8_encode_bytes(256, 101_632) == 256 * (101_632 * 13 + 397 * 4)


def test_mlp_counts():
    p = counts.mlp_params(784, 128, 10)
    assert p == 101_632
    assert counts.mlp_round_flops(p, 256 * 16) == 6 * 101_632 * 4096
