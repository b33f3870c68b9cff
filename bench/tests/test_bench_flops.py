"""bench/flops.py against counts worked out by hand."""

import pytest

from bench import flops, harness


def test_bert_one_chip():
    # 12 layers of 4*2048^2 attention + 2*2048*8192 MLP weights, plus the
    # 30522 x 2048 head: 666,488,832 matmul parameters over 8 x 512 tokens
    params = 12 * (4 * 2048**2 + 2 * 2048 * 8192) + 30522 * 2048
    assert params == 666_488_832
    dense = 6 * params * 8 * 512
    attn = 3 * 4 * 512 * 512 * 2048 * 12 * 8
    got = flops.step_flops(harness.config("bert-base-paper"), harness.traffic("single.b8x512"))
    assert got == pytest.approx(dense + attn, rel=1e-12)
    assert got == pytest.approx(1.70e13, rel=5e-3)


def test_bert_dp4_counts_every_chip():
    one = flops.step_flops(harness.config("bert-base-paper"), harness.traffic("single.b8x512"))
    four = flops.step_flops(harness.config("bert-base-paper"), harness.traffic("dp4.b8x512"))
    assert four == pytest.approx(4 * one, rel=1e-12)


def test_whisper_batch_32():
    d, f, V, B = 768, 3072, 51865, 32
    enc = 12 * (4 * d * d + 2 * d * f) * B * 1500
    cross_kv = 12 * 2 * d * d * B * 1500
    dec = (12 * (6 * d * d + 2 * d * f) + V * d) * B * 448
    dense = 6 * (enc + cross_kv + dec)
    attn = 3 * 4 * d * B * 12 * (1500 * 1500 + 448 * 448 + 448 * 1500)
    got = flops.step_flops(harness.config("whisper-small"), harness.traffic("single.b32x448"))
    assert got == pytest.approx(dense + attn, rel=1e-12)
    assert dense == pytest.approx(4.05e13, rel=5e-3)
    assert attn == pytest.approx(1.105e13, rel=5e-3)


def test_unknown_family():
    with pytest.raises(ValueError):
        flops.step_flops({"family": "ssm", "config": {"d_model": 1, "d_ff": 1, "vocab": 1,
                                                       "n_layers": 1}},
                         {"batch_per_chip": 1, "chips": 1, "seq_len": 1})
