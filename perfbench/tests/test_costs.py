"""costs.py against hand-worked numbers."""

import pytest

import costs
import manifest

MISTRAL = manifest.load_json("configs", "mistral-7b-d16.json")
QWEN = manifest.load_json("configs", "qwen2.5-7b-d14.json")


def test_kv_bytes_a_token():
    assert costs.kv_bytes_per_token(MISTRAL) == 64 * 1024  # 2 x 16 layers x 8 heads x 128 x 2 B
    assert costs.kv_bytes_per_token(QWEN) == 28 * 1024     # 2 x 14 x 4 x 128 x 2 B


def test_parameters():
    assert costs.params_per_layer(MISTRAL) == pytest.approx(218.1e6, rel=5e-4)
    assert costs.params_per_layer(QWEN) == pytest.approx(233.1e6, rel=5e-4)
    assert costs.params_total(MISTRAL) == pytest.approx(3.75e9, rel=2e-3)
    assert costs.params_total(QWEN) == pytest.approx(4.35e9, rel=2e-3)


def test_decode_reads_the_window_at_most():
    assert costs.window(MISTRAL) == 4096 and costs.window(QWEN) is None
    assert costs.decode_attn_bytes(MISTRAL, [100, 5000]) == (100 + 4096) * 65536
    assert costs.decode_attn_bytes(QWEN, [100, 5000]) == 5100 * 28672
