"""Operations and bytes from the configurations' shapes, against hand
counts."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import flops, harness  # noqa: E402

QWEN = harness.load_json(harness.config_file("qwen2-0.5b"))
PHI4 = harness.load_json(harness.config_file("phi4-mini-3.8b"))

# q, k, v, o and three SwiGLU matrices, counted by hand from the widths
QWEN_LAYER = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
PHI4_LAYER = 3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072 + 3 * 3072 * 8192


def test_layer_and_head_params():
    assert flops.layer_matmul_params(QWEN) == QWEN_LAYER == 14_909_440
    assert flops.layer_matmul_params(PHI4) == PHI4_LAYER == 100_663_296
    assert flops.head_params(QWEN) == 896 * 151_936
    assert flops.head_params(PHI4) == 3072 * 200_064


def test_weight_and_cache_bytes():
    # 0.99 GB and 7.67 GB of bf16 matrices (biases and norms aside)
    assert flops.weight_bytes(QWEN) == 2 * (24 * QWEN_LAYER + 896 * 151_936) == 987_922_432
    assert flops.weight_bytes(PHI4) == 2 * (32 * PHI4_LAYER + 3072 * 200_064) == 7_671_644_160
    assert flops.kv_bytes_per_token(QWEN) == 12_288
    assert flops.kv_bytes_per_token(PHI4) == 131_072


@pytest.mark.parametrize("cfg,layers,heads,hd", [(QWEN, 24, 14, 64), (PHI4, 32, 24, 128)])
def test_decode_flops(cfg, layers, heads, hd):
    per_token = 2 * (layers * flops.layer_matmul_params(cfg) + flops.head_params(cfg))
    # two tokens at contexts 10 and 20 keys: scores and sum, 2 FLOPs a MAC
    attn = 4 * layers * heads * hd * (10 + 20)
    assert flops.decode_flops(cfg, [10, 20]) == 2 * per_token + attn
    assert flops.decode_flops(cfg, []) == 0


@pytest.mark.parametrize("cfg,layers,heads,hd", [(QWEN, 24, 14, 64), (PHI4, 32, 24, 128)])
def test_prefill_flops(cfg, layers, heads, hd):
    # a 5-token prompt prefills 4 tokens, attending to 1, 2, 3 and 4 keys
    trunk = 2 * layers * flops.layer_matmul_params(cfg) * 4
    assert flops.prefill_flops(cfg, 5) == trunk + 4 * layers * heads * hd * 10
    assert flops.prefill_flops(cfg, 1) == 0


def test_greedy_sample_roofline_floor():
    ops, nbytes = flops.greedy_sample_cost(16, 151_936)
    assert nbytes == 16 * 151_936 * 4 + 16 * 4
    # reading 9.7 MB at 819 GB/s bounds it, not the 2.4 M compares
    t = flops.least_time_s(ops, nbytes, 197e12, 819e9)
    assert t == pytest.approx(nbytes / 819e9)
