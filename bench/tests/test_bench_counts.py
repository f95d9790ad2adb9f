"""The Llama family's work counts and the peaks table: smollm's FLOPs and
bytes equal a hand reckoning, decode bytes follow live length and not the
cache size, and an unknown device is an error."""
import pytest

from bench import counts
from bench.families import llama
from bench.harness import load_config

SMOLLM = load_config("smollm-135m")
QWEN = load_config("qwen1.5-0.5b")


def test_smollm_parameters_by_hand():
    # Q 576x576, K and V 576x192 each, O 576x576, SwiGLU 3 x 576x1536.
    per_layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert per_layer == 3_538_944
    assert llama.layer_matmul_params(SMOLLM) == per_layer
    # Plus two norms a layer, the final norm and the tied 49152 x 576 table.
    assert llama.weight_count(SMOLLM) == 30 * (per_layer + 2 * 576) + 576 + 49152 * 576
    assert llama.weight_count(SMOLLM) == 134_515_008


def test_kv_bytes_per_token():
    assert llama.kv_bytes_per_token(SMOLLM) == 30 * 2 * 3 * 64 * 2 == 23_040
    assert llama.kv_bytes_per_token(QWEN) == 24 * 2 * 16 * 64 * 2 == 98_304


def test_smollm_prefill_by_hand():
    n = 256
    matmul = 2 * n * 3_538_944
    attention = 2 * 9 * 64 * n * (n + 1)          # causal QK^T and PV
    flops = 30 * (matmul + attention) + 2 * 576 * 49152
    nbytes = 2 * 134_515_008 + n * 23_040 + 4 * 49152
    assert llama.prefill_work(SMOLLM, n) == (flops, nbytes)


def test_smollm_decode_by_hand():
    positions = [100, 300]
    flops = 2 * (30 * 2 * 3_538_944 + 2 * 576 * 49152)
    flops += 30 * 4 * 9 * 64 * (101 + 301)
    nbytes = 2 * 134_515_008 + (100 + 300) * 23_040 + 2 * 23_040 + 4 * 2 * 49152
    assert llama.decode_work(SMOLLM, positions) == (flops, nbytes)


def test_decode_bytes_follow_live_length_not_cache_size():
    short = llama.decode_work(SMOLLM, [100])[1]
    longer = llama.decode_work(SMOLLM, [200])[1]
    assert longer - short == 100 * 23_040
    assert llama.decode_work(SMOLLM, []) == (0.0, 0.0)
    # Idle slots add nothing: one active slot of 64 costs one slot's K/V.
    assert llama.decode_work(SMOLLM, [5])[1] < llama.decode_work(SMOLLM, [5, 5])[1]


def test_qwen_counts_its_bias():
    no_bias = dict(QWEN, qkv_bias=False)
    assert llama.weight_count(QWEN) - llama.weight_count(no_bias) == 24 * 3 * 1024


def test_peaks_table():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_least_seconds_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds((1000.0, 10.0), peak) == 10.0
    assert counts.least_seconds((10.0, 1000.0), peak) == 100.0
