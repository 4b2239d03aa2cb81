"""Operation and byte counts against hand-worked numbers at the
published shapes of qwen1.5-0.5b."""
import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import costs

CONFIGS = os.path.join(bench_tiny.BENCH, "configs")


def conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


QWEN = conf("qwen1.5-0.5b-bnn")
PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
         "hbm_bytes_per_s": 819e9}


def test_bnn_gemm_by_hand():
    # 32 decode rows through qwen's 1024 -> 2816 gate projection
    w = costs.bnn_gemm(32, 1024, 2816)
    assert w.ops == 2 * 32 * 1024 * 2816 == 184_549_376
    # packed weight 360,448 + alpha 11,264 + x 131,072 + out 360,448
    assert w.bytes == 863_232


def test_weights_per_token():
    # q, k, v, o: 1024x1024; gate, up: 1024x2816; down: 2816x1024
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    w = costs.gemms(QWEN, 1)
    assert w.ops == 2 * per_layer * 24
    assert sum(k * n for k, n in costs.projections(QWEN)) == per_layer


def test_qwen_weight_bytes_one_decode_row():
    # 308,281,344 one-bit weights = 38,535,168 bytes, plus alphas,
    # activations and outputs of one row in every projection
    w = costs.gemms(QWEN, 1)
    bits = 12_845_056 * 24
    n_cols = (4 * 1024 + 2 * 2816 + 1024) * 24
    k_rows = (6 * 1024 + 2816) * 24
    assert w.bytes == bits / 8 + 4 * n_cols + 4 * k_rows + 4 * n_cols


def test_paged_attention_decode_row():
    # one decode token with 100 tokens of context, 16 heads of 64
    w = costs.attention(QWEN, [(1, 100)])
    assert w.ops == 4 * 16 * 64 * 100 * 24 == 9_830_400
    assert w.bytes == (2 * 100 * 16 * 64 * 4 + 2 * 16 * 64 * 4) * 24


def test_paged_attention_prefill_chunk_is_causal():
    # 4 new tokens after 6 cached: 6+7+8+9 = 30 query-key pairs... plus
    # their own positions: token i sees 6 + i + 1 keys -> 7+8+9+10 = 34
    w = costs.attention(QWEN, [(4, 10)])
    assert w.ops == 4 * 1024 * 34 * 24


def test_step_least_time_adds_int8_and_float_parts():
    rows = [(1, 100), (1, 300)]
    t = costs.step_least_s(QWEN, rows, PEAKS)
    gem = costs.gemms(QWEN, 2).ops / 393e12
    flt = (costs.float_ops(QWEN, 2) + costs.attention(QWEN, rows).ops) \
        / 197e12
    assert t == pytest.approx(gem + flt, rel=1e-12)
    # the tied head alone: 2 * 1024 * 151936 per token
    assert costs.float_ops(QWEN, 1) > 2 * 1024 * 151936


def test_float_ops_per_token_by_hand():
    # head 2*1024*151936 + final norm 4*1024; per layer 8*1024 + 4*2816
    # + 6*(16+16)*64 = 31,744
    assert costs.float_ops(QWEN, 3) == 3 * (2 * 1024 * 151936 + 4 * 1024
                                            + 24 * 31_744)


def test_roofline_takes_the_larger_bound():
    w = costs.Work(ops=393e12, bytes=819e9 / 2)
    assert costs.roofline_s(w, 393e12, 819e9) == pytest.approx(1.0)
    w = costs.Work(ops=1.0, bytes=819e9)
    assert costs.roofline_s(w, 393e12, 819e9) == pytest.approx(1.0)
