"""The seeded weights, the plain reference, the traffic schedules and the
work counts give, bit for bit, the values that the harness gave before
these parts moved into the family and process files (``loader.py``):
sha256 prefixes of each array"s bytes and the counts as floats,
recorded from that harness on the CPU."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import costs
import loader
import traffic_gen

WEIGHTS = {
    8589934599: {
        "embed/w": "1846a3e2fb81b624",
        "final_norm/scale": "2827f1d3934fbcdc",
        "segments/0/l0/attn/k/b": "ceb6a258692b7363",
        "segments/0/l0/attn/k/w": "ec506f55b0da4d66",
        "segments/0/l0/attn/o/w": "b01bb857b2a33ebd",
        "segments/0/l0/attn/q/b": "e5a00aa9991ac8a5",
        "segments/0/l0/attn/q/w": "8a39d2abd3999ab7",
        "segments/0/l0/attn/v/b": "e5a00aa9991ac8a5",
        "segments/0/l0/attn/v/w": "0e4736f049d009e4",
        "segments/0/l0/ffn/down/w": "7fec7588661a8238",
        "segments/0/l0/ffn/gate/w": "8b9ab2587ddb7209",
        "segments/0/l0/ffn/up/w": "edda7e6490a82ef4",
        "segments/0/l0/norm1/scale": "1e328585f46ad841",
        "segments/0/l0/norm2/scale": "b2417d427d39a5fb",
    },
    5: {
        "embed/w": "0d88697d1c1265a4",
        "final_norm/scale": "6c97e7803934af4b",
        "segments/0/l0/attn/k/b": "65ee3be67cb69340",
        "segments/0/l0/attn/k/w": "781935a7314f4f9b",
        "segments/0/l0/attn/o/w": "bd8f602d8838cba8",
        "segments/0/l0/attn/q/b": "e5a00aa9991ac8a5",
        "segments/0/l0/attn/q/w": "8a39d2abd3999ab7",
        "segments/0/l0/attn/v/b": "e5a00aa9991ac8a5",
        "segments/0/l0/attn/v/w": "cbb4b64a255a9131",
        "segments/0/l0/ffn/down/w": "e95c3797ed363def",
        "segments/0/l0/ffn/gate/w": "6a5b6b7f26d9cc60",
        "segments/0/l0/ffn/up/w": "3a9a8ff1dfd2bd85",
        "segments/0/l0/norm1/scale": "b2026bde39592a38",
        "segments/0/l0/norm2/scale": "180d821faf57e63d",
    },
}

REFERENCE = {"float32": "aa06d7ae1754ec14",
             "bfloat16": "469f030ac9cd5d3a"}

SCHEDULES = {"chat-steady/2147495993": {"n": 122,
                                        "times": "fc88eaca992ba5aa",
                                        "lengths": "ee862f96528bb1cd",
                                        "prompts": "3319b818d5d67bfc",
                                        "greedy": "b7dada0ab3caab33"},
             "chat-steady/7": {"n": 122,
                               "times": "ffbee26e778eab95",
                               "lengths": "cf8e3a67c1a35954",
                               "prompts": "5ccf64cc788b7cfb",
                               "greedy": "b4b9ae8e7706f1ff"},
             "rag-long/2147495993": {"n": 92,
                                     "times": "5a215c1c10a33f1c",
                                     "lengths": "f538713610f20954",
                                     "prompts": "da48b4545972afdc",
                                     "greedy": "7c3fe225960c427e"},
             "rag-long/7": {"n": 92,
                            "times": "ff04ae3663532f06",
                            "lengths": "b29bb71d8f6041eb",
                            "prompts": "d5b8c4098364631f",
                            "greedy": "cae3bee69966d2c3"}}

COSTS = {"decode32": {"gemms": [19730006016.0, 100122624.0],
                      "attention": [2118647808.0, 4243587072.0],
                      "step_least_s": 0.00011162711048134228},
         "prefill256": {"gemms": [157840048128.0, 524009472.0],
                        "attention": [48330964992.0, 452984832.0],
                        "step_least_s": 0.0010523152723177433}}

PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
         "hbm_bytes_per_s": 819e9}
ROWS = {"decode32": [(1, 100 + 37 * i) for i in range(32)],
        "prefill256": [(256, 2048)]}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def conf():
    return bench_tiny.cell("qwen-bnn.chat-steady").config


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_weight_leaves_as_recorded(conf, seed):
    p = loader.family(conf, "weights").make(conf, seed)
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    got = {jax.tree_util.keystr(k, simple=True, separator="/"): digest(v)
           for k, v in flat}
    assert got == WEIGHTS[seed]


@pytest.mark.parametrize("dtype", sorted(REFERENCE))
def test_reference_logits_as_recorded(conf, dtype):
    p = loader.family(conf, "weights").make(conf, 2 ** 33 + 7)
    toks = np.random.default_rng(3).integers(0, conf["vocab"], 96)
    got = loader.family(conf, "reference").logits_at(
        p, conf, toks.astype(np.int32), np.array([0, 17, 50, 95]),
        dtype=getattr(jnp, dtype))
    assert digest(got) == REFERENCE[dtype]


@pytest.mark.parametrize("key", sorted(SCHEDULES))
def test_schedule_as_recorded(key):
    name, seed = key.split("/")
    rate = {"chat-steady": 2.0, "rag-long": 1.5}[name]
    mix = traffic_gen.load(os.path.join(bench_tiny.BENCH, "traffic",
                                        name + ".json"))
    arr = traffic_gen.schedule(mix, rate, 61.0, 151936, int(seed))
    got = {"n": len(arr),
           "times": digest(np.array([a.due_s for a in arr], np.float64)),
           "lengths": digest(np.array([[len(a.prompt), a.max_new]
                                       for a in arr], np.int64)),
           "prompts": digest(np.concatenate([a.prompt for a in arr])
                             .astype(np.int32)),
           "greedy": digest(np.array([[a.greedy, a.temperature, a.top_p,
                                       a.seed] for a in arr], np.float64))}
    assert got == SCHEDULES[key]


@pytest.mark.parametrize("rows", sorted(COSTS))
def test_qwen_work_counts_as_recorded(rows):
    with open(os.path.join(bench_tiny.BENCH, "configs",
                           "qwen1.5-0.5b-bnn.json")) as f:
        qwen = json.load(f)
    r = ROWS[rows]
    tokens = sum(q for q, _ in r)
    g, a = costs.gemms(qwen, tokens), costs.attention(qwen, r)
    assert [g.ops, g.bytes] == COSTS[rows]["gemms"]
    assert [a.ops, a.bytes] == COSTS[rows]["attention"]
    assert costs.step_least_s(qwen, r, PEAKS) == COSTS[rows]["step_least_s"]
