"""The traffic generator: seeded, the same work for every seed, and the
lengths and arrivals its mix files ask for."""
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401
import traffic_gen as tg

MIXES = os.path.join(bench_tiny.BENCH, "traffic")
SEED = 2 ** 31 + 12345          # past 32 signed bits, as large seeds are


def mix(name):
    return tg.load(os.path.join(MIXES, name + ".json"))


@pytest.mark.parametrize("name", ["chat-steady", "rag-long"])
def test_same_seed_same_schedule(name):
    a = tg.schedule(mix(name), 2.0, 120, 1000, SEED)
    b = tg.schedule(mix(name), 2.0, 120, 1000, SEED)
    assert len(a) == len(b) > 100
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt) and x.seed == y.seed


def test_seeds_offer_the_same_work_in_another_order():
    m = mix("chat-steady")
    a = tg.schedule(m, 2.0, 200, 1000, 1)
    b = tg.schedule(m, 2.0, 200, 1000, 2)
    la = sorted(len(x.prompt) for x in a)
    lb = sorted(len(x.prompt) for x in b)
    assert len(a) == len(b)
    n = min(len(a), len(b))
    assert np.mean(np.abs(np.array(la[:n]) - np.array(lb[:n]))) < 20
    assert [x.due_s for x in a[:5]] != [x.due_s for x in b[:5]]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name,median_p,lo,hi", [
    ("chat-steady", 256, 32, 2048), ("rag-long", 2048, 1024, 4096)])
def test_prompt_lengths(name, median_p, lo, hi):
    arr = tg.schedule(mix(name), 4.0, 300, 1000, SEED)
    lens = np.array([len(a.prompt) for a in arr])
    assert lens.min() >= lo and lens.max() <= hi
    assert abs(np.median(lens) - median_p) <= 0.1 * median_p


def test_output_lengths_clipped():
    m = mix("chat-steady")
    arr = tg.schedule(m, 4.0, 300, 1000, SEED)
    out = np.array([a.max_new for a in arr])
    assert out.min() >= 16 and out.max() <= 512
    assert abs(np.median(out) - 128) <= 13


def test_poisson_rate_and_gaps():
    arr = tg.schedule(mix("chat-steady"), 3.0, 400, 1000, SEED)
    t = np.array([a.due_s for a in arr])
    assert abs(len(t) / 400 - 3.0) < 0.15
    gaps = np.diff(t)
    # exponential gaps: coefficient of variation near 1
    assert 0.85 < gaps.std() / gaps.mean() < 1.15


def test_shared_documents():
    m = mix("rag-long")
    arr = tg.schedule(m, 2.0, 200, 1000, SEED)
    docs = {}
    shared = 0
    for a in arr:
        key = a.prompt[:1536].tobytes()
        docs[key] = docs.get(key, 0) + 1
    shared = sum(n for n in docs.values() if n > 1)
    assert len([n for n in docs.values() if n > 1]) == 4
    assert abs(shared / len(arr) - 0.6) < 0.05
    lens = np.array([len(a.prompt) for a in arr])
    assert lens.min() >= 1024 and lens.max() <= 4096


def test_greedy_share():
    m = mix("chat-steady")
    arr = tg.schedule(m, 2.0, 200, 1000, SEED)
    g = [a for a in arr if a.greedy]
    assert abs(len(g) - len(arr) / m["sampling"]["greedy_every"]) <= 1
    assert all(a.temperature == 0.0 for a in g)
    assert all(a.temperature == 0.7 and a.top_p == 0.9
               for a in arr if not a.greedy)
