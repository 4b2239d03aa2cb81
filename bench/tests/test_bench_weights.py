"""The seeded weights make every sign of the 1-bit datapath independent of
the order and rounding of float sums: the reference gives the same
logits, bit for bit, when its attention runs block by block in another
order with its scores off by a relative 1e-4, as a sound kernel's may be
off by rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import loader

DENSE = {"family": "dense"}
reference = loader.family(DENSE, "reference")
weights = loader.family(DENSE, "weights")


@pytest.fixture(scope="module")
def tiny():
    conf = bench_tiny.cell("qwen-bnn.chat-steady").config
    assert conf["family"] == "dense"
    return conf, weights.make(conf, 2 ** 33 + 7)


def test_scores_zero_and_values_exact(tiny):
    _, p = tiny
    a = p["segments"][0]["l0"]["attn"]
    assert not np.any(a["q"]["w"]) and not np.any(a["q"]["b"])
    assert not np.any(a["v"]["b"])
    alpha = np.abs(np.asarray(a["v"]["w"])).mean(axis=1)
    assert set(np.unique(alpha)) <= {2.0 ** -5, 2.0 ** -6}
    for name in ("k", "o"):
        al = np.abs(np.asarray(a[name]["w"])).mean(axis=1)
        assert np.all(al > 0) and np.all(al / weights.ALPHA_QUANTUM
                                         == np.round(al / weights.ALPHA_QUANTUM))


def _blockwise(q, k, v, blk=8):
    """Online-softmax attention over key blocks, last block first, with
    every score scaled by a fixed factor within 1e-4 of 1."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    qpos = jnp.arange(t)

    def head(i):
        qi = q[:, i] * jnp.asarray(dh ** -0.5, q.dtype)
        ki, vi = k[:, i // group], v[:, i // group]
        m = jnp.full((t,), -1e30, q.dtype)
        l = jnp.zeros((t,), q.dtype)
        acc = jnp.zeros((t, dh), q.dtype)
        for b0 in reversed(range(0, t, blk)):
            kpos = jnp.arange(b0, min(b0 + blk, t))
            mask = kpos[None, :] <= qpos[:, None]
            off = jnp.cos(qpos[:, None] * 7.0 + kpos[None, :] * 3.0)
            s = jnp.where(mask, (qi @ ki[b0:b0 + blk].T) * (1 + 1e-4 * off),
                          -1e30)
            mn = jnp.maximum(m, s.max(-1))
            e = jnp.where(mask, jnp.exp(s - mn[:, None]), 0.0)
            corr = jnp.exp(m - mn)
            l = l * corr + e.sum(-1)
            acc = acc * corr[:, None] + e @ vi[b0:b0 + blk]
            m = mn
        return acc / l[:, None]

    out = jax.lax.map(head, jnp.arange(h))
    return out.transpose(1, 0, 2).reshape(t, h * dh)


def test_reference_logits_do_not_depend_on_attention_order(tiny, monkeypatch):
    conf, p = tiny
    rng = np.random.default_rng(3)
    toks = rng.integers(0, conf["vocab"], 96).astype(np.int32)
    pos = np.arange(96)
    plain = np.asarray(reference.logits_at(p, conf, toks, pos))
    monkeypatch.setattr(reference, "_causal_attention", _blockwise)
    reference._jitted.cache_clear()
    try:
        other = np.asarray(reference.logits_at(p, conf, toks, pos))
    finally:
        reference._jitted.cache_clear()
    np.testing.assert_array_equal(plain, other)
