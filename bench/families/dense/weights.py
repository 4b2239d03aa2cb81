"""Seeded weights for the benchmark, made on the device in one jitted call.

The tree has the layout the serving engine takes (embedding, final norm,
and one segment of layers stacked on a leading axis); ``run.py`` checks
it against the program's own parameter shapes before serving.  The
values are the benchmark's: the plain reference (``reference.py``)
rebuilds the same tree from the same seed and never reads the program's.

Projection weights are binary with one scale per output column,
``w[k, n] = s[k, n] * alpha[n]`` with ``s`` in {-1, +1}: what a BNN holds
at inference, where only sign(w) and alpha = mean(|w|) over k are used.
Each alpha is a multiple of 2**-14 below 2**-4, so the mean over any
k <= 2**14 rows is exact in float32 however a program orders the sum.

The values make every sign that the 1-bit datapath takes independent of
the order of float sums, so that a sound program and the reference agree
bit for bit up to the tied head.  On this datapath one sign decided by
rounding changes every later layer's input (a BNN GEMM spreads one
flipped input bit to all of its outputs), so a check that compares
served tokens with a reference needs this:

* the query projection is zero (its weights and bias), so every score
  is exactly 0 and causal attention is the exact mean of the values over
  the context: ``exp(0) = 1`` in any implementation, the sum of the
  values is exact, and the sign of the mean is the sign of that sum;
* the value projection has no bias and power-of-two column scales, so a
  value is ``2**-5`` or ``2**-6`` times an even integer of magnitude
  below 256: exact in bfloat16, and every sum of values over a context
  of up to 2**16 positions is exact in float32.

So paged attention is checked through the value cache (which blocks a
row reads, the causal and length masks, chunking, prefix reuse), not
through the scores; ``PERF.md`` says what this leaves unchecked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ALPHA_QUANTUM = 2.0 ** -14


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, including ones past 2**31."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def _binary(key, layers: int, k: int, n: int) -> jax.Array:
    ks, ka = jax.random.split(key)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (layers, k, n)), 1.0, -1.0)
    base = math.sqrt(2.0 / math.pi) / math.sqrt(k)       # E|N(0, 1/k)|
    u = jax.random.uniform(ka, (layers, 1, n), minval=0.75, maxval=1.25)
    alpha = jnp.maximum(jnp.round(base * u / ALPHA_QUANTUM), 1.0) \
        * ALPHA_QUANTUM
    return (sign * alpha).astype(jnp.float32)


def _binary_pow2(key, layers: int, k: int, n: int) -> jax.Array:
    """Binary weights whose column scales are 2**-5 or 2**-6."""
    ks, ka = jax.random.split(key)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (layers, k, n)), 1.0, -1.0)
    alpha = jnp.where(jax.random.bernoulli(ka, 0.5, (layers, 1, n)),
                      2.0 ** -5, 2.0 ** -6)
    return (sign * alpha).astype(jnp.float32)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape) * std).astype(jnp.float32)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, minval=lo, maxval=hi,
                              dtype=jnp.float32)


def _dense_layers(key, c) -> dict:
    n, d = c["n_layers"], c["d_model"]
    hq, hkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    ks = iter(jax.random.split(key, 16))
    attn = {"q": {"w": jnp.zeros((n, d, hq * dh), jnp.float32)},
            "k": {"w": _binary(next(ks), n, d, hkv * dh)},
            "v": {"w": _binary_pow2(next(ks), n, d, hkv * dh)},
            "o": {"w": _binary(next(ks), n, hq * dh, d)}}
    if c["qkv_bias"]:
        attn["q"]["b"] = jnp.zeros((n, hq * dh), jnp.float32)
        attn["k"]["b"] = _normal(next(ks), (n, hkv * dh), 0.1)
        attn["v"]["b"] = jnp.zeros((n, hkv * dh), jnp.float32)
    return {"norm1": {"scale": _uniform(next(ks), (n, d), 0.75, 1.25)},
            "attn": attn,
            "norm2": {"scale": _uniform(next(ks), (n, d), 0.75, 1.25)},
            "ffn": {"gate": {"w": _binary(next(ks), n, d, c["d_ff"])},
                    "up": {"w": _binary(next(ks), n, d, c["d_ff"])},
                    "down": {"w": _binary(next(ks), n, c["d_ff"], d)}}}


def _build(seed_key, frozen: tuple) -> dict:
    c = dict(frozen)
    ke, kn, kl = jax.random.split(seed_key, 3)
    d = c["d_model"]
    return {"embed": {"w": _normal(ke, (c["vocab"], d), d ** -0.5)},
            "final_norm": {"scale": _uniform(kn, (d,), 0.75, 1.25)},
            "segments": [{"l0": _dense_layers(kl, c)}]}


@functools.lru_cache(maxsize=None)
def _jitted(frozen: tuple):
    return jax.jit(functools.partial(_build, frozen=frozen))


def make(cfg: dict, seed: int) -> dict:
    """The weight tree of configuration ``cfg`` for ``seed``."""
    if not cfg.get("tie_embeddings", False):
        raise ValueError("only dense stacks with tied embeddings are built")
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, str, bool))))
    return _jitted(frozen)(root_key(seed))
