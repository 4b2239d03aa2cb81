"""Seeded weights for the ssm family (Mamba-2), made on the device in one
jitted call, in the layout the serving engine takes: embedding, final
norm, and one segment of layers stacked on a leading axis, each layer a
pre-norm and a Mamba-2 mixer (``in_proj`` to z, x, B, C and dt; the
depthwise conv; ``A_log``, ``D``, ``dt_bias``; the gated norm;
``out_proj``).  ``run.py`` checks the tree against the program's own
parameter shapes before serving; the plain reference
(``reference.py``) reads the same tree and never the program.

The values make every sign the 1-bit datapath takes independent of the
order of float sums and of the matmul precision, while the recurrent
state decides the signs of the SSD's output.  On this datapath one sign
decided by rounding changes every later layer's input, so a check that
compares served tokens with a reference needs this (with random
projections one such sign flipped whole requests of the dense family).
With ``K`` = d_model (a power of two):

* ``A_log`` is -200, so ``A = -exp(A_log)`` is 0 and every decay
  ``exp(dt * A)`` is exactly 1 in the dual form and in the recurrence;
* ``dt_bias`` is 32 and the dt columns of ``in_proj`` are zero, so
  ``dt = softplus(32) = 32`` exactly in float32, in every head;
* B and C are one-hot: their ``in_proj`` columns are zero and the conv
  bias is 32 at state index 0 and 0 elsewhere, so ``silu`` gives B_0 =
  C_0 = 32 and every other entry 0;
* the conv keeps only the current token (taps 0, 0, 0, 1) and adds 64
  to the x channels, whose ``in_proj`` scale is 32/K: x = 64 + (an even
  integer) * 32/K lies in [32, 96], where ``silu(v) == v``, on a grid of
  2**-5 or coarser (12 significant bits at most);
* so the state of a head is ``h_t = 2**10 * sum_{s<=t} x_s`` at index 0
  and zero elsewhere, and ``y_t = 2**15 * (sum_{s<=t} x_s - delta x_t)``
  with ``D = -2**15 * delta``, ``delta`` a whole number in [1, 1024]
  drawn per head: y changes sign when the running sum passes delta
  times the current input, which happens while requests decode, at a
  different position in each head;
* every sum stays below 2**24 of its grid for sequences of up to 2**11
  tokens (the carried state takes up to 23 significant bits), so it is
  exact in float32 however it is ordered; in every product of the SSD's
  contractions one factor is a power of two (dt, B, C, or the dual
  form's C.B), so the product is exact at the "highest" precision the
  configuration states (``ssd_precision``: each float32 operand split
  into three bfloat16 parts on a TPU); at the default precision the
  operands are rounded to bfloat16 and the state's running sums keep
  8 bits;
* z has scale 16/K, so the gate's sign is z's;
* ``out_proj`` is binary with one scale per output column, a multiple of
  2**-14 (``alpha = mean(|w|)`` is exact), and the residual stream adds
  the same exact layer outputs in the same order in both.

What this leaves unchecked on the chip: decay (A), the conv's taps on
earlier tokens and so the carried conv tail, and the B, C and dt
columns of ``in_proj``; the CPU tests cover them with random float
weights (``PERF.md``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

import loader

# the seed's key, binary weights (one scale per output column on a 2**-14
# grid) and uniform draws are the dense family's
_dense = loader.module(os.path.join(loader.BENCH, "families", "dense",
                                    "weights.py"))
root_key, _binary, _uniform = (_dense.root_key, _dense._binary,
                               _dense._uniform)
DELTA_MAX_LOG2 = 10


def _signs(key, shape) -> jax.Array:
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)


def _ssm_layers(key, c) -> dict:
    n, d = c["n_layers"], c["d_model"]
    d_inner = c["ssm_expand"] * d
    heads = d_inner // c["ssm_headdim"]
    ns, k = c["ssm_state"], c["ssm_conv"]
    ks = iter(jax.random.split(key, 8))
    # in_proj column scales: z, x, then B, C and dt at zero
    scale = jnp.concatenate([jnp.full((d_inner,), 16.0 / d),
                             jnp.full((d_inner,), 32.0 / d),
                             jnp.zeros((2 * ns + heads,))])
    in_proj = (_signs(next(ks), (n, d, scale.size)) * scale
               ).astype(jnp.float32)
    conv_w = jnp.zeros((n, k, d_inner + 2 * ns), jnp.float32
                       ).at[:, k - 1].set(1.0)
    one_hot = jnp.zeros((ns,), jnp.float32).at[0].set(32.0)
    conv_b = jnp.broadcast_to(
        jnp.concatenate([jnp.full((d_inner,), 64.0), one_hot, one_hot]),
        (n, d_inner + 2 * ns)).astype(jnp.float32)
    delta = jnp.round(2.0 ** _uniform(next(ks), (n, heads), 0.0,
                                      DELTA_MAX_LOG2))
    return {"norm1": {"scale": _uniform(next(ks), (n, d), 0.75, 1.25)},
            "attn": {
                "in_proj": {"w": in_proj},
                "conv_w": conv_w,
                "conv_b": conv_b,
                "A_log": jnp.full((n, heads), -200.0, jnp.float32),
                "D": (-(2.0 ** 15) * delta).astype(jnp.float32),
                "dt_bias": jnp.full((n, heads), 32.0, jnp.float32),
                "norm": {"scale": _uniform(next(ks), (n, d_inner), 0.75,
                                           1.25)},
                "out_proj": {"w": _binary(next(ks), n, d_inner, d)}}}


def _build(seed_key, frozen: tuple) -> dict:
    c = dict(frozen)
    ke, kn, kl = jax.random.split(seed_key, 3)
    d = c["d_model"]
    return {"embed": {"w": (jax.random.normal(ke, (c["vocab"], d))
                            * d ** -0.5).astype(jnp.float32)},
            "final_norm": {"scale": _uniform(kn, (d,), 0.75, 1.25)},
            "segments": [{"l0": _ssm_layers(kl, c)}]}


@functools.lru_cache(maxsize=None)
def _jitted(frozen: tuple):
    return jax.jit(functools.partial(_build, frozen=frozen))


def make(cfg: dict, seed: int) -> dict:
    """The weight tree of configuration ``cfg`` for ``seed``."""
    d = cfg["d_model"]
    if not cfg.get("tie_embeddings", False) or d & (d - 1):
        raise ValueError("only tied embeddings and a power-of-two d_model "
                         "are built")
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, str, bool))))
    return _jitted(frozen)(root_key(seed))
