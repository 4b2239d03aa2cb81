"""Plain reference forward pass for the ssm family (Mamba-2).

Straightforward ``jax.numpy`` over one whole sequence: no kernels, no
slots, no batching, no chunks, no dual form, and nothing imported from
the program.  The weights come from ``weights.py`` for the same seed.
``logits_at`` runs one sequence and returns the logits at the positions
asked for; the benchmark teacher-forces a prompt and its served tokens
through it.

Semantics followed (hf:state-spaces/mamba2-1.3b; arXiv:2405.21060 and
``mamba_ssm/modules/mamba2.py``), per layer, on the residual stream x:

    u = rmsnorm(x) * norm1
    z, xBC, dt = split(proj(u, in_proj))
    xBC = silu(causal depthwise conv(xBC) + conv_b)        (d_conv taps)
    x', B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t (B_t outer x'_t)      (per head)
    y_t = C_t . h_t + D x'_t
    x = x + proj(rmsnorm(y * silu(z)) * norm, out_proj)

then a final RMSNorm and the tied head.  ``proj`` is the configuration's
``precision``: "bnn" is sign(u) @ sign(w) * alpha with sign(v) = +1 for
v >= 0 and alpha = mean(|w|) over the input axis; "bf16" is u @ w in
float.  The recurrence runs one token at a time (a scan over time inside
a scan over the stacked layers), so the reference holds one state and
one layer's activations at a time.

All float work runs in ``dtype`` (float32 by default) under the highest
matmul precision.  The configuration states float32, so
``dtype=bfloat16`` is the control one precision below.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

import loader

# the norm, the projection and the config key are the dense family's
_dense = loader.module(os.path.join(loader.BENCH, "families", "dense",
                                    "reference.py"))
_rmsnorm, _proj, frozen_config = (_dense._rmsnorm, _dense._proj,
                                  _dense.frozen_config)


def _recurrence(xs, b, c, dt, decay):
    """xs (T, H, P), b and c (T, N), dt and decay (T, H) -> y (T, H, P),
    one token at a time from a zero state."""
    t, heads, p = xs.shape
    n = b.shape[1]

    def step(h, inp):
        x_t, b_t, c_t, dt_t, a_t = inp
        h = h * a_t[:, None, None] \
            + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :]
        return h, jnp.einsum("n,hnp->hp", c_t, h)

    h0 = jnp.zeros((heads, n, p), xs.dtype)
    _, y = jax.lax.scan(step, h0, (xs, b, c, dt, decay), unroll=8)
    return y


def _ssm_layer(c, x, p):
    t = x.shape[0]
    d_inner = c["ssm_expand"] * c["d_model"]
    hd, n, k = c["ssm_headdim"], c["ssm_state"], c["ssm_conv"]
    heads = d_inner // hd
    prec, eps = c["precision"], c["norm_eps"]
    a = p["attn"]
    dtype = x.dtype
    zx = _proj(_rmsnorm(x, p["norm1"]["scale"], eps), a["in_proj"]["w"],
               prec)
    z = zx[:, :d_inner]
    xbc = zx[:, d_inner:2 * d_inner + 2 * n]
    dt = zx[:, 2 * d_inner + 2 * n:]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), dtype), xbc])
    conv = a["conv_b"].astype(dtype)
    for i in range(k):
        conv = conv + padded[i:i + t] * a["conv_w"][i].astype(dtype)
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(t, heads, hd)
    b, cc = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + a["dt_bias"].astype(dtype))
    decay = jnp.exp(dt * -jnp.exp(a["A_log"].astype(dtype)))
    y = _recurrence(xs, b, cc, dt, decay)
    y = y + xs * a["D"].astype(dtype)[None, :, None]
    g = _rmsnorm(y.reshape(t, d_inner) * jax.nn.silu(z),
                 a["norm"]["scale"], eps)
    return x + _proj(g, a["out_proj"]["w"], prec)


def _forward(params, tokens, positions, *, frozen, dtype):
    c = dict(frozen)
    layer = functools.partial(_ssm_layer, c)
    emb = params["embed"]["w"]
    x = emb[tokens].astype(dtype)
    stacked = jax.tree.map(lambda a: a.astype(dtype)
                           if a.dtype == jnp.float32 else a,
                           params["segments"][0]["l0"])
    x, _ = jax.lax.scan(lambda xc, p: (layer(xc, p), None), x, stacked)
    x = _rmsnorm(x[positions], params["final_norm"]["scale"], c["norm_eps"])
    return jnp.matmul(x, emb.astype(dtype).T,
                      preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(frozen: tuple, dtype_name: str):
    return jax.jit(functools.partial(_forward, frozen=frozen,
                                     dtype=jnp.dtype(dtype_name)))


def logits_at(params, cfg: dict, tokens, positions, *,
              dtype=jnp.float32) -> jax.Array:
    """Float32 logits (len(positions), vocab) of one causal sequence, at
    the highest matmul precision.

    ``tokens`` (T,) may be padded past the last real position: every
    layer is causal, so padding never reaches an earlier position.
    ``dtype=bfloat16`` is the control that the comparison has to fail."""
    with jax.default_matmul_precision("highest"):
        return _jitted(frozen_config(cfg), jnp.dtype(dtype).name)(
            params, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32))
