"""Plain reference forward passes for the benchmark's configurations.

Straightforward ``jax.numpy`` over whole sequences: no kernels, no cache,
no batching, no chunking, and nothing imported from the program.  The
weights come from ``weights.py`` for the same seed.  ``logits_at`` runs
one sequence and returns the logits at the positions asked for; the
benchmark teacher-forces a prompt and its served tokens through it.

Semantics followed (the configuration's ``precision``):
  * "bnn": every projection is sign(x) @ sign(w) * alpha with
    sign(v) = +1 for v >= 0 and alpha = mean(|w|) over the input axis
    (the paper's Eq. 1 with LQ-Nets scales); embedding, norms, rotary
    embedding, attention and the tied head are float;
  * "bf16": every projection is x @ w in float32 (the float datapath).

Dense family only: pre-norm RMSNorm, GQA with interleaved-pair rotary
embedding and 1/sqrt(head_dim) scaling, SwiGLU, tied head.

Rotary embedding rotates interleaved pairs, as the program does; the
published Qwen code rotates the two halves of each head, which is the
same map after one fixed permutation of each head's q and k columns.

Softmax is normalized after the value product, the same map: a context
whose values sum to zero then gives exactly zero, as a flash-style
kernel does (``weights.py`` makes every score zero).

All float work runs in ``dtype`` (float32 by default) under the highest
matmul precision.  The configuration states float32 at the TPU's default
matmul precision, so ``dtype=bfloat16`` is the control one precision
below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype)


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _proj(x, w, precision: str):
    dt = x.dtype
    if precision == "bf16":
        return jnp.matmul(x, w.astype(dt), preferred_element_type=jnp.float32
                          ).astype(dt)
    alpha = jnp.mean(jnp.abs(w), axis=0).astype(dt)
    dots = jnp.matmul(_sign(x), _sign(w).astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)
    return dots * alpha


def _rope(x, theta):
    """x (T, H, Dh): rotate pairs (x[2i], x[2i+1]) by position * theta^-2i/Dh."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(x.dtype)[:, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v):
    """q (T, H, Dh), k/v (T, Hkv, Dh) -> (T, H*Dh); one head at a time."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def head(i):
        qi = q[:, i] * jnp.asarray(dh ** -0.5, q.dtype)
        ki, vi = k[:, i // group], v[:, i // group]
        s = jnp.where(mask, qi @ ki.T, -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        return (e @ vi) / e.sum(-1, keepdims=True)   # normalized last

    out = jax.lax.map(head, jnp.arange(h))               # (H, T, Dh)
    return out.transpose(1, 0, 2).reshape(t, h * dh)


def _dense_layer(c, x, p):
    t = x.shape[0]
    hq, hkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    prec, eps = c["precision"], c["norm_eps"]
    a = p["attn"]
    h = _rmsnorm(x, p["norm1"]["scale"], eps)

    def qkv(name, heads):
        y = _proj(h, a[name]["w"], prec)
        if "b" in a[name]:
            y = y + a[name]["b"].astype(y.dtype)
        return y.reshape(t, heads, dh)

    q = _rope(qkv("q", hq), c["rope_theta"])
    k = _rope(qkv("k", hkv), c["rope_theta"])
    v = qkv("v", hkv)
    x = x + _proj(_causal_attention(q, k, v), a["o"]["w"], prec)
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(_proj(h, f["gate"]["w"], prec)) * _proj(h, f["up"]["w"],
                                                             prec)
    return x + _proj(g, f["down"]["w"], prec)


def _forward(params, tokens, positions, *, frozen, dtype):
    c = dict(frozen)
    layer = functools.partial(_dense_layer, c)
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


def frozen_config(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


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
