"""Mamba-2 block (state-space duality / SSD, arXiv:2405.21060).

Chunked SSD algorithm: the sequence is split into chunks of length L;
within a chunk the recurrence is computed as a masked quadratic form
(duality with attention), chunk boundary states are combined with an
associative scan, and the inter-chunk contribution is added back.
Single-token decode is the O(1) recurrence on the cached state — this is
what makes the ``long_500k`` cell tractable for SSM/hybrid archs.

Shapes per block: x (B, T, d_model); d_inner = expand * d_model;
heads H = d_inner / headdim P; state N = d_state; groups G (=1 here).

As ``mamba_ssm/modules/mamba2.py`` computes it: the gate multiplies
before the norm (``RMSNormGated``, ``norm_before_gate=False``):
``out_proj(rmsnorm(y * silu(z)))``, at the configuration's ``norm_eps``.

Every contraction of the SSD runs at the configuration's
``ssd_precision`` ("highest" unless a configuration says otherwise): on
a TPU a float32 einsum at the default precision rounds its operands to
bfloat16, and the carried state is a sum over the whole sequence, so it
would lose its low bits every chunk.

Named scopes (the benchmark reads device time by them): ``ssd`` holds
the conv, the scan or dual form and the gated norm; ``ssm_state`` the
gathers and scatters of the serving slot pools and the snapshot copies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers import common as C

Array = jax.Array

def _einsum(cfg, spec: str, *ops) -> Array:
    return jnp.einsum(spec, *ops, precision=cfg.ssd_precision)


def _gated_norm(y: Array, z: Array, params, eps: float) -> Array:
    """``RMSNormGated`` with the gate before the norm."""
    return C.rmsnorm(y * jax.nn.silu(z).astype(y.dtype), params, eps)


def init(key, cfg, dtype=jnp.float32):
    """cfg fields: d_model, ssm_expand, ssm_headdim, ssm_state, ssm_conv."""
    d_inner = cfg.ssm_expand * cfg.d_model
    h = d_inner // cfg.ssm_headdim
    g = 1
    conv_ch = d_inner + 2 * g * cfg.ssm_state
    d_in_proj = 2 * d_inner + 2 * g * cfg.ssm_state + h
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["in_proj"], s["in_proj"] = C.dense_init(
        ks[0], cfg.d_model, d_in_proj, ("embed", "ssm_inner"), dtype=dtype)
    p["conv_w"] = jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch), dtype) * 0.2
    s["conv_w"] = (None, "ssm_inner")
    p["conv_b"] = jnp.zeros((conv_ch,), dtype)
    s["conv_b"] = ("ssm_inner",)
    p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, h).astype(dtype))
    s["A_log"] = (None,)
    p["D"] = jnp.ones((h,), dtype)
    s["D"] = (None,)
    p["dt_bias"] = jnp.zeros((h,), dtype)
    s["dt_bias"] = (None,)
    p["norm"], s["norm"] = C.norm_init(d_inner, "rmsnorm", dtype)
    s["norm"] = {"scale": ("ssm_inner",)}
    p["out_proj"], s["out_proj"] = C.dense_init(
        ks[3], d_inner, cfg.d_model, ("ssm_inner", "embed"), dtype=dtype)
    return p, s


def _split_proj(cfg, zxbcdt):
    d_inner = cfg.ssm_expand * cfg.d_model
    h = d_inner // cfg.ssm_headdim
    g, n = 1, cfg.ssm_state
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + d_inner + 2 * g * n], axis=-1)
    return z, xbc, dt, d_inner, h, g, n


def _causal_conv(xbc: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv1d: xbc (B, T, C), w (k, C)."""
    k = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + xbc.shape[1], :] * w[i][None, None, :] for i in range(k))
    return jax.nn.silu(out + b[None, None, :])


def forward(params, cfg, x: Array, *, chunk: int = 256,
            precision: str = "bf16") -> Array:
    """Full-sequence SSD (train/prefill)."""
    bsz, t, _ = x.shape
    zxbcdt = C.dense(x, params["in_proj"], precision)
    with jax.named_scope("ssd"):
        z, xbc, dt, d_inner, h, g, n = _split_proj(cfg, zxbcdt)
        p = cfg.ssm_headdim
        xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        xs, b_, c_ = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
        xs = xs.reshape(bsz, t, h, p)
        b_ = b_.reshape(bsz, t, g, n)
        c_ = c_.reshape(bsz, t, g, n)

        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None])
        a = -jnp.exp(params["A_log"].astype(jnp.float32))        # (H,)
        log_decay = dt * a[None, None, :]                         # (B,T,H) = log a_t

        # pad T to chunk multiple
        lpad = (-t) % chunk
        if lpad:
            xs = jnp.pad(xs, ((0, 0), (0, lpad), (0, 0), (0, 0)))
            b_ = jnp.pad(b_, ((0, 0), (0, lpad), (0, 0), (0, 0)))
            c_ = jnp.pad(c_, ((0, 0), (0, lpad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, lpad), (0, 0)))
            log_decay = jnp.pad(log_decay, ((0, 0), (0, lpad), (0, 0)))
        tp = t + lpad
        nc = tp // chunk

        def ch(v, *trail):
            return v.reshape(bsz, nc, chunk, *trail)

        xs_c = ch(xs, h, p)
        b_c = ch(b_, g, n)
        c_c = ch(c_, g, n)
        dt_c = ch(dt, h)
        ld_c = ch(log_decay, h)

        cum = jnp.cumsum(ld_c, axis=2)                            # (B,nc,L,H)
        total = cum[:, :, -1]                                     # (B,nc,H)

        # ---- intra-chunk (quadratic / attention-dual form) ----
        # M[t,s] = (C_t . B_s) * exp(cum_t - cum_s) for s <= t
        cb = _einsum(cfg, "bclgn,bcsgn->bclsg", c_c, b_c)              # (B,nc,L,L,G)
        cb = cb[..., 0]                                           # G=1 -> (B,nc,L,L)
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]       # (B,nc,L,L,H)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        # mask BEFORE exp: for s > t the exponent is positive and can
        # overflow; exp(inf)*0 would poison the backward pass with NaNs.
        seg = jnp.where(causal[None, None, :, :, None], seg, -1e30)
        decay = jnp.exp(seg)
        m = cb[..., None] * decay                                 # (B,nc,L,L,H)
        xdt = xs_c * dt_c[..., None]                              # (B,nc,L,H,P)
        y_intra = _einsum(cfg, "bclsh,bcshp->bclhp", m, xdt)

        # ---- chunk boundary states ----
        # S_c = sum_s exp(total - cum_s) * dt_s * B_s (x) x_s   -> (B,nc,H,N,P)
        w_s = jnp.exp(total[:, :, None, :] - cum) * dt_c          # (B,nc,L,H)
        states = _einsum(cfg, "bclh,bclgn,bclhp->bchnp",
                         w_s, b_c, xs_c)                          # g=1 folded

        # ---- inter-chunk associative scan over (decay, state) ----
        decay_c = jnp.exp(total)                                  # (B,nc,H)

        def combine(left, right):
            dl, sl = left
            dr, sr = right
            return dl * dr, sr + sl * dr[..., None, None]

        dprod, sprefix = jax.lax.associative_scan(combine, (decay_c, states), axis=1)
        # state entering chunk c = prefix of chunks < c
        h_prev = jnp.concatenate(
            [jnp.zeros_like(sprefix[:, :1]), sprefix[:, :-1]], axis=1)

        # ---- inter-chunk contribution ----
        y_inter = _einsum(cfg, "bclgn,bchnp->bclhp", c_c, h_prev) * \
            jnp.exp(cum)[..., None]

        y = (y_intra + y_inter).reshape(bsz, tp, h, p)[:, :t]
        y = y + xs[:, :t] * params["D"].astype(jnp.float32)[None, None, :, None]
        y = y.reshape(bsz, t, d_inner).astype(x.dtype)
        y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    return C.dense(y, params["out_proj"], precision)


def init_cache(cfg, batch: int, dtype=jnp.float32):
    d_inner = cfg.ssm_expand * cfg.d_model
    h = d_inner // cfg.ssm_headdim
    g = 1
    conv_ch = d_inner + 2 * g * cfg.ssm_state
    return {
        "h": jnp.zeros((batch, h, cfg.ssm_state, cfg.ssm_headdim), dtype),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
    }


def decode_step(params, cfg, x: Array, cache, *,
                precision: str = "bf16") -> tuple[Array, dict]:
    """O(1) single-token step. x: (B, 1, d_model)."""
    bsz = x.shape[0]
    zxbcdt = C.dense(x, params["in_proj"], precision)
    with jax.named_scope("ssd"):
        z, xbc, dt, d_inner, h, g, n = _split_proj(cfg, zxbcdt)
        p = cfg.ssm_headdim

        # conv with cached history
        hist = jnp.concatenate([cache["conv"], xbc], axis=1)       # (B, k, C)
        w = params["conv_w"]
        out = jnp.sum(hist * w[None], axis=1, keepdims=True)
        xbc1 = jax.nn.silu(out + params["conv_b"][None, None])
        new_conv = hist[:, 1:]

        xs, b_, c_ = jnp.split(xbc1, [d_inner, d_inner + g * n], axis=-1)
        xs = xs.reshape(bsz, h, p)
        b_ = b_.reshape(bsz, n)
        c_ = c_.reshape(bsz, n)
        dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"][None])
        a = -jnp.exp(params["A_log"].astype(jnp.float32))
        decay = jnp.exp(dt * a[None])                              # (B,H)

        hstate = cache["h"].astype(jnp.float32)
        upd = _einsum(cfg, "bh,bn,bhp->bhnp", dt, b_.astype(jnp.float32),
                      xs.astype(jnp.float32))
        hstate = hstate * decay[:, :, None, None] + upd
        y = _einsum(cfg, "bn,bhnp->bhp", c_.astype(jnp.float32), hstate)
        y = y + xs.astype(jnp.float32) * params["D"].astype(jnp.float32)[None, :, None]
        y = y.reshape(bsz, 1, d_inner).astype(x.dtype)
        y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    out = C.dense(y, params["out_proj"], precision)
    return out, {"h": hstate.astype(cache["h"].dtype), "conv": new_conv}


# ---------------------------------------------------------------------------
# per-slot recurrent state (serving engine; see repro/serving/)
#
# The SSM mixer-state layout: a request's entire cache is ONE fixed-size
# slot holding (SSD hidden state, conv tail) — O(1) in sequence length,
# so there is no block table and nothing to page.  Slot 0 is reserved as
# scratch (writes for padded batch rows are redirected there and never
# read).  Swap/preempt snapshots the whole slot; prefill advances the
# state one chunk at a time through the quadratic SSD dual form with the
# carried initial state folded in.


def init_paged_state(cfg, num_slots: int, dtype=jnp.float32):
    """Per-layer slot pool (the recurrent mixer-state layout)."""
    d_inner = cfg.ssm_expand * cfg.d_model
    h = d_inner // cfg.ssm_headdim
    conv_ch = d_inner + 2 * cfg.ssm_state
    return {
        "h": jnp.zeros((num_slots, h, cfg.ssm_state, cfg.ssm_headdim),
                       dtype),
        "conv": jnp.zeros((num_slots, cfg.ssm_conv - 1, conv_ch), dtype),
    }


def copy_slot(dst_pool: dict, dst: Array, src_pool: dict,
              src: Array) -> dict:
    """Copy one slot's whole recurrent state (h, conv tail) between two
    slot pools of any row counts — the prefix-snapshot store/restore
    primitive (serving/mixer_state.py jits this with the destination
    pool donated: store writes a live slot into the snapshot pool,
    restore writes a snapshot row back into the live pool)."""
    with jax.named_scope("ssm_state"):
        return {k: v.at[dst].set(src_pool[k][src].astype(v.dtype))
                for k, v in dst_pool.items()}


def snapshot_slots(cache, slots: Array) -> dict:
    """Device-side copy of each row's recurrent slot — taken BEFORE a
    multi-token verify so a partially-rejected speculative step can be
    rolled back (restore + re-advance by the accepted prefix only)."""
    with jax.named_scope("ssm_state"):
        return {k: v[slots] for k, v in cache.items()}


def restore_slots(cache, slots: Array, snap: dict) -> dict:
    """Write per-row snapshots back into the slot pool (the speculative
    rollback).  Rows sharing the scratch slot all rewrite the same
    scratch snapshot, so duplicate indices are harmless."""
    with jax.named_scope("ssm_state"):
        return {k: v.at[slots].set(snap[k].astype(v.dtype))
                for k, v in cache.items()}


def paged_decode_step(params, cfg, x: Array, cache, slots: Array, *,
                      precision: str = "bf16",
                      active: Array | None = None) -> tuple[Array, dict]:
    """O(1) decode against the slot pool.  x (B, 1, d); slots (B,) slot
    ids (padded rows masked to scratch slot 0 by ``active``)."""
    with jax.named_scope("ssm_state"):
        state = {"h": cache["h"][slots], "conv": cache["conv"][slots]}
    y, new = decode_step(params, cfg, x, state, precision=precision)
    dst = slots if active is None else jnp.where(active, slots, 0)
    with jax.named_scope("ssm_state"):
        cache = {k: v.at[dst].set(new[k].astype(v.dtype))
                 for k, v in cache.items()}
    return y, cache


def prefill_chunk(params, cfg, x: Array, cache, slots: Array,
                  n_valid: Array, *,
                  precision: str = "bf16") -> tuple[Array, dict]:
    """Advance each row's recurrent state by one chunk of C tokens.

    x (B, C, d); n_valid (B,) real tokens per row (rest is padding —
    masked by zeroing dt, so padded steps neither decay nor update the
    state).  Single-chunk SSD dual form with the slot's carried state
    h0 folded in: y_t += C_t · h0 · exp(cum_t) and the written state is
    h0 · exp(total) + (chunk boundary state).  Chunks are engine-sized
    (<= prefill_chunk), so the quadratic intra-chunk term stays tiny.

    Doubles as the speculative VERIFY/REPAIR entry point: verify runs
    it over [last_token, draft...] (full n_valid, logits at every
    position); on partial acceptance the repair pass restores the
    pre-verify slot snapshot (snapshot_slots/restore_slots) and re-runs
    this with n_valid = committed prefix, which advances the state by
    exactly the accepted tokens — the dt masking makes rejected
    positions true no-ops.
    """
    bsz, c_len, _ = x.shape
    with jax.named_scope("ssm_state"):
        h0 = cache["h"][slots].astype(jnp.float32)             # (B,H,N,P)
        hist = cache["conv"][slots]                            # (B, k-1, ch)
    zxbcdt = C.dense(x, params["in_proj"], precision)
    with jax.named_scope("ssd"):
        z, xbc, dt, d_inner, h, g, n = _split_proj(cfg, zxbcdt)
        p = cfg.ssm_headdim
        k = params["conv_w"].shape[0]

        # depthwise causal conv with the slot's carried (k-1)-token tail
        full = jnp.concatenate([hist.astype(xbc.dtype), xbc], axis=1)
        out = sum(full[:, i:i + c_len] * params["conv_w"][i][None, None]
                  for i in range(k))
        xbc1 = jax.nn.silu(out + params["conv_b"][None, None])
        # new tail = last k-1 inputs up to the row's valid length
        idx = n_valid[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
        new_conv = jnp.take_along_axis(full, idx[:, :, None], axis=1)

        xs, b_, c_ = jnp.split(xbc1, [d_inner, d_inner + g * n], axis=-1)
        xs = xs.reshape(bsz, c_len, h, p).astype(jnp.float32)
        b_ = b_.reshape(bsz, c_len, n).astype(jnp.float32)         # g = 1
        c_ = c_.reshape(bsz, c_len, n).astype(jnp.float32)

        valid = jnp.arange(c_len, dtype=jnp.int32)[None, :] < n_valid[:, None]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None])
        dt = dt * valid[..., None]                                 # (B,C,H)
        a = -jnp.exp(params["A_log"].astype(jnp.float32))
        log_decay = dt * a[None, None, :]
        cum = jnp.cumsum(log_decay, axis=1)                        # (B,C,H)
        total = cum[:, -1]                                         # (B,H)

        # intra-chunk quadratic (attention-dual) form
        cb = _einsum(cfg, "bln,bsn->bls", c_, b_)
        seg = cum[:, :, None, :] - cum[:, None, :, :]              # (B,C,C,H)
        causal = jnp.tril(jnp.ones((c_len, c_len), bool))
        seg = jnp.where(causal[None, :, :, None], seg, -1e30)
        m = cb[..., None] * jnp.exp(seg)
        xdt = xs * dt[..., None]                                   # (B,C,H,P)
        y = _einsum(cfg, "blsh,bshp->blhp", m, xdt)

        # carried-state contribution + new boundary state
        y = y + _einsum(cfg, "bln,bhnp->blhp", c_, h0) * jnp.exp(cum)[..., None]
        w_s = jnp.exp(total[:, None, :] - cum) * dt                # (B,C,H)
        states = _einsum(cfg, "blh,bln,blhp->bhnp", w_s, b_, xs)
        hstate = h0 * jnp.exp(total)[:, :, None, None] + states

        y = y + xs * params["D"].astype(jnp.float32)[None, None, :, None]
        y = y.reshape(bsz, c_len, d_inner).astype(x.dtype)
        y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    out = C.dense(y, params["out_proj"], precision)

    dst = jnp.where(n_valid > 0, slots, 0)
    with jax.named_scope("ssm_state"):
        cache = {
            "h": cache["h"].at[dst].set(hstate.astype(cache["h"].dtype)),
            "conv": cache["conv"].at[dst].set(
                new_conv.astype(cache["conv"].dtype)),
        }
    return out, cache


def forward_reference(params, cfg, x: Array) -> Array:
    """O(T) sequential reference (tests): plain recurrence."""
    bsz, t, _ = x.shape
    zxbcdt = C.dense(x, params["in_proj"], "bf16")
    z, xbc, dt, d_inner, h, g, n = _split_proj(cfg, zxbcdt)
    p = cfg.ssm_headdim
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    xs, b_, c_ = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(bsz, t, h, p)
    b_ = b_.reshape(bsz, t, n)
    c_ = c_.reshape(bsz, t, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None])
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a[None, None])                        # (B,T,H)

    def step(hs, inp):
        xt, bt, ct, dct, dtt = inp
        upd = _einsum(cfg, "bh,bn,bhp->bhnp", dtt, bt, xt)
        hs = hs * dct[:, :, None, None] + upd
        yt = _einsum(cfg, "bn,bhnp->bhp", ct, hs)
        return hs, yt

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (
        xs.transpose(1, 0, 2, 3).astype(jnp.float32),
        b_.transpose(1, 0, 2).astype(jnp.float32),
        c_.transpose(1, 0, 2).astype(jnp.float32),
        decay.transpose(1, 0, 2),
        dt.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3)
    y = y + xs.astype(jnp.float32) * params["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(bsz, t, d_inner).astype(x.dtype)
    y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    return C.dense(y, params["out_proj"], "bf16")
