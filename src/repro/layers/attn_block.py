"""GQA attention block: QKV/O projections + RoPE + KV cache around the
chunked flash attention core."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as pa
from repro.layers import attention as attn_mod
from repro.layers import common as C

Array = jax.Array


def init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p, s = {}, {}
    p["q"], s["q"] = C.dense_init(ks[0], cfg.d_model, h * dh,
                                  ("embed", "heads"), bias=cfg.qkv_bias, dtype=dtype)
    p["k"], s["k"] = C.dense_init(ks[1], cfg.d_model, hkv * dh,
                                  ("embed", "kv_heads"), bias=cfg.qkv_bias, dtype=dtype)
    p["v"], s["v"] = C.dense_init(ks[2], cfg.d_model, hkv * dh,
                                  ("embed", "kv_heads"), bias=cfg.qkv_bias, dtype=dtype)
    p["o"], s["o"] = C.dense_init(ks[3], h * dh, cfg.d_model,
                                  ("heads", "embed"), dtype=dtype)
    return p, s


def _qkv(params, cfg, x, positions, precision):
    b, t, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = C.dense(x, params["q"], precision).reshape(b, t, h, dh)
    k = C.dense(x, params["k"], precision).reshape(b, t, hkv, dh)
    v = C.dense(x, params["v"], precision).reshape(b, t, hkv, dh)
    q = C.apply_rope(q, positions, cfg.rope_theta)
    k = C.apply_rope(k, positions, cfg.rope_theta)
    # head-sharding preferred; head_dim split is the automatic fallback
    # when the head count does not divide the 'model' axis (dedup +
    # divisibility in logical_to_pspec) — e.g. llama's 24 q-heads or
    # 8 kv-heads on a 16-way axis.
    q = C.lsc(q, "batch", None, "heads_dim", "head_dim")
    k = C.lsc(k, "batch", None, "kv_heads_dim", "head_dim")
    v = C.lsc(v, "batch", None, "kv_heads_dim", "head_dim")
    return q, k, v


def forward(params, cfg, x: Array, positions: Array, *,
            precision: str = "bf16") -> Array:
    b, t, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions, precision)
    o = attn_mod.attention(q, k, v, causal=True, window=cfg.sliding_window,
                           q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    o = o.reshape(b, t, cfg.n_heads * cfg.head_dim)
    return C.dense(o, params["o"], precision)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.float32):
    # sliding-window archs only need a window-sized ring; we keep it
    # simple: window-bounded length for SWA, full length otherwise.
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, hkv, dh), dtype),
        "v": jnp.zeros((batch, length, hkv, dh), dtype),
    }


# ---------------------------------------------------------------------------
# block-paged KV cache (serving engine; see repro/serving/)
#
# The per-layer cache is a pool of fixed-size token blocks
# k/v: (num_blocks, block_size, Hkv, Dh).  A sequence owns a list of
# physical block ids; its (B, max_blocks) block table maps logical block
# index -> physical id.  Block 0 is a reserved scratch block: writes for
# padded/inactive rows are redirected there and never read back (every
# read is masked by the per-row kv_len).
#
# Sliding-window archs run the same pool as a RING: logical block index
# (pos // bs) wraps modulo the table width, so a sequence only ever owns
# a window-sized block list and the trailing block is recycled to the
# front as the window advances.  Keys then sit out of positional order,
# so reads pass explicit per-slot absolute positions (ring_key_positions)
# into the attention mask instead of the arange default.


def init_paged_state(cfg, num_blocks: int, block_size: int,
                     dtype=jnp.float32):
    """Per-layer paged KV pool (the GQA mixer-state layout)."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_blocks, block_size, hkv, dh), dtype),
        "v": jnp.zeros((num_blocks, block_size, hkv, dh), dtype),
    }


def gather_blocks(pool: Array, block_table: Array) -> Array:
    """(num_blocks, bs, *rest) x (B, max_blocks) -> (B, max_blocks*bs,
    *rest) — a sequence's cached state, logically contiguous.  Slots past
    the owned blocks point at scratch block 0; callers mask by kv_len /
    key positions."""
    nb, bs, *rest = pool.shape
    b, mb = block_table.shape
    return pool[block_table].reshape(b, mb * bs, *rest)


def scatter_blocks(pool: Array, block_table: Array, positions: Array,
                   values: Array, valid: Array, *,
                   ring: bool = False) -> Array:
    """Write per-row token values into the paged pool.

    positions (B, C) absolute token positions; values (B, C, *rest);
    valid (B, C) bool — invalid writes are redirected to scratch block 0.
    ring=True wraps the logical block index modulo the table width
    (sliding-window ring buffer) instead of clipping.
    """
    nb, bs, *rest = pool.shape
    mb = block_table.shape[1]
    with jax.named_scope("kv_update"):
        bidx = positions // bs                                  # (B, C)
        bidx = bidx % mb if ring else jnp.clip(bidx, 0, mb - 1)
        phys = jnp.take_along_axis(block_table, bidx, axis=1)   # (B, C)
        phys = jnp.where(valid, phys, 0)
        offs = jnp.where(valid, positions % bs, 0)
        return pool.at[phys.reshape(-1), offs.reshape(-1)].set(
            values.reshape(-1, *rest).astype(pool.dtype))


def ring_key_positions(newest: Array, mb: int, bs: int) -> Array:
    """(B, mb*bs) absolute position of every ring slot.

    newest (B,) is the highest absolute position written; slot s holds
    the most recent position congruent to s mod the ring capacity:
    ``newest - ((newest - s) mod R)``.  Slots never written resolve to a
    negative position, which the attention mask drops.
    """
    r = mb * bs
    s = jnp.arange(r, dtype=jnp.int32)
    return newest[:, None] - ((newest[:, None] - s[None, :]) % r)


def _paged_attend(cfg, q, cache, block_table, lengths, kv_len, newest,
                  ring, causal, impl):
    """GQA paged attention with impl dispatch: the fused Pallas kernel
    walks the block table in-kernel; the XLA path (gather_blocks + the
    chunked flash core) is the differential oracle."""
    mb = block_table.shape[1]
    bs = cache["k"].shape[1]
    if pa.resolve_impl(impl) == "pallas":
        return pa.paged_attention(
            q, cache["k"], cache["v"], block_table, kv_len=kv_len,
            q_offset=lengths, layout="gqa", causal=causal,
            window=cfg.sliding_window, ring=ring,
            newest=newest if ring else None)
    keys = gather_blocks(cache["k"], block_table)
    vals = gather_blocks(cache["v"], block_table)
    kpos = ring_key_positions(newest, mb, bs) if ring else None
    return attn_mod.attention(q, keys.astype(q.dtype), vals.astype(q.dtype),
                              causal=causal, kv_len=kv_len,
                              window=cfg.sliding_window, q_offset=lengths,
                              k_positions=kpos,
                              q_chunk=min(cfg.q_chunk, q.shape[1]),
                              kv_chunk=cfg.kv_chunk)


def paged_decode_step(params, cfg, x: Array, cache, block_table: Array,
                      lengths: Array, *, precision: str = "bf16",
                      active: Array | None = None,
                      ring: bool = False,
                      attn_impl: str = "auto") -> tuple[Array, dict]:
    """One-token decode against the paged pool with PER-ROW lengths.

    x (B, 1, d); block_table (B, max_blocks); lengths (B,) current
    per-sequence cache fill; active (B,) bool masks padded batch slots;
    ring=True treats the table as a sliding-window ring buffer;
    attn_impl selects the fused Pallas kernel or the XLA oracle
    (kernels/paged_attention.resolve_impl).
    """
    b = x.shape[0]
    positions = lengths[:, None]                                 # (B, 1)
    q, k, v = _qkv(params, cfg, x, positions, precision)
    valid = (jnp.ones((b, 1), bool) if active is None
             else active[:, None])
    cache = {
        "k": scatter_blocks(cache["k"], block_table, positions, k, valid,
                            ring=ring),
        "v": scatter_blocks(cache["v"], block_table, positions, v, valid,
                            ring=ring),
    }
    o = _paged_attend(cfg, q, cache, block_table, lengths, lengths + 1,
                      lengths, ring, causal=False, impl=attn_impl)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return C.dense(o, params["o"], precision), cache


def prefill_chunk(params, cfg, x: Array, cache, block_table: Array,
                  lengths: Array, n_valid: Array, *,
                  precision: str = "bf16",
                  ring: bool = False,
                  attn_impl: str = "auto") -> tuple[Array, dict]:
    """Chunked prefill: C tokens per row appended at per-row offsets.

    x (B, C, d); lengths (B,) tokens already cached; n_valid (B,) how
    many of the C chunk positions are real (the rest are padding).
    Causal within the chunk, full (or window-masked) attention to the
    cached prefix.

    This is also the engine's multi-token SPECULATIVE VERIFY entry
    point ([last_token, draft...] rows): rejecting a draft suffix needs
    no block-level rollback — the engine simply rewinds the committed
    length, stale writes past it are masked by per-row kv_len (and, in
    ring mode, resolve to out-of-window ages via ring_key_positions as
    long as the verify chunk is no wider than the prefill chunk the
    ring was sized for).
    """
    b, ch, _ = x.shape
    positions = lengths[:, None] + jnp.arange(ch, dtype=jnp.int32)[None, :]
    q, k, v = _qkv(params, cfg, x, positions, precision)
    valid = jnp.arange(ch, dtype=jnp.int32)[None, :] < n_valid[:, None]
    cache = {
        "k": scatter_blocks(cache["k"], block_table, positions, k, valid,
                            ring=ring),
        "v": scatter_blocks(cache["v"], block_table, positions, v, valid,
                            ring=ring),
    }
    o = _paged_attend(cfg, q, cache, block_table, lengths,
                      lengths + n_valid, lengths + n_valid - 1,
                      ring, causal=True, impl=attn_impl)
    o = o.reshape(b, ch, cfg.n_heads * cfg.head_dim)
    return C.dense(o, params["o"], precision), cache


def decode_step(params, cfg, x: Array, cache, length: Array, *,
                precision: str = "bf16") -> tuple[Array, dict]:
    """One-token decode; cache k/v updated in place at ``length``
    (ring-buffer position for sliding-window archs)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), length, jnp.int32)
    q, k, v = _qkv(params, cfg, x, positions, precision)
    size = cache["k"].shape[1]
    slot = length % size if cfg.sliding_window else length
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, 1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, 1),
    }
    # For SWA the ring buffer holds the last `window` tokens; attending
    # over all valid slots with no causal mask within them is equivalent.
    kv_len = jnp.minimum(length + 1, size)
    o = attn_mod.attention(q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype),
                           causal=False, kv_len=kv_len,
                           q_chunk=1, kv_chunk=cfg.kv_chunk)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return C.dense(o, params["o"], precision), cache
