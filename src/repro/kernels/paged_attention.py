"""Pallas TPU kernel: fused paged-attention decode, templated across
mixer layouts.

The serving decode hot path used to materialize every request's full
block table with an XLA gather (``pool[block_table]`` — a copy of the
whole addressable window per step) before a dense flash attention in
plain jnp.  This kernel walks the block table INSIDE the kernel instead:
the grid is (batch_row, logical_block) and the K/V pool BlockSpec's
index map reads the scalar-prefetched block table to DMA exactly the
one physical block each step touches — gather + QK + online-softmax + V
accumulation in one pass, nothing intermediate in HBM.  This is the TPU
analogue of the paper's DWDM-parallel OXG arrays streaming operands
through the photo-charge accumulator: one pass over packed operands, no
materialization (cf. XNOR Neural Engine, arXiv:1807.03010).

ONE template, three layout variants (specialized by static params, not
hand-written triplicates):

  * layout="gqa"           pools k/v (NB, BS, Hkv, Dh); grouped heads.
  * layout="mla"           pools c_kv (NB, BS, R) / k_rope (NB, BS, Dr);
                           per-head K (nope ++ broadcast rope) and V are
                           decompressed in-kernel from the gathered
                           latents via the k_up/v_up weights (resident
                           in VMEM across the whole walk).
  * ring=True              slot = pos mod ring capacity: per-slot
                           absolute positions are recomputed in-kernel
                           (``newest - ((newest - slot) mod R)``) and
                           negative (never-written) slots are masked.
                           Composes with either pool layout.

Masking semantics are exactly ``layers/attention.py``'s: per-row
``kv_len`` and ``q_offset``, optional causal (multi-token prefill /
speculative-verify chunks) and sliding-window masks, NEG_INF fill, and
fully-masked rows produce zeros.  The XLA gather+attention path remains
the differential oracle (tests/test_paged_kernels.py).

On CPU/GPU the kernel runs under ``interpret=True`` — numerically
exact but slow (the grid is unrolled at trace time); it exists there
for differential testing, not speed.  ``resolve_impl("auto")`` therefore
picks "pallas" only on TPU backends.  See docs/kernels.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def resolve_impl(impl: str = "auto") -> str:
    """'auto' -> 'pallas' on TPU (compiled), 'xla' elsewhere (the
    gather-based oracle).  'pallas' is honored anywhere — off-TPU it
    runs in interpret mode (correctness only)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return impl


def _dot_nt(a, b):
    """(m, d) x (n, d) -> (m, n) in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _paged_attn_kernel(
        # scalar-prefetch refs (available to index maps AND the body)
        tab_ref, kvlen_ref, qoff_ref, newest_ref,
        # tensor refs (block-sliced per grid step)
        q_ref, pool_a_ref, pool_b_ref, *rest,
        layout: str, ring: bool, causal: bool, window: int | None,
        bs: int, mb: int, c: int, nope_dim: int, v_dim: int):
    """One (batch_row, logical_block) grid step of the template.

    q arrives head-major, (Hkv, G*C, Dq): the G query heads that share
    one KV head are stacked as row blocks of C, so every score and
    value product is a plain 2D matmul per KV head (no rank-4 einsum,
    no sublane reshape).  Scratch (m, l, acc) carries the online-softmax
    state across the row's block walk — the same revisit-in-VMEM
    pattern as the XNOR kernel's photo-charge accumulator.
    """
    if layout == "mla":
        k_up_ref, v_up_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hkv, gc, dq = q_ref.shape[1:]
    scale = dq ** -0.5

    # ---- per-slot absolute key positions + mask (all 2D iota: TPU) ----
    slots = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    if ring:
        newest = newest_ref[b]
        kpos = newest - ((newest - slots) % (mb * bs))
    else:
        kpos = slots                            # (1, bs)
    mask = (kpos >= 0) & (kpos < kvlen_ref[b])
    if causal or (window is not None and window > 0):
        # row r of a head block is query position q_offset + r mod C
        row = jax.lax.broadcasted_iota(jnp.int32, (gc, 1), 0)
        qpos = qoff_ref[b] + jax.lax.rem(row, c)
        if causal:
            mask = mask & (qpos >= kpos)
        if window is not None and window > 0:
            mask = mask & (qpos - kpos < window)
    mask = jnp.broadcast_to(mask, (gc, bs))     # (G*C, BS)

    if layout == "mla":
        lat = pool_a_ref[0].astype(jnp.float32)         # (BS, R)
        rope = pool_b_ref[0].astype(jnp.float32)        # (BS, Dr)

    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32) * scale     # (G*C, Dq)
        # ---- layout-specialized K/V of this head for this block ----
        if layout == "mla":
            # in-kernel latent decompression (the MLA memory win: HBM
            # only ever sees the compressed latents)
            k = jnp.dot(lat, k_up_ref[:, h * nope_dim:(h + 1) * nope_dim],
                        preferred_element_type=jnp.float32)
            v = jnp.dot(lat, v_up_ref[:, h * v_dim:(h + 1) * v_dim],
                        preferred_element_type=jnp.float32)
            scores = (_dot_nt(q[:, :nope_dim], k)
                      + _dot_nt(q[:, nope_dim:], rope))
        else:
            k = pool_a_ref[0, :, h, :].astype(jnp.float32)  # (BS, Dh)
            v = pool_b_ref[0, :, h, :].astype(jnp.float32)  # (BS, Dv)
            scores = _dot_nt(q, k)
        scores = jnp.where(mask, scores, NEG_INF)           # (G*C, BS)

        # ---- online-softmax merge with the running (m, l, acc) ----
        m_prev = m_ref[h]                                   # (G*C, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # explicit mask (not exp of NEG_INF-NEG_INF): a fully-masked
        # block with m_new still at NEG_INF would otherwise add exp(0)=1
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(i == mb - 1)
    def _finalize():
        # fully-masked rows: l stayed 0 -> output 0 (flash semantics)
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q: Array, pool_a: Array, pool_b: Array,
                    block_table: Array, *,
                    kv_len: Array, q_offset: Array,
                    layout: str = "gqa",
                    causal: bool = False,
                    window: int | None = None,
                    ring: bool = False,
                    newest: Array | None = None,
                    k_up: Array | None = None,
                    v_up: Array | None = None,
                    nope_dim: int = 0,
                    interpret: bool | None = None) -> Array:
    """Fused block-table walk + flash attention over a paged pool.

    q (B, C, H, Dq); block_table (B, MB) int32 physical block ids;
    kv_len/q_offset (B,) per-row valid length / absolute q position.

    layout="gqa": pool_a/pool_b = k/v pools (NB, BS, Hkv, Dh).
    layout="mla": pool_a/pool_b = c_kv (NB, BS, R) / k_rope (NB, BS, Dr)
      pools; k_up (R, H*nope_dim) and v_up (R, H*Dv) decompress the
      gathered latents in-kernel; q packs [nope ++ rope] on its last
      axis (``nope_dim`` splits it).
    ring=True: the table is a sliding-window ring buffer; ``newest``
      (B,) is the highest absolute position written per row and slot
      positions are recovered modulo the ring capacity (negative =
      never written = masked).

    Returns (B, C, H, Dv) in q's dtype.  Differentially tested against
    gather_blocks + layers.attention (the XLA oracle).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, c, h, dq = q.shape
    nb, bs = pool_a.shape[:2]
    mb = block_table.shape[1]
    if layout == "mla":
        assert k_up is not None and v_up is not None and nope_dim > 0
        hkv = h
        v_dim = v_up.shape[1] // h
    elif layout == "gqa":
        hkv = pool_a.shape[2]
        v_dim = pool_b.shape[3]
        nope_dim = 0
    else:
        raise ValueError(f"unknown paged-attention layout {layout!r}")
    if newest is None:
        assert not ring, "ring layout needs per-row `newest` positions"
        newest = jnp.zeros((b,), jnp.int32)

    g = h // hkv
    kernel = functools.partial(
        _paged_attn_kernel, layout=layout, ring=ring, causal=causal,
        window=window, bs=bs, mb=mb, c=c, nope_dim=nope_dim, v_dim=v_dim)

    # head-major query rows: (B, C, Hkv*G, Dq) -> (B, Hkv, G*C, Dq), so
    # the kernel sees one 2D (G*C, Dq) operand per KV head
    qh = q.reshape(b, c, hkv, g, dq).transpose(0, 2, 3, 1, 4)
    qh = qh.reshape(b, hkv, g * c, dq)

    # scalar-prefetched operands feed the pool index maps: the kernel
    # sees exactly one physical block per grid step, chosen by the
    # row's block table — the in-kernel gather.
    in_specs = [
        pl.BlockSpec((1, hkv, g * c, dq), lambda bi, i, *s: (bi, 0, 0, 0)),
        pl.BlockSpec(
            (1, bs) + pool_a.shape[2:],
            lambda bi, i, tab, *s: (tab[bi, i],) + (0,) * (pool_a.ndim - 1)),
        pl.BlockSpec(
            (1, bs) + pool_b.shape[2:],
            lambda bi, i, tab, *s: (tab[bi, i],) + (0,) * (pool_b.ndim - 1)),
    ]
    args = [qh, pool_a, pool_b]
    if layout == "mla":
        in_specs += [
            pl.BlockSpec(k_up.shape, lambda bi, i, *s: (0, 0)),
            pl.BlockSpec(v_up.shape, lambda bi, i, *s: (0, 0)),
        ]
        args += [k_up.astype(jnp.float32), v_up.astype(jnp.float32)]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, g * c, v_dim),
                                   lambda bi, i, *s: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, g * c, 1), jnp.float32),      # running max
                pltpu.VMEM((hkv, g * c, 1), jnp.float32),      # running sum
                pltpu.VMEM((hkv, g * c, v_dim), jnp.float32),  # weighted acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * c, v_dim), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32),
      kv_len.astype(jnp.int32),
      jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,)),
      jnp.broadcast_to(jnp.asarray(newest, jnp.int32), (b,)),
      *args)
    out = out.reshape(b, hkv, g, c, v_dim).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, h, v_dim)
