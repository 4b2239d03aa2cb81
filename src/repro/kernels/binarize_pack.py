"""Pallas TPU kernel: fused binarize + bitpack (the 'OXG operand drive').

Binarizes a float activation tile against a threshold and packs 32
elements per uint32 word in one VMEM pass — the producer side of the
XNOR GEMM.  Fusing the comparator (paper Fig. 4) with the pack avoids a
full-precision round-trip of the activation tensor through HBM.

Layout: input (M, S) float; output (M, S/32) uint32, little-endian bit
order (bit j of word k = element 32k + j), identical to
repro.core.packing.pack_bits.

The pack runs on the MXU: the 0/1 bit tile times a constant
(32*kw, kw) matrix holding 2^j at row 32k + j of column k sums each
word's bits.  Splitting every word into two 16-bit halves keeps each
sum below 2^16, exact in a float32 accumulator.  Every operand (0/1
bits, powers of two up to 2^15) is exact even in bfloat16, so the
product is exact at any matmul precision.  No lane axis is ever split
into (words, 32), which the TPU compiler refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

WORD_BITS = 32
DEFAULT_BM = 128
DEFAULT_BKW = 128  # words per block (= 4096 elements): one full lane tile


def _half_word_matrix(s: int, upper: int) -> Array:
    """(s, s/32) float32: 2^(j-16*upper) where row 32k+j feeds word k
    and bit j lies in the requested 16-bit half, else 0."""
    r = jax.lax.broadcasted_iota(jnp.int32, (s, s // WORD_BITS), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (s, s // WORD_BITS), 1)
    j = r & (WORD_BITS - 1)
    sel = ((r >> 5) == c) & ((j >> 4) == upper)
    w = jnp.where(sel, jnp.left_shift(jnp.int32(1), j & 15), 0)
    return w.astype(jnp.float32)


def pack_tile(x: Array, threshold: float) -> Array:
    """(bm, 32*kw) float tile -> (bm, kw) int32 packed sign words (bit
    pattern of the uint32 words); usable inside a kernel body."""
    s = x.shape[1]
    bits = (x >= threshold).astype(jnp.float32)
    lo, hi = (jnp.dot(bits, _half_word_matrix(s, u),
                      preferred_element_type=jnp.float32).astype(jnp.int32)
              for u in (0, 1))
    return jnp.left_shift(hi, 16) | lo


def _binarize_pack_kernel(x_ref, out_ref, *, threshold: float):
    out_ref[...] = pack_tile(x_ref[...], threshold)


def binarize_pack(x: Array, *, threshold: float = 0.0,
                  bm: int = DEFAULT_BM, bkw: int = DEFAULT_BKW,
                  interpret: bool | None = None) -> Array:
    """(M, S) float -> (M, ceil(S/32)) uint32 packed sign bits."""
    m, s = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kw = -(-s // WORD_BITS)
    bm = min(bm, m)
    bkw = min(bkw, kw)    # the whole word axis, or a full lane tile

    # pad: elements below threshold pack to 0 bits
    pad_s = (-s) % (bkw * WORD_BITS)
    pad_m = (-m) % bm
    xp = jnp.pad(x, ((0, pad_m), (0, pad_s)), constant_values=threshold - 1.0)
    mp, sp = xp.shape
    kwp = sp // WORD_BITS

    kernel = functools.partial(_binarize_pack_kernel, threshold=threshold)
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, kwp // bkw),
        in_specs=[pl.BlockSpec((bm, bkw * WORD_BITS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bkw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kwp), jnp.int32),
        interpret=interpret,
        name="bnn_binarize_pack",
    )(xp)
    return jax.lax.bitcast_convert_type(out[:m, :kw], jnp.uint32)
