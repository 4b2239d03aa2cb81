"""Pallas TPU kernel: packed XNOR + popcount GEMM with PCA-style accumulation.

This is the compute hot-spot of the paper: Eq. (2)'s XNOR-bitcount VDP,
tiled for the TPU memory hierarchy.

Design (HW adaptation of the XPC, see DESIGN.md):
  * The contraction (S) axis is bitpacked into uint32 words — 32 binary
    "wavelengths" per word (DWDM -> SIMD lanes).
  * Grid = (M/bm, N/bn, Kw/bk).  The (bm, bn) int32 accumulator tile
    lives in VMEM and is REVISITED across the K grid dimension: partial
    bitcounts accumulate IN PLACE, never touching HBM — the exact TPU
    analogue of the PCA holding charge across PASSes (no psum
    reduction network, paper Sec. IV-C).
  * The epilogue (agreeing-bit count + {-1,+1} rescale + LQ-Nets alpha
    scale or the paper's comparator activation) is fused into the final K step
    — the analogue of the PCA's comparator producing the next layer's
    activation before anything is written back.

The kernel is VPU work (integer xor/popcount/add); MXU is not used.
The weight operand enters word-major, (Kw, N): each packed word of the
activation tile is a (bm, 1) column broadcast along lanes against one
(1, bn) weight row, so every XOR/popcount runs on a full (bm, bn) tile.
The kernel counts DISAGREEING bits, popcount(a ^ w): pad bits are zero
in both operands, so z = s - count needs no pad correction.

Validated on CPU via interpret=True against ref.py across shape/dtype
sweeps (tests/test_xnor_kernel.py) and compiled for the TPU
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

WORD_BITS = 32

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128  # packed words per K step (= 4096 binary elements)

MODES = ("bitcount", "dot", "dot_scaled", "binary_act")


def accumulate_xor_popcount(acc: Array, ip: Array, wp_t: Array) -> Array:
    """acc (bm, bn) int32 + sum_k popcount(ip[:, k] ^ wp_t[k, :]) for an
    int32 activation tile ip (bm, bk) and weight tile wp_t (bk, bn)."""
    for k in range(ip.shape[1]):
        acc = acc + jax.lax.population_count(ip[:, k:k + 1] ^ wp_t[k:k + 1, :])
    return acc


def epilogue(xor_count: Array, alpha: Array, s: int, mode: str) -> Array:
    """Readout of the accumulated disagreement count; alpha is (1, bn)."""
    z = s - xor_count                       # agreeing bits
    if mode == "bitcount":
        return z
    if mode == "dot":
        return 2 * z - s
    if mode == "dot_scaled":
        return (2 * z - s).astype(jnp.float32) * alpha
    if mode == "binary_act":
        return (2 * z > s).astype(jnp.int32)
    raise ValueError(mode)


def gemm_call(kernel, operand, block_cols: int, wp: Array,
              alpha: Array | None, *, m: int, kwp: int, mode: str,
              bm: int, bn: int, bk: int, interpret: bool) -> Array:
    """Shared pallas_call for the packed GEMMs: ``operand`` is the padded
    (mp, .) activation array, ``block_cols`` wide per K block of ``bk``
    words (``kwp`` padded words in all); ``wp`` is the (N, Kw) packed
    weight, transposed here to (Kw, N)."""
    if mode not in MODES:
        raise ValueError(mode)
    n = wp.shape[0]
    if alpha is None:
        alpha = jnp.ones((n,), jnp.float32)
    assert alpha.shape == (n,)
    mp = operand.shape[0]
    bn = min(bn, n)

    def padto(a, b, axis):
        pad = (-a.shape[axis]) % b
        if pad == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        return jnp.pad(a, widths)

    # pad words are zero, like the activation side's, so they never
    # disagree; word-major layout puts N on lanes
    wp_t = jax.lax.bitcast_convert_type(wp, jnp.int32).T
    wp_t = padto(padto(wp_t, kwp, 0), bn, 1)
    alpha_p = padto(alpha.astype(jnp.float32).reshape(1, n), bn, 1)
    np_ = wp_t.shape[1]

    out_dtype = jnp.float32 if mode == "dot_scaled" else jnp.int32
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, kwp // bk),
        in_specs=[
            pl.BlockSpec((bm, block_cols), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bnn_xnor_gemm",
    )(operand, wp_t, alpha_p)

    out = out[:m, :n]
    if mode == "binary_act":
        out = out.astype(jnp.uint8)
    return out


def _xnor_popcount_kernel(ip_ref, wp_ref, alpha_ref, out_ref, acc_ref, *,
                          s: int, mode: str):
    """One (m, n, k) grid step.

    acc_ref: VMEM scratch (bm, bn) int32 — the 'photo-charge' accumulator.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = accumulate_xor_popcount(acc_ref[...], ip_ref[...],
                                           wp_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        out_ref[...] = epilogue(acc_ref[...], alpha_ref[...], s, mode)


def xnor_popcount_matmul(ip: Array, wp: Array, s: int, *,
                         mode: str = "dot",
                         alpha: Array | None = None,
                         bm: int = DEFAULT_BM,
                         bn: int = DEFAULT_BN,
                         bk: int = DEFAULT_BK,
                         interpret: bool | None = None) -> Array:
    """Packed XNOR-bitcount GEMM: (M, Kw) x (N, Kw) -> (M, N).

    ip/wp are uint32 bitpacked along K (zero-padded); ``s`` is the true
    contraction length in bits.  See module docstring for modes.
    """
    m, kw = ip.shape
    assert wp.shape[1] == kw, (kw, wp.shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bm = min(bm, m)
    bk = min(bk, kw)
    ip_i = jax.lax.bitcast_convert_type(ip, jnp.int32)
    ip_p = jnp.pad(ip_i, ((0, (-m) % bm), (0, (-kw) % bk)))
    kernel = functools.partial(_xnor_popcount_kernel, s=s, mode=mode)
    return gemm_call(kernel, ip_p, bk, wp, alpha, m=m, kwp=ip_p.shape[1],
                     mode=mode, bm=bm, bn=bn, bk=bk, interpret=interpret)
