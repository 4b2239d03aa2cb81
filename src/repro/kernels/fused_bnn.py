"""Pallas TPU kernel: fused binarize -> bitpack -> XNOR-popcount GEMM.

``binarize_pack`` and ``xnor_popcount_matmul`` used to run as SEPARATE
pallas calls: the packed activation matrix round-tripped through HBM
between the comparator and the GEMM.  This kernel fuses the whole BNN
chain — the float activation tile is binarized against the threshold
and packed into words in VMEM (``binarize_pack.pack_tile``), then
XNOR'd/popcounted against the (pre-packed, weight-stationary) weight
tile in the same grid step.  Packed activations never exist in HBM,
matching the paper's datapath where the PCA comparator feeds the next
layer's OXG operand drive directly (Sec. IV-C; cf. XNORBIN's fused
binarize-convolve loop, arXiv:1803.05849).

Same grid/accumulator/epilogue as kernels/xnor_popcount.py (shared
``gemm_call``: the (bm, bn) int32 VMEM accumulator revisited across the
K grid dim = the PCA photo-charge), so the two kernels stay
differentially comparable; only the activation operand arrives
unpacked.

Weights stay a packed (N, Kw) uint32 operand: they are static across
forwards, so packing them once per weight identity (kernels/ops.py
caches this) and keeping the fused kernel activation-only is the right
split — re-binarizing W per call would waste the weight-stationary
energy story the paper's MRR banks model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import binarize_pack as _bp
from repro.kernels import xnor_popcount as _xp

Array = jax.Array

WORD_BITS = 32

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 64   # packed words per K step (= 2048 float elements of x)


def _fused_bnn_kernel(x_ref, wp_ref, alpha_ref, out_ref, acc_ref, *,
                      s: int, mode: str, threshold: float):
    """One (m, n, k) grid step: binarize+pack x tile, XNOR-popcount it
    against the packed weight tile, accumulate in VMEM scratch."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fused operand drive: comparator + pack, never leaving VMEM
    ip = _bp.pack_tile(x_ref[...], threshold)        # (bm, bk) int32
    acc_ref[...] = _xp.accumulate_xor_popcount(acc_ref[...], ip,
                                               wp_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        out_ref[...] = _xp.epilogue(acc_ref[...], alpha_ref[...], s, mode)


def fused_bnn_matmul(x: Array, wp: Array, s: int, *,
                     mode: str = "dot",
                     alpha: Array | None = None,
                     threshold: float = 0.0,
                     bm: int = DEFAULT_BM,
                     bn: int = DEFAULT_BN,
                     bk: int = DEFAULT_BK,
                     interpret: bool | None = None) -> Array:
    """Fused binarize(x) @ unpack(wp).T in one kernel: (M, S) float x
    (N, Kw) packed -> (M, N).

    ``s`` is the true contraction length in bits (= x.shape[1]); modes
    match xnor_popcount_matmul.  The activation side is binarized and
    packed in-kernel; only the weight operand is pre-packed.
    """
    m, sx = x.shape
    assert sx == s, (sx, s)
    kw = wp.shape[1]
    assert kw == -(-s // WORD_BITS), (kw, s)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bm = min(bm, m)
    bk = min(bk, kw)

    # pad x with sub-threshold values (-> 0 bits, same as the packed
    # weight's zero tail) so pad bits never disagree
    pad_m = (-m) % bm
    pad_s = (-s) % (bk * WORD_BITS)
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_m), (0, pad_s)),
                 constant_values=threshold - 1.0)
    kernel = functools.partial(_fused_bnn_kernel, s=s, mode=mode,
                               threshold=threshold)
    return _xp.gemm_call(kernel, xp, bk * WORD_BITS, wp, alpha, m=m,
                         kwp=xp.shape[1] // WORD_BITS, mode=mode,
                         bm=bm, bn=bn, bk=bk, interpret=interpret)
