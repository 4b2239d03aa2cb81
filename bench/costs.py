"""Operations and bytes the algorithm needs, from shapes alone.

The counts do not depend on how the program implements a step: a GEMM
is 2*M*K*N operations whether it runs as XNOR-popcount on the vector
unit or as int8 on the matrix unit, and its weight is K*N/8 bytes however
the program stores it.  Rooflines and the whole step's share of the peak
are read against these counts (``metrics/``).

What holds for any family is here; what depends on the layers is asked
of the configuration's family (``families/<family>/costs.py``, found by
``loader.family``): the (K, N) of each binarized projection of a layer,
the float operations per token, and the sequence mixer's work.
"""
from __future__ import annotations

from dataclasses import dataclass

import loader


@dataclass
class Work:
    ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.ops + o.ops, self.bytes + o.bytes)


def bnn_gemm(m: int, k: int, n: int, act_bytes: int = 4) -> Work:
    """One binarized projection of m rows: 2*m*k*n +-1 operations; the
    packed weight (k*n bits), one float32 alpha per column, the float
    activations in and the float32 outputs out."""
    return Work(2.0 * m * k * n,
                k * n / 8 + 4 * n + m * k * act_bytes + m * n * 4)


def projections(c: dict) -> list[tuple[int, int]]:
    """(K, N) of every binarized projection of one layer."""
    return loader.family(c, "costs").projections(c)


def float_ops(c: dict, tokens: int) -> float:
    """Float operations besides the mixer's products for ``tokens`` rows."""
    return loader.family(c, "costs").float_ops(c, tokens)


def mixer(c: dict, rows: list[tuple[int, int]]) -> Work:
    """The sequence mixer's work over ``rows`` of (new tokens, context
    after them), in every layer: paged attention in the dense family."""
    return loader.family(c, "costs").mixer(c, rows)


attention = mixer       # the dense family's mixer, as its readers name it


def gemms(c: dict, tokens: int) -> Work:
    """Every binarized projection of a step that feeds ``tokens`` rows."""
    w = Work()
    for k, n in projections(c):
        w = w + bnn_gemm(tokens, k, n)
    return Work(w.ops * c["n_layers"], w.bytes * c["n_layers"])


def step_least_s(c: dict, rows: list[tuple[int, int]], peaks: dict) -> float:
    """The least time of one step at the chip's peaks: the binarized
    GEMMs' operations at the int8 peak plus every float operation
    (the mixer's included) at the bf16 peak."""
    tokens = sum(q for q, _ in rows)
    f = float_ops(c, tokens) + mixer(c, rows).ops
    return gemms(c, tokens).ops / peaks["int8_ops_per_s"] \
        + f / peaks["bf16_flops_per_s"]


def roofline_s(w: Work, peak_ops: float, bw: float) -> float:
    """The larger of the compute and the memory bound of ``w``."""
    return max(w.ops / peak_ops, w.bytes / bw)
