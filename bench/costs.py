"""Operations and bytes the algorithm needs, from shapes alone.

The counts do not depend on how the program implements a step: a GEMM
is 2*M*K*N operations whether it runs as XNOR-popcount on the vector
unit or as int8 on the matrix unit, and its weight is K*N/8 bytes however
the program stores it.  Rooflines and the whole step's share of the peak
are read against these counts (``metrics/``).
"""
from __future__ import annotations

from dataclasses import dataclass


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
    d = c["d_model"]
    if c["family"] == "dense":
        hq, hkv, dh, f = c["n_heads"], c["n_kv_heads"], c["head_dim"], \
            c["d_ff"]
        return [(d, hq * dh), (d, hkv * dh), (d, hkv * dh), (hq * dh, d),
                (d, f), (d, f), (f, d)]
    raise ValueError(f"no projection list for family {c['family']!r}")


def gemms(c: dict, tokens: int) -> Work:
    """Every binarized projection of a step that feeds ``tokens`` rows."""
    w = Work()
    for k, n in projections(c):
        w = w + bnn_gemm(tokens, k, n)
    return Work(w.ops * c["n_layers"], w.bytes * c["n_layers"])


def attention(c: dict, rows: list[tuple[int, int]],
              kv_bytes: int = 4) -> Work:
    """Paged attention over ``rows`` of (new tokens, context after them):
    each new token attends to every position up to its own (QK and PV,
    2 operations a multiply-add each); the K and V of each row's context
    are read once, the queries and outputs once, in every layer."""
    hq, hkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    ops = byt = 0.0
    for q_len, ctx in rows:
        start = ctx - q_len
        pairs = q_len * start + q_len * (q_len + 1) / 2
        ops += 4.0 * hq * dh * pairs
        byt += 2.0 * ctx * hkv * dh * kv_bytes + 2.0 * q_len * hq * dh * 4
    return Work(ops * c["n_layers"], byt * c["n_layers"])


def float_ops(c: dict, tokens: int) -> float:
    """Float operations besides attention's products for ``tokens`` rows:
    the tied head, the norms, the gates and the rotary embedding."""
    d, v, n_l = c["d_model"], c["vocab"], c["n_layers"]
    per_token = 2.0 * d * v + 4.0 * d
    per_layer = 8.0 * d + 4.0 * c["d_ff"] \
        + 6.0 * (c["n_heads"] + c["n_kv_heads"]) * c["head_dim"]
    return tokens * (per_token + n_l * per_layer)


def step_least_s(c: dict, rows: list[tuple[int, int]], peaks: dict) -> float:
    """The least time of one step at the chip's peaks: the binarized
    GEMMs' operations at the int8 peak plus every float operation
    (attention included) at the bf16 peak."""
    tokens = sum(q for q, _ in rows)
    f = float_ops(c, tokens) + attention(c, rows).ops
    return gemms(c, tokens).ops / peaks["int8_ops_per_s"] \
        + f / peaks["bf16_flops_per_s"]


def roofline_s(w: Work, peak_ops: float, bw: float) -> float:
    """The larger of the compute and the memory bound of ``w``."""
    return max(w.ops / peak_ops, w.bytes / bw)
