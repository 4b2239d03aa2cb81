"""The ssm family's share of the work counts (``costs.py``): a pre-norm
Mamba-2 mixer per layer, no FFN, a tied head; ``in_proj`` and
``out_proj`` binarized."""
from costs import Work


def _dims(c: dict) -> tuple[int, int, int, int, int]:
    """(d_inner, heads, head dim, state size, conv channels)."""
    d_inner = c["ssm_expand"] * c["d_model"]
    hd, n = c["ssm_headdim"], c["ssm_state"]
    return d_inner, d_inner // hd, hd, n, d_inner + 2 * n


def projections(c: dict) -> list[tuple[int, int]]:
    """(K, N) of every binarized projection of one layer: ``in_proj`` to
    z, x, B, C and dt, and ``out_proj``."""
    d = c["d_model"]
    d_inner, heads, _, n, _ = _dims(c)
    return [(d, 2 * d_inner + 2 * n + heads), (d_inner, d)]


def mixer(c: dict, rows: list[tuple[int, int]], state_bytes: int = 4
          ) -> Work:
    """The SSD over ``rows`` of (new tokens, context after them), in
    every layer, by the recurrence (the least work; the dual form a
    prefill chunk runs does more): per token and head, the state update
    ``h = a h + dt B x`` (3 operations an entry) and the readout ``C . h``
    (2); per row, the slot's state and conv tail read once and written
    once; per token, the in_proj output read and the gated output
    written."""
    d_inner, heads, hd, n, ch = _dims(c)
    state = (heads * n * hd + (c["ssm_conv"] - 1) * ch) * state_bytes
    d_in = 2 * d_inner + 2 * n + heads
    ops = byt = 0.0
    for q_len, _ctx in rows:
        ops += 5.0 * heads * n * hd * q_len
        byt += 2.0 * state + 4.0 * q_len * (d_in + d_inner)
    return Work(ops * c["n_layers"], byt * c["n_layers"])


def float_ops(c: dict, tokens: int) -> float:
    """Float operations besides the SSD's products for ``tokens`` rows:
    the tied head and final norm; per layer the block norm, the conv and
    its silu, softplus and the D skip, the gate and the gated norm."""
    d, v, n_l = c["d_model"], c["vocab"], c["n_layers"]
    d_inner, heads, _, _, ch = _dims(c)
    per_token = 2.0 * d * v + 4.0 * d
    per_layer = 4.0 * d + (2.0 * c["ssm_conv"] + 4.0) * ch + 4.0 * heads \
        + 10.0 * d_inner
    return tokens * (per_token + n_l * per_layer)
