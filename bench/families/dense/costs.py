"""The dense family's share of the work counts (``costs.py``): pre-norm
GQA attention with rotary embedding, a SwiGLU FFN and a tied head, every
projection binarized."""
from costs import Work


def projections(c: dict) -> list[tuple[int, int]]:
    """(K, N) of every binarized projection of one layer."""
    d = c["d_model"]
    hq, hkv, dh, f = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    return [(d, hq * dh), (d, hkv * dh), (d, hkv * dh), (hq * dh, d),
            (d, f), (d, f), (f, d)]


def mixer(c: dict, rows: list[tuple[int, int]], kv_bytes: int = 4) -> Work:
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
