"""Percentiles and window arithmetic of one run, from the client's clock.

``nearest_rank`` is copied from the serving engine's (``Engine.stats``)
so that this yardstick does not move when the program's copy does.
"""
from __future__ import annotations

import math

INF = float("inf")


def nearest_rank(sorted_vals, p: float) -> float:
    """Nearest-rank percentile over an ascending sample: the smallest
    value with at least p% of the sample at or below it, 0-indexed
    ``ceil(p/100 * n) - 1``."""
    if not len(sorted_vals):
        return float("nan")
    n = len(sorted_vals)
    idx = max(math.ceil(p / 100 * n) - 1, 0)
    return sorted_vals[min(idx, n - 1)]


def censored_percentile(done, waiting, p: float) -> float:
    """Percentile over finished samples ``done`` and unfinished ones
    whose wait so far is ``waiting``: every unfinished sample ranks
    above every finished one, and reads as its wait so far."""
    ranked = sorted(done) + sorted(waiting)
    if not waiting:
        return nearest_rank(ranked, p)
    n = len(ranked)
    idx = min(max(math.ceil(p / 100 * n) - 1, 0), n - 1)
    return ranked[idx] if idx < len(done) else max(ranked[idx],
                                                   max(done, default=0.0))


def ttft_ms(requests, w0: float, w1: float) -> tuple[list, list]:
    """(finished, unfinished) time-to-first-token samples in ms of the
    requests due in [w0, w1): from the due time to the first token, or
    to the window's end when no token came."""
    done, waiting = [], []
    for r in requests:
        if not w0 <= r["due"] < w1:
            continue
        t = r["tokens"][0] if r["tokens"] else None
        if t is not None and t <= w1:
            done.append((t - r["due"]) * 1e3)
        else:
            waiting.append((w1 - r["due"]) * 1e3)
    return done, waiting


def inter_token_ms(requests, w0: float, w1: float) -> list[float]:
    """Every gap between two consecutive tokens of one request, pooled,
    where both tokens became visible inside [w0, w1]."""
    gaps = []
    for r in requests:
        ts = r["tokens"]
        for a, b in zip(ts, ts[1:]):
            if w0 <= a and b <= w1:
                gaps.append((b - a) * 1e3)
    return gaps


def tokens_in(requests, w0: float, w1: float) -> int:
    return sum(1 for r in requests for t in r["tokens"] if w0 <= t <= w1)
