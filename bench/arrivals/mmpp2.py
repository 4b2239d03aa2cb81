"""A two-phase modulated Poisson process: the rate is the mean rate
times each phase's multiplier, the phases of fixed lengths take turns,
and the seed draws where in the cycle the traffic starts.

    "arrivals": {"process": "mmpp2",
                 "phases": [{"seconds": 2.0, "rate_x": 2.5},
                            {"seconds": 4.0, "rate_x": 0.25}]}

The multipliers are scaled so that their time-weighted mean is 1.  The
arrivals are a unit-rate Poisson process (exponential gaps drawn at
fixed quantiles, in a seeded stratified order, as ``poisson.py`` draws
them) put through the inverse of the cumulative rate, scaled so that
the ``n`` arrivals and one more mean gap fill the horizon: a burst
phase packs 2.5x the mean rate's arrivals into its seconds, a quiet
phase spreads them out."""
import numpy as np

import traffic_gen


def times(spec: dict, n: int, rate: float, horizon_s: float,
          rng: np.random.Generator) -> np.ndarray:
    secs = np.array([p["seconds"] for p in spec["phases"]], float)
    mult = np.array([p["rate_x"] for p in spec["phases"]], float)
    if (secs <= 0).any() or (mult <= 0).any():
        raise ValueError("every phase needs positive seconds and rate_x")
    mult = mult / (secs @ mult / secs.sum())
    cycle = secs.sum()
    offset = rng.uniform(0.0, cycle)
    # the phases on the traffic's clock, clipped to [0, horizon_s]
    ends = np.cumsum(secs)
    t, lv = [0.0], []
    k = 0
    while k * cycle - offset < horizon_s:
        for i in range(len(secs)):
            e = min(k * cycle + ends[i] - offset, horizon_s)
            if e > t[-1]:
                t.append(e)
                lv.append(mult[i])
        k += 1
    t = np.array(t)
    cum = np.concatenate([[0.0], np.cumsum(np.diff(t) * np.array(lv))])
    gaps = traffic_gen.stratified(-np.log1p(-traffic_gen.quantiles(n)), rng)
    units = np.cumsum(gaps) * cum[-1] / (gaps.sum() + 1.0)
    return np.interp(units, cum, t)
