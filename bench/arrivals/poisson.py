"""Poisson arrivals: exponential gaps at mean 1/rate, drawn at fixed
quantiles and put in a seeded stratified order, then scaled so that the
``n`` arrivals and one more mean gap fill the horizon."""
import numpy as np

import traffic_gen


def times(spec: dict, n: int, rate: float, horizon_s: float,
          rng: np.random.Generator) -> np.ndarray:
    gaps = traffic_gen.stratified(
        -(1.0 / rate) * np.log1p(-traffic_gen.quantiles(n)), rng)
    return np.cumsum(gaps) * horizon_s / (gaps.sum() + 1.0 / rate)
