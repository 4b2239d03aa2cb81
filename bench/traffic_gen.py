"""The one traffic generator: turns a traffic mix (a data file under
``traffic/``) and a cell's rate into a seeded open-loop schedule.

Every seed gets the same multiset of request sizes and of gaps between
arrivals, drawn once at fixed quantiles of the mix's distributions, in
an order the seed chooses; the seed also draws every token id.  So two
seeds offer the same work in a different order, and a run's spread comes
from the system, not from a lucky draw of lengths.  The order is
stratified (``stratified``): every ``STRATA`` consecutive requests hold
one size from each of ``STRATA`` quantile bands, so that the part of
the schedule a window serves, which is all a cell above its knee
reaches, is the same work for every seed too.

A mix file holds:
  arrivals  {"process": "poisson"}
  prompt    a length distribution (below) for prompts of unique tokens
  output    a length distribution for the tokens generated
  shared    optional {"share", "documents", "doc_len", "question"}: that
            share of requests is one of ``documents`` fixed documents of
            ``doc_len`` tokens followed by a unique question
  sampling  {"temperature", "top_p", "greedy_every"}: every
            ``greedy_every``-th request decodes greedily (the requests the
            correctness check compares), the rest sample

Length distributions: {"dist": "lognormal", "median", "sigma", "min",
"max"} or {"dist": "uniform", "min", "max"}, clipped to [min, max].
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Arrival:
    due_s: float            # scheduled send time from the traffic start
    prompt: np.ndarray      # int32 token ids
    max_new: int
    temperature: float
    top_p: float
    seed: int               # the request's sampling stream
    greedy: bool


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


STRATA = 8


def stratified(values: np.ndarray, rng: np.random.Generator,
               k: int = STRATA) -> np.ndarray:
    """``values`` (ascending) in a seeded order in which each run of ``k``
    consecutive entries takes one entry from each of ``k`` quantile
    bands, the bands in a seeded order each round."""
    bands = [rng.permutation(b)
             for b in np.array_split(np.arange(len(values)), k)]
    order = []
    for t in range(max((len(b) for b in bands), default=0)):
        order.extend(bands[g][t] for g in rng.permutation(k)
                     if t < len(bands[g]))
    return np.asarray(values)[np.asarray(order, np.int64)]


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``, ascending."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _exp_gaps(n: int, mean: float) -> np.ndarray:
    return -mean * np.log1p(-_quantiles(n))


def arrival_times(spec: dict, rate: float, horizon_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival times in [0, horizon_s) at mean ``rate`` per second: the
    same number for every seed, from gaps at fixed quantiles in a seeded
    order."""
    if rate <= 0:
        raise ValueError("rate must be positive")
    n = max(1, int(round(rate * horizon_s)))
    if spec["process"] == "poisson":
        gaps = stratified(_exp_gaps(n, 1.0 / rate), rng)
        return np.cumsum(gaps) * horizon_s / (gaps.sum() + 1.0 / rate)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def schedule(mix: dict, rate: float, horizon_s: float, vocab: int,
             seed: int) -> list[Arrival]:
    """The seeded request schedule of one run."""
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], rate, horizon_s, rng)
    n = len(due)
    out_len = stratified(lengths(mix["output"], n), rng)
    shared = mix.get("shared")
    n_shared = int(round(shared["share"] * n)) if shared else 0
    is_shared = stratified(np.arange(n) >= n - n_shared, rng)
    uniq = stratified(lengths(mix["prompt"], n - n_shared), rng)
    docs = doc_ids = questions = None
    if shared:
        docs = [rng.integers(0, vocab, shared["doc_len"], dtype=np.int64)
                for _ in range(shared["documents"])]
        doc_ids = rng.permutation(np.arange(n_shared) % shared["documents"])
        questions = stratified(lengths(shared["question"], n_shared), rng)
    samp = mix["sampling"]
    every = samp["greedy_every"]
    first_greedy = int(rng.integers(0, every))
    arrivals, iu, isd = [], 0, 0
    for i in range(n):
        if is_shared[i]:
            q = rng.integers(0, vocab, questions[isd], dtype=np.int64)
            prompt = np.concatenate([docs[doc_ids[isd]], q])
            isd += 1
        else:
            prompt = rng.integers(0, vocab, uniq[iu], dtype=np.int64)
            iu += 1
        greedy = i % every == first_greedy
        arrivals.append(Arrival(
            due_s=float(due[i]), prompt=prompt.astype(np.int32),
            max_new=int(out_len[i]),
            temperature=0.0 if greedy else samp["temperature"],
            top_p=1.0 if greedy else samp["top_p"],
            seed=int(rng.integers(0, 1 << 31)), greedy=greedy))
    return arrivals


def longest(mix: dict) -> int:
    """Upper bound of prompt + output tokens of any request of the mix."""
    p = mix["prompt"]["max"]
    if mix.get("shared"):
        s = mix["shared"]
        p = max(p, s["doc_len"] + s["question"]["max"])
    return p + mix["output"]["max"]
