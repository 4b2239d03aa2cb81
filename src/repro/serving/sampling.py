"""Per-request sampling: host-side parameters + in-jit token selection.

``SamplingParams`` travels with every request (temperature / top-k /
top-p / seed / stop tokens); the jitted engine steps call
``sample_tokens`` so token selection happens ON DEVICE, next to the
logits, instead of round-tripping the full vocab distribution to host.

Determinism contract: the PRNG key for the token at absolute sequence
index ``i`` is ``fold_in(PRNGKey(seed), i)`` — a pure function of the
request's seed and the token position.  Batch composition, power-of-two
bucket padding, preemption (recompute replays the same positions) and
swap-in (positions restored exactly) therefore never change a sampled
stream: same seed => same tokens, by construction.  ``temperature == 0``
is exact argmax (greedy) and ignores the seed entirely.

Sampling itself is Gumbel-max over the filtered logits: top-k keeps the
k highest logits, top-p keeps the smallest prefix of the sorted
distribution whose probability mass reaches p (always at least the top
token), and ``argmax(logits/T + gumbel)`` draws exactly from the
renormalized categorical — no explicit normalization needed.  The
filter sorts the values only: the kept prefix ends at some value, so
the support is masked in vocabulary order as ``logits >= cut`` — no
argsort and no scatter back (a TPU expands that scatter into an index
sort and flattened fusions over every row's vocabulary).  Every token
tied with the cut is kept, and ``top_p = 1`` keeps the whole
vocabulary.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

NEG = -1e30


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy.  Defaults reproduce greedy decoding."""
    temperature: float = 0.0      # 0 => greedy argmax (seed ignored)
    top_k: int = 0                # 0 => no top-k filter
    top_p: float = 1.0            # 1.0 => no nucleus filter
    seed: int = 0                 # per-request PRNG stream
    stop: tuple[int, ...] = ()    # stop/eos token ids (early termination)

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0: {self.top_k}")
        object.__setattr__(self, "stop", tuple(int(t) for t in self.stop))

    @property
    def stop_set(self) -> frozenset[int]:
        return frozenset(self.stop)


GREEDY = SamplingParams()


@dataclass
class SamplingRows:
    """Padded per-row device operands for one jitted step."""
    seeds: np.ndarray             # (B,) uint32
    temps: np.ndarray             # (B,) float32
    top_k: np.ndarray             # (B,) int32
    top_p: np.ndarray             # (B,) float32

    @property
    def sampled(self) -> int:
        """Rows drawn by the filter and Gumbel noise (temperature > 0)."""
        return int(np.count_nonzero(self.temps > 0))

    def as_args(self):
        return (jnp.asarray(self.seeds), jnp.asarray(self.temps),
                jnp.asarray(self.top_k), jnp.asarray(self.top_p))


def sampling_rows(reqs, batch: int) -> SamplingRows:
    """Pack each request's SamplingParams into padded (B,) arrays;
    padded rows are greedy (their outputs are discarded anyway)."""
    rows = SamplingRows(np.zeros(batch, np.uint32),
                        np.zeros(batch, np.float32),
                        np.zeros(batch, np.int32),
                        np.ones(batch, np.float32))
    for i, r in enumerate(reqs):
        sp = r.sampling
        rows.seeds[i] = sp.seed & 0xFFFFFFFF
        rows.temps[i] = sp.temperature
        rows.top_k[i] = sp.top_k
        rows.top_p[i] = sp.top_p
    return rows


def _filter_row(logits: Array, top_k: Array, top_p: Array) -> Array:
    """Mask one row's logits to the top-k / nucleus support (NEG out).

    The support is a prefix of the descending sorted values, so it is
    every token whose logit reaches the value at the prefix's last rank:
    the cut is found on a values-only sort and applied in vocabulary
    order, with no permutation back.  Ties: every token equal to the cut
    is kept, so the support depends on the logit values alone.
    ``top_p >= 1`` keeps every token (a float32 cumsum saturates below 1
    in a wide row's tail; the test would otherwise drop it).
    """
    v = logits.shape[-1]
    # descending values; unstable, as equal values need no order (a
    # stable sort carries an index operand through it on a TPU)
    ranked = -jnp.sort(-logits, stable=False)
    # top-k: rank >= k is out (k == 0 disables)
    ranks = jnp.arange(v, dtype=jnp.int32)
    keep = (top_k <= 0) | (ranks < top_k)
    # top-p: keep the smallest prefix with cumulative mass >= p; the
    # "- prob" keeps every token whose cumsum FIRST reaches p (so the
    # top token always survives even when p < its probability)
    probs = jax.nn.softmax(ranked)
    keep &= (top_p >= 1.0) | (jnp.cumsum(probs) - probs < top_p)
    # n = leading ranks that pass (the first failing rank; rank 0 always
    # passes, so n >= 1): a float32 cumsum need not be monotone, so a
    # count of the passing ranks could reach past the first that fails
    fail = ~keep
    n = jnp.where(jnp.any(fail), jnp.argmax(fail), v)
    return jnp.where(logits >= ranked[n - 1], logits, NEG)


def _sample_row(logits: Array, key: Array, temp: Array, top_k: Array,
                top_p: Array) -> Array:
    greedy_tok = jnp.argmax(logits).astype(jnp.int32)
    safe_t = jnp.maximum(temp, 1e-6)
    filtered = _filter_row(logits.astype(jnp.float32) / safe_t, top_k, top_p)
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)
    sampled = jnp.argmax(filtered + gumbel).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy_tok)


def token_key(seed: Array, index: Array) -> Array:
    """PRNG key for the token at absolute sequence position ``index``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), index)


def sample_tokens(logits: Array, index: Array, seeds: Array, temps: Array,
                  top_k: Array, top_p: Array) -> Array:
    """Select one token per row, on device.

    logits (B, V); index (B,) absolute sequence position of the token
    being chosen (the PRNG stream position); seeds/temps/top_k/top_p
    (B,) per-request sampling params.  Returns (B,) int32.
    """
    keys = jax.vmap(token_key)(seeds, index)
    return jax.vmap(_sample_row)(logits, keys, temps, top_k, top_p)


# ---------------------------------------------------------------------------
# prompt-lookup drafting (speculative decoding's "draft model")


def prompt_lookup_draft(seq: np.ndarray, k: int, max_ngram: int = 3
                        ) -> np.ndarray:
    """Draft up to ``k`` tokens by n-gram lookup in the sequence itself.

    Finds the most recent earlier occurrence of the sequence's final
    n-gram (longest n first) and proposes the tokens that followed it —
    prompt-lookup decoding (no second model).  Returns an empty array
    when nothing matches.
    """
    seq = np.asarray(seq)
    ln = len(seq)
    if k <= 0 or ln < 2:
        return np.empty(0, np.int32)
    for n in range(min(max_ngram, ln - 1), 0, -1):
        pat = seq[ln - n:]
        # all candidate windows ending strictly before the suffix
        wins = np.lib.stride_tricks.sliding_window_view(seq[:-1], n)
        hits = np.nonzero((wins == pat).all(axis=1))[0]
        for i in hits[::-1]:                 # most recent first
            cont = seq[i + n:i + n + k]
            if len(cont):
                return np.asarray(cont, np.int32)
    return np.empty(0, np.int32)
