"""Per-request sampling + speculative decoding (and the engine
stats/termination bugfixes that landed with them).

Contracts under test:
  * nearest-rank percentiles (p50 no longer biased high, p99 != max
    for n = 100) and the decode/total tokens-per-second split;
  * stop tokens finish a request the step they are emitted and release
    its blocks; a stalled ``Engine.run()`` reports WHY each stuck
    request cannot progress;
  * sampling is a pure function of (seed, position): same seed => same
    tokens across bucket-size changes and forced preempt/swap cycles;
  * speculative decoding is a pure accelerator: greedy spec-decode
    reproduces plain greedy EXACTLY for one arch per mixer family
    (incl. across a forced preempt/swap cycle), sampled spec-decode
    reproduces sampled non-spec decoding, and partial draft acceptance
    rolls back correctly on SSM slots and ring tables.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import SamplingParams, nearest_rank, prompt_lookup_draft
from repro.serving.request import State
from repro.serving.sampling import NEG, _filter_row, sample_tokens, token_key
from test_serving import _engine  # bnn_cfg/bnn_params live in conftest.py


# ------------------------------------------------------------ percentiles


def test_nearest_rank_percentile_boundaries():
    """Satellite: int(p/100*n) reads p50 one-high on even n and p99 as
    the max for n=100; ceil(p/100*n)-1 is the nearest-rank index."""
    lat100 = list(range(100))
    assert nearest_rank(lat100, 50) == 49     # was 50
    assert nearest_rank(lat100, 99) == 98     # was 99 (the max)
    assert nearest_rank(lat100, 100) == 99
    assert nearest_rank([7.0], 50) == 7.0
    assert nearest_rank([1.0, 2.0], 50) == 1.0   # lower of the two
    assert nearest_rank([1.0, 2.0], 51) == 2.0
    assert nearest_rank([1.0, 2.0, 3.0, 4.0], 75) == 3.0
    assert np.isnan(nearest_rank([], 50))
    assert nearest_rank(lat100, 0) == 0       # clamped low


# --------------------------------------------------------- sampling maths


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    assert SamplingParams(stop=[3, 5]).stop_set == {3, 5}


def _sample(logits, idx, seed=0, temp=1.0, top_k=0, top_p=1.0):
    b = logits.shape[0]
    return np.asarray(sample_tokens(
        jnp.asarray(logits, jnp.float32),
        jnp.full(b, idx, jnp.int32), jnp.full(b, seed, jnp.int32),
        jnp.full(b, temp, jnp.float32), jnp.full(b, top_k, jnp.int32),
        jnp.full(b, top_p, jnp.float32)))


def test_sample_tokens_greedy_and_filters():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 64)).astype(np.float32)
    argmax = logits.argmax(axis=-1)
    # temperature 0 == argmax regardless of seed
    np.testing.assert_array_equal(_sample(logits, 5, seed=1, temp=0.0),
                                  argmax)
    # top_k=1 / vanishing nucleus collapse any temperature onto argmax
    np.testing.assert_array_equal(_sample(logits, 5, temp=2.0, top_k=1),
                                  argmax)
    np.testing.assert_array_equal(_sample(logits, 5, temp=2.0, top_p=1e-6),
                                  argmax)
    # top_k support: samples always fall inside the k highest logits
    top8 = np.argsort(-logits, axis=-1)[:, :8]
    for idx in range(16):
        s = _sample(logits, idx, temp=3.0, top_k=8)
        assert all(s[i] in top8[i] for i in range(4))
    # deterministic in (seed, position); different position -> new draw
    a = _sample(logits, 7, seed=3, temp=1.0)
    b = _sample(logits, 7, seed=3, temp=1.0)
    np.testing.assert_array_equal(a, b)
    draws = {tuple(_sample(logits, i, seed=3, temp=5.0)) for i in range(32)}
    assert len(draws) > 1


_filter = jax.jit(jax.vmap(_filter_row))


def _logit_rows(seed, n, width):
    """Random logit rows, each with its own spread: nuclei from one
    token to thousands, as a vocabulary head at various temperatures
    gives."""
    rng = np.random.default_rng(seed)
    spread = np.geomspace(0.5, 24.0, n)[:, None]
    return (rng.normal(size=(n, width)) * spread).astype(np.float32)


def _reference_support(row, top_k, top_p, tol=4e-6):
    """float64 NumPy reference of the filter: sort, softmax, cumsum, the
    prefix cut.  Returns (kept mask in vocabulary order, decided):
    ``decided`` is False where the cut is tied or where the mass ahead
    of a rank up to the cut lies within ``tol`` of p, so that float32
    rounding, not the algorithm, places the cut (the float32 cumsum of
    a 151936-wide row strays from float64 by under 4e-7)."""
    x = row.astype(np.float64)
    v = x.size
    order = np.argsort(-x, kind="stable")
    ranked = x[order]
    n = v if top_k <= 0 else min(top_k, v)
    decided = True
    if top_p < 1.0:
        p = np.exp(ranked - ranked[0])
        p /= p.sum()
        ahead = np.concatenate([[0.0], np.cumsum(p)[:-1]])
        out = np.nonzero(ahead >= top_p)[0]
        n = min(n, int(out[0]) if out.size else v)
        near = np.abs(ahead[1:n + 1] - top_p) < tol
        decided = not near.any()
    if n < v and ranked[n] == ranked[n - 1]:
        decided = False
    keep = np.zeros(v, bool)
    keep[order[:n]] = True
    return keep, decided


@pytest.mark.parametrize("width", [64, 151936])
@pytest.mark.parametrize("top_p", [1e-6, 0.5, 0.9, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 8, 1000])
def test_filter_support_matches_float64_reference(top_k, top_p, width):
    """The threshold mask keeps exactly the reference's prefix wherever
    the cut is decided, and keeps each logit unchanged."""
    logits = _logit_rows(width, 12, width)
    out = np.asarray(_filter(jnp.asarray(logits),
                             jnp.full(12, top_k, jnp.int32),
                             jnp.full(12, top_p, jnp.float32)))
    kept = out > NEG
    np.testing.assert_array_equal(out[kept], logits[kept])
    assert np.all(out[~kept] == NEG)
    assert kept.any(axis=1).all()             # the top token survives
    decided = 0
    for i in range(12):
        want, ok = _reference_support(logits[i], top_k, top_p)
        if ok:
            decided += 1
            np.testing.assert_array_equal(kept[i], want, err_msg=str(i))
    assert decided >= 4


def test_filter_top_p_one_keeps_whole_vocabulary():
    """top_p = 1 keeps every token of a 151936-wide row, although the
    float32 cumsum saturates below 1 in the tail."""
    logits = _logit_rows(1, 8, 151936)
    out = np.asarray(_filter(jnp.asarray(logits), jnp.zeros(8, jnp.int32),
                             jnp.ones(8, jnp.float32)))
    np.testing.assert_array_equal(out, logits)


def test_filter_keeps_every_token_tied_at_cut():
    """Tokens equal to the cut's value are all kept, for a top-k cut and
    for a nucleus cut."""
    row = np.full(64, -30.0, np.float32)
    row[[5, 17, 40, 63]] = [4.0, 2.0, 2.0, 2.0]
    row[9] = -1.0
    rows = jnp.asarray(np.stack([row, row]))
    # top-k 2 cuts at rank 1; mass ahead of rank 2 is ~0.71 + 0.10,
    # past a top-p of 0.8, so the nucleus too cuts after one tied token
    out = np.asarray(_filter(rows, jnp.asarray([2, 0], jnp.int32),
                             jnp.asarray([1.0, 0.8], jnp.float32)))
    for r in out:
        np.testing.assert_array_equal(np.nonzero(r > NEG)[0],
                                      [5, 17, 40, 63])


def _scatter_filter_row(logits, top_k, top_p):
    """The argsort-and-scatter filter the threshold mask replaced."""
    v = logits.shape[-1]
    order = jnp.argsort(-logits)
    ranked = logits[order]
    ranks = jnp.arange(v, dtype=jnp.int32)
    keep = (top_k <= 0) | (ranks < top_k)
    probs = jax.nn.softmax(ranked)
    keep &= (jnp.cumsum(probs) - probs < top_p)
    masked = jnp.where(keep, ranked, NEG)
    return jnp.zeros(v, logits.dtype).at[order].set(masked)


@jax.jit
def _scatter_sample_tokens(logits, index, seeds, temps, top_k, top_p):
    def row(lg, key, temp, k, p):
        greedy_tok = jnp.argmax(lg).astype(jnp.int32)
        filtered = _scatter_filter_row(
            lg.astype(jnp.float32) / jnp.maximum(temp, 1e-6), k, p)
        gumbel = jax.random.gumbel(key, lg.shape, jnp.float32)
        sampled = jnp.argmax(filtered + gumbel).astype(jnp.int32)
        return jnp.where(temp > 0, sampled, greedy_tok)
    keys = jax.vmap(token_key)(seeds, index)
    return jax.vmap(row)(logits, keys, temps, top_k, top_p)


@pytest.mark.parametrize("seed", [0, 1])
def test_sampled_batch_matches_argsort_scatter_filter(seed):
    """A decode batch of 32 at the 151936 vocabulary, greedy rows mixed
    with top-p and top-k rows: the same tokens as the argsort-and-scatter
    filter, for data whose cuts are untied."""
    b, v = 32, 151936
    rng = np.random.default_rng(seed)
    logits = _logit_rows(seed, b, v)[rng.permutation(b)]
    temps = np.where(np.arange(b) % 2 == 1, 0.7, 0.0).astype(np.float32)
    top_k = np.where(np.arange(b) % 4 == 3, 50, 0).astype(np.int32)
    top_p = np.where(np.arange(b) % 8 == 1, 1.0, 0.9).astype(np.float32)
    # precondition: every sampled row's cut value occurs once
    scaled = logits / np.maximum(temps, 1e-6)[:, None]
    out = np.asarray(_filter(jnp.asarray(scaled), jnp.asarray(top_k),
                             jnp.asarray(top_p)))
    for i in np.nonzero(temps)[0]:
        cut = out[i][out[i] > NEG].min()
        assert np.count_nonzero(scaled[i] == cut) == 1, i
    args = (jnp.asarray(logits), jnp.asarray(rng.integers(0, 4096, b),
                                             jnp.int32),
            jnp.asarray(rng.integers(0, 2**31, b), jnp.uint32),
            jnp.asarray(temps), jnp.asarray(top_k), jnp.asarray(top_p))
    np.testing.assert_array_equal(np.asarray(sample_tokens(*args)),
                                  np.asarray(_scatter_sample_tokens(*args)))


def test_prompt_lookup_draft():
    seq = np.array([5, 6, 7, 1, 2, 5, 6, 7, 9, 4, 5, 6, 7], np.int32)
    # suffix 3-gram (5,6,7) last recurred at index 5 -> continuation 9,4
    np.testing.assert_array_equal(prompt_lookup_draft(seq, 2, 3), [9, 4])
    np.testing.assert_array_equal(prompt_lookup_draft(seq, 4, 3),
                                  [9, 4, 5, 6])
    # no recurrence anywhere -> empty draft
    assert prompt_lookup_draft(np.arange(8, dtype=np.int32), 3, 3).size == 0
    # falls back to shorter n-grams when the long one never recurred
    seq2 = np.array([1, 2, 9, 8, 3, 2], np.int32)
    np.testing.assert_array_equal(prompt_lookup_draft(seq2, 2, 3), [9, 8])
    assert prompt_lookup_draft(seq2, 0, 3).size == 0


# ---------------------------------------------- engine stats + termination


def test_stats_split_decode_and_total_rates(bnn_cfg, bnn_params):
    eng = _engine(bnn_cfg, bnn_params)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, bnn_cfg.vocab, 8), 4)
    eng.run()
    st = eng.stats()
    assert "tokens_per_s" not in st           # the mislabeled key is gone
    assert st["decoded_tokens"] == 4 and st["prefill_tokens"] == 8
    # total covers prefill + decode over the same wall clock
    assert st["total_tokens_per_s"] == pytest.approx(
        st["decode_tokens_per_s"] * (4 + 8) / 4)


def test_stop_token_finishes_early_and_releases_blocks(bnn_cfg, bnn_params):
    """Satellite: an emitted stop token must finish the request at that
    step (blocks freed), not keep decoding until max_new."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, bnn_cfg.vocab, 6)
    ref = _engine(bnn_cfg, bnn_params, prefix_cache=False)
    rr = ref.submit(prompt, 8)
    full = ref.run()[rr][len(prompt):]
    stop_tok, stop_at = int(full[3]), 3

    eng = _engine(bnn_cfg, bnn_params, prefix_cache=False)
    rid = eng.submit(prompt, 8, sampling=SamplingParams(stop=(stop_tok,)))
    out = eng.run()[rid]
    req = eng.requests[rid]
    assert req.state == State.FINISHED and req.stopped
    assert len(out) == len(prompt) + stop_at + 1     # ended AT the stop
    np.testing.assert_array_equal(out[len(prompt):], full[:stop_at + 1])
    assert req.blocks == [] and req.slot is None     # state released
    assert eng.cache.attn.allocator.num_used == 0
    # finish landed the same step the stop token was emitted
    fin = next(e for e in eng.scheduler.trace if e["event"] == "finish")
    later = [e for e in eng.scheduler.trace
             if e["step"] > fin["step"] and e["event"] == "decode"]
    assert not later, "engine kept decoding after the stop token"


def test_stop_token_in_prompt_does_not_stop(bnn_cfg, bnn_params):
    """Only GENERATED tokens terminate: a stop id inside the prompt is
    ordinary context."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, bnn_cfg.vocab, 6)
    eng = _engine(bnn_cfg, bnn_params)
    rid = eng.submit(prompt, 4, sampling=SamplingParams(
        stop=(int(prompt[2]),)))
    out = eng.run()[rid]
    req = eng.requests[rid]
    if not req.stopped:                       # generated 4 tokens normally
        assert len(out) == len(prompt) + 4


def test_run_stall_diagnostics_names_reason(bnn_cfg, bnn_params):
    """Satellite: a stalled run() must aggregate per-request stall
    reasons from the trace, not unconditionally blame the block pool."""
    eng = _engine(bnn_cfg, bnn_params, max_tokens_in_flight=4)
    rid = eng.submit(np.zeros(4, np.int32), 4)   # needs 8 tokens in flight
    with pytest.raises(RuntimeError) as ei:
        eng.run()
    msg = str(ei.value)
    assert "token_budget" in msg and f"rid={rid}" in msg
    assert "queued" in msg
    assert eng.scheduler.stall_reasons()[rid] == ("queued", "token_budget")


# ------------------------------------------------- sampling determinism


SAMPLED = SamplingParams(temperature=0.8, top_k=24, top_p=0.95, seed=1234)


def _gen(eng, rid):
    req = eng.requests[rid]
    return eng.run()[rid][req.prompt_len:]


def test_sampled_stream_invariant_to_bucket_size(bnn_cfg, bnn_params):
    """Same seed => same tokens whether the request decodes alone
    (bucket 1) or padded into a larger bucket with neighbours."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, bnn_cfg.vocab, 7)
    solo = _engine(bnn_cfg, bnn_params, max_batch=1)
    want = _gen(solo, solo.submit(prompt, 6, sampling=SAMPLED))

    crowd = _engine(bnn_cfg, bnn_params, max_batch=4)
    rid = crowd.submit(prompt, 6, sampling=SAMPLED)
    for b in range(3):                        # neighbours change buckets
        crowd.submit(rng.integers(0, bnn_cfg.vocab, 5), 4,
                     sampling=SamplingParams(temperature=0.7, seed=77 + b))
    out = crowd.run()
    np.testing.assert_array_equal(
        out[rid][len(prompt):], want)


@pytest.mark.parametrize("policy", [
    "swap", pytest.param("recompute", marks=pytest.mark.slow)])
def test_sampled_stream_survives_forced_preempt(bnn_cfg, bnn_params,
                                                policy):
    """Satellite test: forced preempt/swap cycles replay or restore the
    exact PRNG positions — same seed => same sampled tokens."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, bnn_cfg.vocab, 7) for _ in range(2)]
    calm = _engine(bnn_cfg, bnn_params, max_model_len=16)
    want = [_gen(calm, calm.submit(p, 6, sampling=SamplingParams(
        temperature=0.9, seed=10 + i))) for i, p in enumerate(prompts)]

    eng = _engine(bnn_cfg, bnn_params, max_model_len=16, max_batch=2,
                  preempt_policy=policy)
    rids = [eng.submit(p, 6, sampling=SamplingParams(
        temperature=0.9, seed=10 + i)) for i, p in enumerate(prompts)]
    for _ in range(6):
        eng.step()
    eng.scheduler._preempt_one(eng.step_count, None)
    out = eng.run()
    assert eng.stats()["preemptions"] >= 1
    for rid, w, p in zip(rids, want, prompts):
        np.testing.assert_array_equal(out[rid][len(p):], w)


# ---------------------------------------------------- speculative decode


def _rep_prompt(rng, vocab, unit=3, reps=3):
    """Periodic prompt: its final n-gram recurs, so prompt-lookup
    always has a draft to propose."""
    return np.tile(rng.integers(0, vocab, unit), reps)


def _repeat_next_prompt(cfg, params, rng, horizon=12):
    """A periodic head plus the model's own greedy continuation, cut
    just before the first generated token that repeats an earlier one:
    the first decode step then ends on an n-gram seen before, so
    prompt-lookup proposes a draft whatever the model's outputs are."""
    eng = _engine(cfg, params, max_model_len=64)
    head = _rep_prompt(rng, cfg.vocab, reps=2)
    rid = eng.submit(head, horizon)
    seq = eng.run()[rid]
    cut = next(i for i in range(len(head), len(seq))
               if seq[i] in seq[:i])
    return seq[:cut]


def _spec_vs_plain(cfg, params, sampling=None, gen=8, prompts=None,
                   **ekw):
    rng = np.random.default_rng(6)
    if prompts is None:
        prompts = [_rep_prompt(rng, cfg.vocab) for _ in range(2)]
    plain = _engine(cfg, params, **ekw)
    want = [_gen(plain, plain.submit(p, gen, sampling=sampling))
            for p in prompts]
    spec = _engine(cfg, params, spec_k=3, **ekw)
    rids = [spec.submit(p, gen, sampling=sampling) for p in prompts]
    out = spec.run()
    got = [out[r][len(p):] for r, p in zip(rids, prompts)]
    return spec, want, got


# mla/swa re-test the same engine mechanism over slower stacks: full
# coverage stays in the tier-1 full lane, the fast lane keeps one
# block-family and one slot-family arch
@pytest.mark.parametrize("family", [
    "gqa", "ssm",
    pytest.param("mla", marks=pytest.mark.slow),
    pytest.param("swa", marks=pytest.mark.slow)])
def test_spec_greedy_matches_plain_greedy_per_family(
        family, family_models, bnn_cfg, bnn_params):
    """Acceptance: greedy speculative decode reproduces plain greedy
    EXACTLY for one arch per mixer family, and drafts were actually
    proposed/verified (not a degenerate no-draft run)."""
    cfg, params = (bnn_cfg, bnn_params) if family == "gqa" \
        else family_models[family]
    prompts = None
    if family == "ssm":
        # the periodic prompt's first generated token never recurs in
        # this model's outputs, so nothing would be drafted
        rng = np.random.default_rng(6)
        prompts = [_repeat_next_prompt(cfg, params, rng) for _ in range(2)]
    spec, want, got = _spec_vs_plain(cfg, params, prompts=prompts,
                                     max_model_len=24)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    sp = spec.stats()["speculative"]
    assert sp["enabled"] and sp["spec_steps"] > 0
    assert sp["draft_tokens"] > 0
    assert sp["tokens_per_decode_step"] >= 1.0
    if family == "ssm" and sp["accepted_tokens"] < sp["draft_tokens"]:
        # partial acceptance exercised the snapshot-restore rollback
        assert sp["repairs"] >= 1
    assert np.isfinite(spec.stats()["photonic"]["modeled_spec_speedup"])


def test_spec_sampled_matches_plain_sampled(bnn_cfg, bnn_params):
    """Sampling is a pure function of (seed, position), so speculation
    is exact for ANY temperature, not just greedy."""
    spec, want, got = _spec_vs_plain(bnn_cfg, bnn_params,
                                     sampling=SAMPLED, max_model_len=24)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("family,policy", [
    ("gqa", "swap"), ("ssm", "swap"),
    pytest.param("mla", "swap", marks=pytest.mark.slow),
    pytest.param("swa", "swap", marks=pytest.mark.slow),
    pytest.param("gqa", "recompute", marks=pytest.mark.slow),
    pytest.param("ssm", "recompute", marks=pytest.mark.slow),
])
def test_spec_greedy_survives_forced_preempt_cycle(
        family, policy, family_models, bnn_cfg, bnn_params):
    """Acceptance: greedy spec-decode still matches plain greedy across
    a forced preempt/swap cycle for every mixer family."""
    cfg, params = (bnn_cfg, bnn_params) if family == "gqa" \
        else family_models[family]
    rng = np.random.default_rng(7)
    prompts = [_rep_prompt(rng, cfg.vocab) for _ in range(2)]
    calm = _engine(cfg, params, max_model_len=24)
    want = [_gen(calm, calm.submit(p, 8)) for p in prompts]

    eng = _engine(cfg, params, max_model_len=24, max_batch=2,
                  preempt_policy=policy, spec_k=3)
    rids = [eng.submit(p, 8) for p in prompts]
    for _ in range(5):
        eng.step()
    eng.scheduler._preempt_one(eng.step_count, None)
    out = eng.run()
    assert eng.stats()["preemptions"] >= 1
    for rid, w, p in zip(rids, want, prompts):
        np.testing.assert_array_equal(out[rid][len(p):], w)


def test_spec_rollback_on_ring_tables(bnn_cfg, bnn_params):
    """Partial acceptance on a sliding-window ring: rejected writes
    wrapped into the ring must be masked once lengths rewind — tokens
    match the plain engine through several window wraps."""
    cfg = bnn_cfg.replace(sliding_window=5)
    rng = np.random.default_rng(8)
    prompts = [_rep_prompt(rng, cfg.vocab) for _ in range(2)]
    kw = dict(block_size=2, num_blocks=65, max_batch=2, max_model_len=32)
    plain = _engine(cfg, bnn_params, **kw)
    want = [_gen(plain, plain.submit(p, 14)) for p in prompts]
    spec = _engine(cfg, bnn_params, spec_k=3, **kw)
    rids = [spec.submit(p, 14) for p in prompts]
    out = spec.run()
    blk = spec.stats()["mixer"]["blocks"]
    assert blk["layout"] == "ring" and blk["ring_reuses"] > 0
    assert spec.stats()["speculative"]["draft_tokens"] > 0
    for rid, w, p in zip(rids, want, prompts):
        np.testing.assert_array_equal(out[rid][len(p):], w)


def test_spec_rollback_partial_acceptance_ssm_slots(family_models):
    """SSM slots fold every verified token into their recurrent state;
    partial acceptance must restore the pre-verify snapshot and
    re-advance by the accepted prefix only.  A rejected draft that was
    NOT rolled back would corrupt every later token."""
    cfg, params = family_models["ssm"]
    spec, want, got = _spec_vs_plain(cfg, params, gen=10,
                                     max_model_len=24)
    sp = spec.stats()["speculative"]
    assert sp["draft_tokens"] > 0
    # with random weights some draft is always rejected -> repair ran
    assert sp["accepted_tokens"] < sp["draft_tokens"]
    assert sp["repairs"] >= 1
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("family", ["gqa", "ssm"])
def test_spec_full_acceptance_commits_multiple_tokens(
        family, family_models, bnn_cfg, bnn_params, monkeypatch):
    """With an oracle drafter (returns the true greedy continuation)
    every draft is accepted: each verify step commits k+1 tokens, no
    SSM repair pass ever runs, and the modeled photonic speedup
    exceeds 1x — the end-to-end payoff path."""
    cfg, params = (bnn_cfg, bnn_params) if family == "gqa" \
        else family_models[family]
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 7)
    plain = _engine(cfg, params, max_model_len=24)
    gold = _gen(plain, plain.submit(prompt, 8))

    import repro.serving.engine as E

    def oracle(seq, k, ngram):
        g = len(seq) - len(prompt)            # tokens generated so far
        return np.asarray(gold[g:g + k], np.int32)

    monkeypatch.setattr(E, "prompt_lookup_draft", oracle)
    spec = _engine(cfg, params, spec_k=3, max_model_len=24)
    rid = spec.submit(prompt, 8)
    out = spec.run()[rid]
    np.testing.assert_array_equal(out[len(prompt):], gold)
    sp = spec.stats()["speculative"]
    assert sp["draft_tokens"] > 0
    assert sp["accepted_tokens"] == sp["draft_tokens"]
    assert sp["acceptance_rate"] == 1.0
    assert sp["tokens_per_decode_step"] > 2.0     # k+1-sized commits
    assert sp["repairs"] == 0                     # nothing to roll back
    assert spec.stats()["photonic"]["modeled_spec_speedup"] > 1.0


def test_spec_stop_mid_draft_clamps_acceptance(bnn_cfg, bnn_params,
                                               monkeypatch):
    """Regression: a stop token landing mid-draft truncates the commit
    loop, and the accepted-token counter must follow the COMMITTED
    prefix — the old code added m - 1 before the loop, so acceptance
    (and acceptance_rate) read inflated relative to the tokens the
    stream actually contains."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, bnn_cfg.vocab, 7)
    plain = _engine(bnn_cfg, bnn_params, max_model_len=24)
    gold = _gen(plain, plain.submit(prompt, 8))
    # stop at the first generated token that did not appear earlier in
    # the generation (a repeat would end the run before the draft)
    stop_at = next(i for i in range(1, len(gold))
                   if gold[i] not in gold[:i])
    stop_tok = int(gold[stop_at])

    import repro.serving.engine as E

    def oracle(seq, k, ngram):
        g = len(seq) - len(prompt)
        return np.asarray(gold[g:g + k], np.int32)

    monkeypatch.setattr(E, "prompt_lookup_draft", oracle)
    eng = _engine(bnn_cfg, bnn_params, spec_k=3, max_model_len=24)
    rid = eng.submit(prompt, 8, sampling=SamplingParams(stop=(stop_tok,)))
    out = eng.run()[rid]
    # the run ends AT the stop token, tokens identical to plain greedy
    np.testing.assert_array_equal(out[len(prompt):], gold[:stop_at + 1])
    sp = eng.stats()["speculative"]
    assert sp["draft_tokens"] > 0
    # committed draft tokens: everything after the prefill-produced
    # first token up to and including the stop, minus verifier bonus
    # tokens (one per FULLY-committed verify step)
    spec_events = [e for e in eng.scheduler.trace
                   if e["event"] == "spec_decode"]
    committed = sum(e["committed"] for e in spec_events)
    full_steps = len(spec_events) - 1        # last step stopped mid-draft
    assert sp["accepted_tokens"] == committed - full_steps
    assert sp["acceptance_rate"] <= 1.0
    assert sp["acceptance_rate"] == pytest.approx(
        sp["accepted_tokens"] / sp["draft_tokens"])
    # the old accounting would have credited the full accepted prefix
    assert sp["accepted_tokens"] < sp["draft_tokens"]


def test_scheduler_budget_charges_speculative_rows(bnn_cfg):
    """max_batched_tokens must account for verify width: a decode row
    in a speculative engine burns up to spec_k+1 compute tokens per
    step, so the prefill chunk shrinks accordingly."""
    from repro.serving import BlockKVCache, Scheduler, SchedulerConfig
    from repro.serving.request import Request, State
    cache = BlockKVCache(bnn_cfg, num_blocks=64, block_size=4,
                         max_model_len=32)
    sched = Scheduler(SchedulerConfig(max_batch=4, prefill_chunk=16,
                                      max_batched_tokens=12,
                                      decode_cost=4), cache)
    sched.submit(Request(0, np.zeros(20, np.int32), 4), step=0)
    assert sched.schedule(0).prefill_tokens == 12   # no decode rows yet
    sched.running[0].state = State.DECODE
    sched.submit(Request(1, np.zeros(20, np.int32), 4), step=1)
    plan = sched.schedule(1)
    assert len(plan.decode) == 1
    assert plan.prefill_tokens == 12 - 4            # 1 row x spec width


def test_engine_wires_decode_cost_from_spec_k(bnn_cfg, bnn_params):
    assert _engine(bnn_cfg, bnn_params).scheduler.cfg.decode_cost == 1
    assert _engine(bnn_cfg, bnn_params,
                   spec_k=3).scheduler.cfg.decode_cost == 4


@pytest.mark.slow
def test_spec_greedy_matches_plain_greedy_hybrid_jamba(jamba_models):
    """Hybrid stacks (jamba: SSD slots + periodic paged attention)
    speculate too: the repair pass restores slot layers while block
    layers rewind — one verify step drives both rollbacks."""
    cfg, params = jamba_models
    spec, want, got = _spec_vs_plain(cfg, params, max_model_len=24)
    assert spec.cache.ssm is not None and spec.cache.attn is not None
    assert spec.stats()["speculative"]["draft_tokens"] > 0
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


def test_spec_respects_max_new_budget(bnn_cfg, bnn_params):
    """A draft never runs generation past max_new (the cache footprint
    of pos + k + 1 stays inside the admitted budget)."""
    rng = np.random.default_rng(9)
    prompt = _rep_prompt(rng, bnn_cfg.vocab)
    eng = _engine(bnn_cfg, bnn_params, spec_k=3, max_model_len=16)
    rid = eng.submit(prompt, 7)               # 9 + 7 == max_model_len
    out = eng.run()[rid]
    assert out.shape == (16,)
    assert len(eng.requests[rid].out) == 7
