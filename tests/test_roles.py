"""Disaggregated prefill/decode worker-role tests.

The role layer (serving/roles.py) splits a ShardedEngine topology into
dedicated prefill workers — chunked prefill only, finished prompts
stream to a decode shard over the swap-to-peer plane — and decode
workers that never see a fresh prompt while a prefill shard lives.
The acceptance bar everywhere is TOKEN IDENTITY against the mixed
oracle: sampling keys are pure functions of (seed, position), so the
handoff must be bit-exact for every mixer-state family, under
speculative decoding, and across a killed prefill shard.

Also covered: role parsing/validation, the division of labor (prefill
shards never batch decode rows, decode shards never prefill), the
transfer-aware admission defer (reason=transfer_pending at a slow
modeled link), and the v3 handoff spans + replay transfer term.
"""
import numpy as np
import pytest

from repro.serving import (DECODE, MIXED, PREFILL, Engine, EngineConfig,
                           ShardedEngine, State, get_role, parse_roles,
                           read_trace, replay_trace, validate_roles,
                           validate_trace)

# bnn_cfg / bnn_params / family_models / jamba_models: tests/conftest.py

EKW = dict(block_size=4, num_blocks=33, max_batch=4, prefill_chunk=4,
           max_model_len=32)


def _sharded(cfg, params, n_shards, roles=None, **kw):
    d = dict(EKW)
    d.update(kw)
    return ShardedEngine(params, cfg, EngineConfig(**d), n_shards,
                         roles=roles)


def _reference(cfg, params, prompts, max_news, **kw):
    """Single mixed Engine: the token-identity oracle."""
    d = dict(EKW)
    d.update(kw)
    eng = Engine(params, cfg, EngineConfig(**d))
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    out = eng.run()
    return [out[r] for r in rids]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _assert_division_of_labor(se):
    """Prefill shards only prefill (their decoded tokens are exactly
    the first tokens that fall out of prompt completion); decode
    shards never run a prefill chunk."""
    st = se.stats()
    for row in st["per_shard"]:
        if row["role"] == "prefill":
            assert row["prefill_tokens"] > 0
        elif row["role"] == "decode":
            assert row["prefill_tokens"] == 0
    assert st["handoff"]["handoffs"] > 0
    assert st["handoff"]["handoff_bytes"] > 0
    return st


# ------------------------------------------------------------- parsing

def test_parse_roles_counts_and_names():
    assert parse_roles("1:2", 3) == ["prefill", "decode", "decode"]
    assert parse_roles("2:2", 4) == ["prefill", "prefill",
                                     "decode", "decode"]
    assert parse_roles("prefill,decode,mixed", 3) == \
        ["prefill", "decode", "mixed"]
    with pytest.raises(ValueError):
        parse_roles("1:1", 3)                     # count mismatch
    with pytest.raises(ValueError):
        parse_roles("prefill,bogus", 2)           # unknown role name
    with pytest.raises(ValueError):
        validate_roles(["prefill", "prefill"])    # nobody can decode
    with pytest.raises(ValueError):
        _ = get_role("bogus")


def test_role_flags():
    assert MIXED.runs_decode and not MIXED.hands_off
    assert PREFILL.hands_off and not PREFILL.runs_decode
    assert DECODE.runs_decode and not DECODE.hands_off
    assert get_role("mixed") is MIXED


def test_all_prefill_topology_rejected(bnn_cfg, bnn_params):
    with pytest.raises(ValueError):
        _sharded(bnn_cfg, bnn_params, 2, roles="2:0")


# ------------------------------------------------- token-identity oracle

def test_disaggregated_matches_single_engine(bnn_cfg, bnn_params):
    """1 prefill + 2 decode produces the mixed oracle's tokens exactly,
    with the labor split by role and every request handed off once."""
    prompts = _prompts(bnn_cfg, [4, 7, 8, 5, 4], seed=3)
    max_news = [8, 6, 8, 4, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, 3, roles="1:2")
    assert se.roles == ["prefill", "decode", "decode"]
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    # fresh prompts land on the prefill shard while it lives
    assert all(se.shard_of[r] == 0 for r in rids)
    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    st = _assert_division_of_labor(se)
    assert st["handoff"]["handoffs"] == len(rids)
    # finished requests ended up owned by decode shards
    assert all(se.shard_of[r] in (1, 2) for r in rids)


@pytest.mark.parametrize("family", ["ssm", "mla", "swa"])
def test_disaggregated_families(family_models, family):
    """The handoff is bit-exact for every mixer-state layout: recurrent
    SSM slots, paged MLA latents, and sliding-window ring buffers all
    cross the peer-swap plane losslessly."""
    cfg, params = family_models[family]
    prompts = _prompts(cfg, [4, 8, 6, 5], seed=21)
    max_news = [8, 6, 8, 8]
    want = _reference(cfg, params, prompts, max_news)

    se = _sharded(cfg, params, 3, roles="1:2")
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    _assert_division_of_labor(se)


def test_disaggregated_jamba_hybrid(jamba_models):
    """Hybrid stacks hand off BOTH families per request (SSD slots and
    paged KV) and stay token-identical."""
    cfg, params = jamba_models
    prompts = _prompts(cfg, [4, 8, 6], seed=29)
    max_news = [8, 6, 8]
    want = _reference(cfg, params, prompts, max_news)

    se = _sharded(cfg, params, 3, roles="1:2")
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    _assert_division_of_labor(se)


def test_disaggregated_spec_decoding(bnn_cfg, bnn_params):
    """Speculative decoding runs only on decode shards (a prefill
    worker compiles no verify graph) and the tokens still match a
    mixed spec engine exactly."""
    prompts = _prompts(bnn_cfg, [8, 4, 8, 6], seed=31)
    max_news = [12, 8, 8, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news, spec_k=3)

    se = _sharded(bnn_cfg, bnn_params, 3, roles="1:2", spec_k=3)
    assert se.engines[0]._spec_k == 0             # prefill never drafts
    assert se.engines[1]._spec_k == 3
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    _assert_division_of_labor(se)
    assert sum(e._draft_tokens for e in se.engines[1:]) > 0
    assert se.engines[0]._draft_tokens == 0


# --------------------------------------------------------------- fault

def test_kill_prefill_shard_requeues_on_survivors(bnn_cfg, bnn_params):
    """A dead prefill shard degrades, never corrupts: in-flight prompts
    requeue on the surviving decode shards (recompute-from-scratch),
    tokens stay identical, and fresh submissions fall back to the
    decode-capable survivors."""
    prompts = _prompts(bnn_cfg, [8, 8, 8, 8], seed=37)
    max_news = [8, 8, 8, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, 3, roles="1:2")
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    se.step()                         # prompts mid-prefill on shard 0
    doomed = [r for r in rids if se.shard_of[r] == 0]
    assert doomed
    se.kill_shard(0)
    assert se.alive == [1, 2]
    assert all(se.shard_of[r] in (1, 2) for r in rids)
    # with no prefill worker left the survivors prefill their own
    late = se.submit(prompts[0], 4)
    assert se.shard_of[late] in (1, 2)

    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    assert len(out[late]) == len(prompts[0]) + 4
    st = se.stats()
    assert st["requeued_lost"] >= len(doomed)
    # decode shards prefilled (rescue + the late request), by necessity
    assert sum(p["prefill_tokens"] for p in st["per_shard"][1:]) > 0


def test_kill_all_decode_shards_refuses(bnn_cfg, bnn_params):
    se = _sharded(bnn_cfg, bnn_params, 2, roles="1:1")
    with pytest.raises(RuntimeError):
        se.kill_shard(1)              # would leave only a prefill shard


# ----------------------------------------- transfer-aware admission

def test_transfer_pending_defers_admission(bnn_cfg, bnn_params):
    """At a slow modeled link the destination scheduler parks the
    arriving request with the distinct transfer_pending reason —
    overlapping the modeled stream with its decode steps — and
    releases it at the deadline with tokens unchanged."""
    prompts = _prompts(bnn_cfg, [8, 4], seed=41)
    max_news = [8, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, 2, roles="1:1", link_gbps=1e-6)
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    # step until a handoff armed a transfer deadline, then catch the
    # destination deferring it while the modeled link streams
    seen_pending = False
    for _ in range(600):
        se.step()
        stalls = se.stall_reasons()
        if any(stalls.get(r, (None, None))[1] == "transfer_pending"
               for r in rids):
            seen_pending = True
            break
    assert seen_pending
    pending = [r for r in rids if se.requests[r].transfer_until_step]
    assert pending and all(se.requests[r].transfer_steps > 1
                           for r in pending)

    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    dst = se.engines[1]
    defers = [e for e in dst.scheduler.trace if e["event"] == "defer"
              and e.get("reason") == "transfer_pending"]
    assert defers and all("until_step" in e for e in defers)


def test_transfer_overlap_model(bnn_cfg, bnn_params):
    """The modeled transfer term is well-behaved: latency is the exact
    link formula, the overlap step count is never free (>= 1), grows
    monotonically with payload size, and clamps at 256 steps so a slow
    link cannot park a request forever.  A 1:1 topology drains to
    completion with every prompt handed off exactly once."""
    se = _sharded(bnn_cfg, bnn_params, 2, roles="1:1")
    rids = [se.submit(p, 8) for p in _prompts(bnn_cfg, [8, 4], seed=43)]
    out = se.run()
    assert len(out) == 2
    assert se.handoffs == len(rids) and se.handoff_bytes > 0
    cm = se.engines[1].cost_model
    assert cm.transfer_latency_s(8 << 10) == pytest.approx(
        (8 << 10) * 8 / (100.0 * 1e9))
    steps = [cm.transfer_steps_overlap(n)
             for n in (1, 1 << 10, 8 << 10, 1 << 20, 1 << 30)]
    assert all(s >= 1 for s in steps)         # a handoff is never free
    assert steps == sorted(steps)             # monotone in bytes
    assert cm.transfer_steps_overlap(1 << 40) == 256   # hard clamp


# ------------------------------------- v3 handoff spans + replay term

def test_handoff_spans_and_replay_transfer_term(bnn_cfg, bnn_params,
                                                tmp_path):
    se = _sharded(bnn_cfg, bnn_params, 3, roles="1:2")
    prefix = str(tmp_path / "trace")
    se.start_trace(prefix)
    rids = [se.submit(p, 6) for p in _prompts(bnn_cfg, [4, 8], seed=47)]
    se.run()
    se.stop_trace()
    assert len(rids) == 2

    all_records = {i: read_trace(f"{prefix}.shard{i}.jsonl")
                   for i in range(3)}
    out_spans, in_spans = [], []
    for i, records in all_records.items():
        validate_trace(records)
        meta = records[0]
        assert meta["schema"] == 5
        assert meta["role"] == se.roles[i]
        assert meta["link_gbps"] == 100.0
        assert "t0" in meta
        for r in records:
            if r["type"] == "step":
                assert r["role"] == se.roles[i]
            elif r["type"] == "span" and r["name"] == "handoff_out":
                out_spans.append(r)
            elif r["type"] == "span" and r["name"] == "handoff_in":
                in_spans.append(r)
    # every handoff leaves a paired, byte-counted span on each side
    assert {s["handoff_id"] for s in out_spans} == \
        {s["handoff_id"] for s in in_spans}
    assert len(out_spans) == se.handoffs
    assert all(s["bytes"] > 0 for s in in_spans)
    assert all("transfer_s" in s for s in in_spans)

    # the replay report prices the link: decode shards report bytes in
    # and a transfer term; the prefill shard only streams out
    rep0 = replay_trace(f"{prefix}.shard0.jsonl", cfg=bnn_cfg)
    assert rep0["role"] == "prefill"
    assert rep0["handoff"]["handoffs_out"] == se.handoffs
    assert rep0["handoff"]["bytes_in"] == 0
    got_in = 0
    for i in (1, 2):
        rep = replay_trace(f"{prefix}.shard{i}.jsonl", cfg=bnn_cfg)
        assert rep["role"] == "decode"
        ho = rep["handoff"]
        got_in += ho["handoffs_in"]
        if ho["handoffs_in"]:
            assert ho["bytes_in"] > 0
            assert ho["modeled_transfer_s"] > 0
            assert ho["exposed_transfer_s"] >= 0
            assert rep["simulated_s_with_transfer"] >= rep["simulated_s"]
    assert got_in == se.handoffs


# --------------------------- terminal requests parked in handoff_ready

def test_cancel_while_parked_in_handoff_never_exports(bnn_cfg, bnn_params):
    """Regression: a request cancelled while parked in a prefill
    shard's ``handoff_ready`` must be dropped, not exported — the old
    drain loop would hand the dead request to a decode peer (and, on an
    otherwise-idle prefill shard, never drop it at all)."""
    # prefix_cache off so the pool-empty assertion below is exact (the
    # index would otherwise keep released prompt blocks resident)
    se = _sharded(bnn_cfg, bnn_params, 2, roles="prefill,decode",
                  prefix_cache=False)
    rid = se.submit(_prompts(bnn_cfg, [8], seed=51)[0], 8)
    live = se.submit(_prompts(bnn_cfg, [8], seed=52)[0], 8)
    # step ONLY the prefill shard so the sharded drain never runs: the
    # completed prefill parks awaiting export
    with se._on_shard(0) as p:
        for _ in range(30):
            if rid in p.handoff_ready and live in p.handoff_ready:
                break
            p.step()
    assert rid in p.handoff_ready
    assert se.cancel(rid)                    # engine drops it from the queue
    assert rid not in p.handoff_ready
    assert se.requests[rid].state is State.CANCELLED
    out = se.run()                           # the live request still flows
    assert rid not in out and live in out
    assert rid not in se.engines[1].requests     # never reached the peer
    assert se.handoffs == 1                      # only the live handoff
    assert se.engines[0].cache.attn.allocator.num_used == 0


def test_terminal_parked_request_dropped_by_idle_shard_drain(bnn_cfg,
                                                             bnn_params):
    """Second line of defense: if a parked request somehow reaches a
    terminal state while still listed in ``handoff_ready`` (bypassing
    ``Engine.cancel``'s own removal), the sharded drain discards it —
    even when the prefill shard is otherwise idle, which the old
    ``step()`` skipped entirely."""
    se = _sharded(bnn_cfg, bnn_params, 2, roles="prefill,decode")
    rid = se.submit(_prompts(bnn_cfg, [8], seed=53)[0], 8)
    with se._on_shard(0) as p:
        for _ in range(30):
            if rid in p.handoff_ready:
                break
            p.step()
    assert rid in p.handoff_ready
    req = p.requests[rid]
    p.cache.release(req)
    p.scheduler.running.remove(req)
    req.state = State.CANCELLED              # terminal, still parked
    assert p.scheduler.idle                  # shard has nothing else
    se.step()                                # drain runs despite idleness
    assert rid not in p.handoff_ready
    assert rid not in se.engines[1].requests
    assert se.handoffs == 0
