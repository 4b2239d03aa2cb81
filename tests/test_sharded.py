"""Data-axis sharded decode tests: shard mesh construction, placement
balance, token-identical differentials at 2 and 4 shards, swap-to-peer
migration (including content-hash re-adoption of prefixes the
destination already holds), shard-loss rescue surfacing ``swap_lost``,
the replay-curve verify-chunk cap (spec_chunk_cap), schema-v3 per-shard
trace fields, and heartbeat-driven reaping.  Disaggregated prefill/
decode role topologies are covered in tests/test_roles.py.

All tests run on a single physical device: ``shard_meshes`` tiles the
device list round-robin, so every shard still owns a distinct Mesh and
Engine (distinct pools, jit caches, indexes) — the same isolation the
``xla_force_host_platform_device_count`` CI smoke exercises with real
separate devices.
"""
import jax
import numpy as np
import pytest

from repro.dist import sharding as S
from repro.serving import (Engine, EngineConfig, ShardedEngine, State,
                           TRACE_SCHEMA_VERSION, read_trace,
                           spec_chunk_cap, validate_trace)

# bnn_cfg / bnn_params come from tests/conftest.py

EKW = dict(block_size=4, num_blocks=33, max_batch=4, prefill_chunk=4,
           max_model_len=32)


def _sharded(cfg, params, n_shards, **kw):
    d = dict(EKW)
    d.update(kw)
    return ShardedEngine(params, cfg, EngineConfig(**d), n_shards)


def _reference(cfg, params, prompts, max_news, **kw):
    """Single plain Engine run: ground truth for token identity."""
    d = dict(EKW)
    d.update(kw)
    eng = Engine(params, cfg, EngineConfig(**d))
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    out = eng.run()
    return [out[r] for r in rids]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


# ------------------------------------------------------------- meshes

def test_shard_meshes_round_robin_single_device():
    meshes = S.shard_meshes(4)
    assert len(meshes) == 4
    devs = jax.devices()
    for i, m in enumerate(meshes):
        assert m.devices.flat[0] == devs[i % len(devs)]  # round-robin
    for m in meshes:
        assert m.axis_names == ("data", "model")
        assert m.devices.shape == (1, 1)         # one primary per shard


def test_shard_meshes_rejects_zero():
    with pytest.raises(ValueError):
        S.shard_meshes(0)


# ---------------------------------------------------------- placement

def test_placement_balances_committed_tokens(bnn_cfg, bnn_params):
    se = _sharded(bnn_cfg, bnn_params, 2)
    prompts = _prompts(bnn_cfg, [4, 4, 4, 8])
    rids = [se.submit(p, 8) for p in prompts]
    # least-loaded wins, index breaks ties: 0, 1, 0 (tie), 1
    assert [se.shard_of[r] for r in rids[:2]] == [0, 1]
    assert abs(se.shard_load(0) - se.shard_load(1)) <= 16
    with pytest.raises(ValueError):
        se.submit(prompts[0], 4, shard=7)         # not a live shard


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_matches_single_engine(bnn_cfg, bnn_params, n_shards):
    """Acceptance differential: the sharded engine produces
    token-identical output to one plain Engine at 2 and 4 shards —
    placement, per-shard batching, and padding never leak into
    tokens (sampling keys are pure functions of (seed, position))."""
    prompts = _prompts(bnn_cfg, [4, 7, 8, 5, 4], seed=3)
    max_news = [8, 6, 8, 4, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, n_shards)
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    out = se.run()
    assert len(out) == len(rids)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    st = se.stats()
    assert st["finished"] == len(rids)
    assert st["n_shards"] == n_shards
    assert len(st["per_shard"]) == n_shards
    assert st["decoded_tokens"] == sum(
        p["decoded_tokens"] for p in st["per_shard"])
    # more than one shard actually decoded (placement spread the load)
    assert sum(1 for p in st["per_shard"] if p["decoded_tokens"]) >= 2


# ---------------------------------------------------------- migration

def test_migrate_mid_decode_token_identical(bnn_cfg, bnn_params):
    prompts = _prompts(bnn_cfg, [4, 8, 4, 8], seed=5)
    max_news = [12, 8, 8, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, 2)
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    for _ in range(5):
        se.step()
    victim = rids[0]
    src = se.shard_of[victim]
    assert se.requests[victim].state == State.DECODE
    dst = se.migrate(victim)
    assert dst != src and se.shard_of[victim] == dst
    assert se.migrations == 1

    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    src_ev = [e["event"] for e in se.engines[src].scheduler.trace]
    dst_ev = [e["event"] for e in se.engines[dst].scheduler.trace]
    assert "migrate_out" in src_ev and "migrate_in" in dst_ev


def test_migrate_peer_readopts_shared_prefix(bnn_cfg, bnn_params):
    """Swap-to-peer serializes against the DESTINATION's prefix index:
    blocks the destination already holds by content hash never cross
    shards — the source records a re-adoption depth and the
    destination's ordinary swap_in adopts the head locally."""
    prompt = _prompts(bnn_cfg, [8], seed=7)[0]    # 2 full blocks
    se = _sharded(bnn_cfg, bnn_params, 2)
    ra = se.submit(prompt, 8, shard=0)
    rb = se.submit(prompt.copy(), 8, shard=1)     # same hash chain on 1
    while (se.requests[ra].state != State.DECODE
           or se.requests[rb].state != State.DECODE):
        se.step()
    se.migrate(ra, 1)
    req = se.requests[ra]
    assert req.swap_readopt >= 1        # head resolved against the peer
    before = se.engines[1].cache.attn.readopted_blocks
    out = se.run()
    assert se.engines[1].cache.attn.readopted_blocks > before
    want = _reference(bnn_cfg, bnn_params, [prompt], [8])
    np.testing.assert_array_equal(out[ra], want[0])
    np.testing.assert_array_equal(out[rb], want[0])


def test_migrate_with_spec_draft_in_flight(bnn_cfg, bnn_params):
    """Migrating a request mid-speculation: the victim's latest verify
    step wrote draft tokens optimistically past its committed position
    and rolled the rejected suffix back, so export must serialize the
    pos-consistent state only.  The destination resumes drafting and
    the tokens match the no-migration spec oracle exactly."""
    prompts = _prompts(bnn_cfg, [8, 4, 8], seed=23)
    max_news = [12, 8, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news, spec_k=3)

    se = _sharded(bnn_cfg, bnn_params, 2, spec_k=3)
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    victim = rids[0]
    # step until the victim is mid-decode AND its shard has actually
    # drafted: prompt-lookup returns empty until the sequence grows a
    # repeated n-gram, and the engine falls back to plain decode steps
    # (no draft counters) on empty-draft rounds
    req = se.requests[victim]
    while not (req.state == State.DECODE and len(req.out) > 1
               and se.engines[se.shard_of[victim]]._draft_tokens > 0):
        assert not req.done
        se.step()
    src = se.shard_of[victim]
    assert se.engines[src]._draft_tokens > 0      # drafts actually flew
    dst = se.migrate(victim)
    assert dst != src and se.shard_of[victim] == dst

    out = se.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    # speculation continued on the destination after adoption
    assert se.engines[dst]._spec_rows > 0


def test_rebalance_moves_queued_only(bnn_cfg, bnn_params):
    se = _sharded(bnn_cfg, bnn_params, 2)
    prompts = _prompts(bnn_cfg, [4, 4, 4], seed=9)
    rids = [se.submit(p, 8, shard=0) for p in prompts]   # pile on shard 0
    assert se.shard_load(1) == 0
    moved = se.rebalance()
    assert moved == 1 and se.migrations == 1
    # the youngest queued request moved; no device state crossed shards
    assert se.shard_of[rids[-1]] == 1
    assert [se.shard_of[r] for r in rids[:2]] == [0, 0]
    out = se.run()
    assert len(out) == 3


# -------------------------------------------------------------- fault

def test_kill_shard_rescues_token_identically(bnn_cfg, bnn_params):
    """A lost decode shard degrades to swap_lost-style recompute
    requeue: every in-flight request finishes token-identically on a
    survivor, and the loss is visible in stall_reasons() and traces."""
    prompts = _prompts(bnn_cfg, [4, 8, 4, 8], seed=11)
    max_news = [8, 8, 12, 8]
    want = _reference(bnn_cfg, bnn_params, prompts, max_news)

    se = _sharded(bnn_cfg, bnn_params, 2)
    se.start_trace()                              # ring-buffer traces
    rids = [se.submit(p, m) for p, m in zip(prompts, max_news)]
    for _ in range(4):
        se.step()
    doomed = [r for r in rids if se.shard_of[r] == 0]
    assert doomed and any(se.requests[r].state != State.QUEUED
                          for r in doomed)
    se.kill_shard(0)

    assert se.alive == [1]
    assert all(se.shard_of[r] == 1 for r in rids)
    stalls = se.stall_reasons()
    lost_rids = [r for r in doomed
                 if se.requests[r].state == State.QUEUED
                 and se.requests[r].preemptions]
    assert any(stalls.get(r, (None, None))[1] == "swap_lost"
               for r in doomed)
    with pytest.raises(ValueError):
        se.kill_shard(0)                          # already dead

    out = se.run()
    assert len(out) == len(rids)                  # nothing dropped
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)
    st = se.stats()
    assert st["requeued_lost"] >= 1
    surv = st["per_shard"][1]
    assert surv["swap_losts"] >= 1
    # the loss reached the survivor's trace stream too
    ev = se.engines[1].tracer.events()
    assert any(r.get("event") == "swap_lost"
               and r.get("reason") == "shard_lost" for r in ev)
    se.stop_trace()
    assert lost_rids == [] or st["requeued_lost"] >= len(lost_rids)


def test_kill_last_shard_refuses(bnn_cfg, bnn_params):
    se = _sharded(bnn_cfg, bnn_params, 2)
    se.kill_shard(1)
    with pytest.raises(RuntimeError):
        se.kill_shard(0)                          # nothing to rescue onto


def test_heartbeat_reap_kills_silent_shard(bnn_cfg, bnn_params):
    se = ShardedEngine(bnn_params, bnn_cfg, EngineConfig(**EKW), 2,
                       dead_after=5.0)
    prompts = _prompts(bnn_cfg, [4, 4], seed=13)
    rids = [se.submit(p, 6, shard=i) for i, p in enumerate(prompts)]
    se.step()                                     # both shards beat
    now = se.monitor._last_beat[1]
    se.monitor.beat(1, now - 10.0)                # shard 1 goes silent
    assert se.reap(now) == [1]
    assert se.alive == [0] and se.shard_of[rids[1]] == 0
    out = se.run()
    assert len(out) == 2                          # rescued and finished


# ----------------------------------------- replay-curve verify capping

def _curve(points):
    return {str(b): {"step_latency_s": t} for b, t in points}


def test_spec_chunk_cap_breakeven():
    # shallow marginals: every added token cheaper than a solo step
    assert spec_chunk_cap(_curve([(1, 1.0), (2, 1.1), (4, 1.3),
                                  (8, 1.7)])) == 8
    # steep past 2: marginal (4.0-1.5)/2 >= 1.0 stops the walk
    assert spec_chunk_cap(_curve([(1, 1.0), (2, 1.5), (4, 4.0)])) == 2
    # a smaller break-even always yields a smaller (or equal) cap
    assert spec_chunk_cap(_curve([(1, 1.0), (2, 1.5), (4, 4.0)])) \
        < spec_chunk_cap(_curve([(1, 1.0), (2, 1.1), (4, 1.3)]))
    # no batch-1 anchor -> no cap
    assert spec_chunk_cap(_curve([(2, 1.0), (4, 2.0)])) is None
    assert spec_chunk_cap({}) is None


def test_apply_replay_curve_shrinks_spec_chunk(bnn_cfg, bnn_params):
    """Satellite: the scheduler consults the replayed cost curve — a
    smaller modeled break-even shrinks the chosen speculative verify
    chunk AND the per-row decode budget charge; a generous curve never
    raises it back."""
    eng = Engine(bnn_params, bnn_cfg,
                 EngineConfig(**{**EKW, "spec_k": 3}))
    assert eng._spec_k == 3 and eng.scheduler.decode_cost == 4
    k = eng.apply_replay_curve(_curve([(1, 1.0), (2, 1.5), (4, 4.0)]))
    assert k == eng._spec_k == 1                  # cap 2 -> draft 1
    assert eng.scheduler.decode_cost == 2
    eng.apply_replay_curve(_curve([(1, 1.0), (2, 1.05), (8, 1.2)]))
    assert eng._spec_k == 1                       # never raised

    # still produces correct tokens after the cap tightens mid-flight
    prompts = _prompts(bnn_cfg, [4, 8], seed=17)
    want = _reference(bnn_cfg, bnn_params, prompts, [8, 8])
    eng2 = Engine(bnn_params, bnn_cfg,
                  EngineConfig(**{**EKW, "spec_k": 3}))
    rids = [eng2.submit(p, 8) for p in prompts]
    for _ in range(3):
        eng2.step()
    eng2.apply_replay_curve(_curve([(1, 1.0), (2, 1.5), (4, 4.0)]))
    out = eng2.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid], w)


def test_sharded_apply_replay_curve_propagates(bnn_cfg, bnn_params):
    se = _sharded(bnn_cfg, bnn_params, 2, spec_k=3)
    k = se.apply_replay_curve(_curve([(1, 1.0), (2, 1.5), (4, 4.0)]))
    assert k == 1
    for eng in se.engines:
        assert eng._spec_k == 1 and eng.scheduler.decode_cost == 2


# ----------------------------------------------------- per-shard traces

def test_trace_schema_per_shard_fields(bnn_cfg, bnn_params, tmp_path):
    se = _sharded(bnn_cfg, bnn_params, 2)
    prefix = str(tmp_path / "trace")
    se.start_trace(prefix)
    rids = [se.submit(p, 6) for p in _prompts(bnn_cfg, [4, 4], seed=19)]
    se.run()
    se.stop_trace()
    assert TRACE_SCHEMA_VERSION == 5
    for i in range(2):
        records = read_trace(f"{prefix}.shard{i}.jsonl")
        validate_trace(records)
        meta = records[0]
        assert meta["schema"] == 5
        assert meta["shard"] == i and meta["n_shards"] == 2
        # v3: worker role + clock anchor in meta, role on every step
        assert meta["role"] == "mixed" and "t0" in meta
        steps = [r for r in records if r["type"] == "step"]
        assert steps and all(r["shard"] == i for r in steps)
        assert all(r["role"] == "mixed" for r in steps)
    assert len(rids) == 2
