"""The ssm family's cell on the CPU, at its tiny widths: faults planted in
the recurrent-slot path read ``correct`` false; the bursty arrival
process and the family's work counts against hand-worked numbers."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import costs
import traffic_gen

CELL = "mamba2-bnn.chat-burst"


def _slot_of_another_row(eng):
    """Decode rows 0 and 1 read and write each other's slot."""
    dec = eng._decode_fn

    def f(params, pools, tokens, table, lengths, active, slots, *a):
        crossed = slots.at[0].set(slots[1]).at[1].set(slots[0])
        slots = jnp.where(active.sum() >= 2, crossed, slots)
        return dec(params, pools, tokens, table, lengths, active, slots, *a)
    eng._decode_fn = f


def _state_dropped_between_chunks(eng):
    """A prompt's later chunks start from a zero state, not from the
    state its earlier chunks left in the slot."""
    pre = eng._prefill_fn

    def f(params, pools, tokens, table, lengths, n_valid, slots, *a):
        dst = jnp.where(lengths > 0, slots, 0)
        pools = [{k: v.at[dst].set(0.0) for k, v in p.items()}
                 for p in pools]
        return pre(params, pools, tokens, table, lengths, n_valid, slots,
                   *a)
    eng._prefill_fn = f


def _swap_in_without_restore(eng):
    """Preempt by swap every few steps; the swap-in gives the request a
    fresh (zeroed) slot and skips the restore of its parked state."""
    ssm = eng.cache.ssm

    def swap_in(req):
        if not ssm._alloc_slot(req, zero=True):
            return False
        req.host_state, req.snap_readopt = None, False
        return True
    ssm.swap_in = swap_in
    step = eng.step

    def stepping():
        step()
        if eng.step_count % 4 == 0 and eng.scheduler.running:
            eng.scheduler._preempt_one(eng.step_count, None)
    eng.step = stepping


@pytest.mark.parametrize("fault", [_slot_of_another_row,
                                   _state_dropped_between_chunks,
                                   _swap_in_without_restore],
                         ids=["slot_of_another_row", "state_dropped",
                              "swap_in_unrestored"])
def test_slot_fault_is_not_correct(fault):
    res = bench_tiny.serve(bench_tiny.cell(CELL), fault=fault)
    assert res["check"]["served_tokens"]["value"] >= 64
    assert res["correct"] is False


def test_bursts_hold_the_mean_rate():
    with open(os.path.join(bench_tiny.BENCH, "traffic",
                           "chat-burst.json")) as f:
        spec = json.load(f)["arrivals"]
    horizon = 600.0
    for seed in (1, 2 ** 33 + 7):
        t = traffic_gen.arrival_times(spec, 3.0, horizon,
                                      np.random.default_rng(seed))
        assert len(t) == 1800 and (np.diff(t) >= 0).all()
        assert 0 <= t[0] and t[-1] < horizon
        # per cycle of six seconds, the two busiest seconds are the burst
        # (2.5x the mean rate), the other four the quiet phase (0.25x)
        per_s = np.histogram(t, bins=np.arange(0, horizon + 1))[0]
        cycles = np.sort(per_s.reshape(-1, 6), axis=1)
        assert cycles[:, -2:].mean() > 4 * cycles[:, :4].mean()
    a = traffic_gen.arrival_times(spec, 3.0, 60.0, np.random.default_rng(1))
    b = traffic_gen.arrival_times(spec, 3.0, 60.0, np.random.default_rng(2))
    assert len(a) == len(b) and not np.allclose(a, b)


CONF = bench_tiny.run.load_cell(CELL).config


def test_ssm_work_counts_by_hand():
    # in_proj 2048 -> 8512 (z 4096, x 4096, B 128, C 128, dt 64),
    # out_proj 4096 -> 2048
    assert costs.projections(CONF) == [(2048, 8512), (4096, 2048)]
    per_layer = 2048 * 8512 + 4096 * 2048
    assert costs.gemms(CONF, 1).ops == 2 * per_layer * 48
    # 16 decode rows: 5 operations per state entry and token; each slot's
    # state (64 x 128 x 64 + conv tail 3 x 4352 float32) read and written
    w = costs.mixer(CONF, [(1, 300)] * 16)
    assert w.ops == 48 * 16 * 5 * 64 * 128 * 64
    state = (64 * 128 * 64 + 3 * 4352) * 4
    assert w.bytes == 48 * 16 * (2 * state + 4 * (8512 + 4096))
    # the state is most of it: 3.3 GB a step, 4.0 ms at 819 GB/s
    assert 3.2e9 < w.bytes < 3.4e9


def test_ssd_roofline_counts_the_state_traffic(monkeypatch):
    """The SSD's least time is mostly the slot state's bytes, which XLA
    moves in the ``ssm_state`` scope's fusions: the share's device time
    is the ``ssd`` scope's, the ``ssm_state`` scope's and the unscoped
    copies of a whole slot pool, each op once."""
    import types

    import engine_spans
    import run
    import trace_reduce

    pool = "f32[17,64,128,64]"
    ops = [trace_reduce.Event("%fusion.1", "%fusion.1", 0.0, 0.002),
           trace_reduce.Event("%fusion.2", "%fusion.2", 0.002, 0.006),
           trace_reduce.Event("%copy.3", f"%copy.3 {pool} {pool}", 0.008,
                              0.001),
           trace_reduce.Event("%sort.4", "%sort.4", 0.009, 0.001)]
    module = trace_reduce.Event("jit__decode(1)", "jit__decode(1)", 0.0,
                                0.01)
    programs = {"jit__decode(1)": {
        "fusion.1": "jit(_decode)/attn/ssd/dot_general",
        "fusion.2": "jit(_decode)/attn/ssm_state/scatter",
        "sort.4": "jit(_decode)/sample/sort"}}
    spans = [engine_spans.Span("step", 0.0, 0.01, {"step": 1})]
    monkeypatch.setattr(trace_reduce, "newest_xplane", lambda d: "hand")
    monkeypatch.setattr(engine_spans, "load_spans", lambda p: spans)
    monkeypatch.setattr(engine_spans, "program_op_names",
                        lambda p: programs)
    engine_spans._CACHE.clear()
    rows = [(1, 300)] * 16
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(
        cell=run.load_cell(CELL), requests={}, pools=[pool], peaks=peaks,
        trace=trace_reduce.Trace(ops, [module], [], 1),
        window=types.SimpleNamespace(
            traced_steps=[{"prefill": [], "decode": rows}]))
    try:
        state_ms = run.load_reader("step.ssm_state_ms").read(ctx)
        share = run.load_reader("ssd_roofline").read(ctx)
    finally:
        engine_spans._CACHE.clear()
    assert state_ms == pytest.approx(7.0)
    least = costs.roofline_s(costs.mixer(CONF, rows), 197e12, 819e9)
    assert share == pytest.approx(100 * least / 0.009)
    assert 0 < share < 100
