"""The trace reduction: device busy time and idle share, kernel time by
name, and the host span that each long idle gap falls in."""
import json

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce as T

def ev(name, start, dur, label=None, device=0):
    return T.Event(name, label or name, start, dur, device)


def synthetic():
    ops = [ev("fusion.1", 0.000, 0.010),
           ev("custom-call.7", 0.005, 0.010, "custom-call.7 _fused_bnn_kernel"),
           ev("sort.3", 0.030, 0.005),                 # gap 0.015-0.030
           ev("custom-call.9", 0.036, 0.004, "_paged_attn_kernel"),
           ev("fusion.1", 0.100, 0.010)]               # gap 0.040-0.100
    modules = [ev("jit__decode", 0.0, 0.040), ev("jit__prefill", 0.1, 0.01)]
    host = [ev("bench.step", 0.0, 0.045), ev("bench.idle", 0.045, 0.05),
            ev("bench.schedule", 0.012, 0.02)]
    return T.Trace(ops, modules, host, 1)


def test_union_and_busy():
    tr = synthetic()
    got = [x for iv in T.union(tr.ops) for x in iv]
    assert got == pytest.approx([0.0, 0.015, 0.030, 0.035, 0.036, 0.040,
                                 0.100, 0.110])
    assert T.busy_s(tr) == pytest.approx(0.015 + 0.005 + 0.004 + 0.010)


def test_kernel_time_by_name():
    tr = synthetic()
    bnn = T.matching(tr.ops, ("fused_bnn", "binarize_pack"))
    assert [e.name for e in bnn] == ["custom-call.7"]
    assert T.device_time(bnn) == pytest.approx(0.010)
    assert T.device_time(T.matching(tr.modules, ("_decode",))) == \
        pytest.approx(0.040)


def test_top_ops_and_gap_attribution():
    tr = synthetic()
    top = T.top_ops(tr, 2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(0.020)
    gaps = T.idle_gaps(tr, 3)
    # 0.040-0.100: the middle (0.070) lies in bench.idle;
    # 0.015-0.030: the middle lies in bench.schedule inside bench.step
    assert [g[0] for g in gaps] == ["bench.idle", "bench.schedule",
                                    "bench.step"]
    assert gaps[0][1] == pytest.approx(0.060)


def test_json_round_trip():
    tr = synthetic()
    back = T.from_json(json.loads(json.dumps(T.to_json(tr))))
    assert T.busy_s(back) == pytest.approx(T.busy_s(tr))
    assert T.idle_gaps(back) == T.idle_gaps(tr)


def recorded():
    """One decode (8 rows) and one prefill step of qwen-bnn.rag-long as a
    TPU v5e trace shows them: every Pallas kernel, and the 40 longest
    other ops, with labels cut to the op's name and operand types."""
    import gzip
    import os
    path = os.path.join(bench_tiny.HERE, "data", "rag_long_two_steps.json.gz")
    with gzip.open(path, "rt") as f:
        return T.from_json(json.load(f))


def test_recorded_trace_kernels_by_operand_shapes():
    """A trace from before the kernels carried names: the shape rule
    (``record_trace.shape_rules``) finds paged attention by the KV pool
    among its operands, and the name rule finds nothing to read."""
    import types

    import engine_spans
    import record_trace
    import run
    tr = recorded()
    cell = run.load_cell("qwen-bnn.rag-long")
    ctx = types.SimpleNamespace(cell=cell, trace=tr)
    kernels = [e for e in tr.ops if "tpu_custom_call" in e.label]
    by_shape = record_trace.shape_rules(tr.ops, cell)
    paged = by_shape["attention"]
    # one paged-attention call per layer in each of the two steps
    assert len(paged) == 2 * 24
    assert T.device_time(paged) == pytest.approx(0.075564, rel=1e-4)
    assert len(kernels) - len(paged) == len(by_shape["bnn"]) == 672
    assert engine_spans.kernel_calls(tr.ops, record_trace.ATTN +
                                     record_trace.BNN) == []
    ms = run.load_reader("step.decode_ms").read(ctx)
    assert ms == pytest.approx(142.877, rel=1e-4)


NAMED = ["qwen-bnn.chat-steady", "qwen-bnn.rag-long"]


@pytest.mark.parametrize("cell", NAMED)
def test_chip_trace_name_rules_select_what_shape_rules_did(cell):
    """One decode and one prefill program run of the cell on a TPU v5e,
    reduced by ``record_trace.py``: the kernels found by name and the
    pool copies found by the engine's own pools are the ops that the
    dense KV pool's shape found, with the same device time."""
    import gzip
    import os

    import engine_spans as S
    import record_trace as R
    import run
    path = os.path.join(bench_tiny.HERE, "data", f"named_{cell}.json.gz")
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    tr = T.from_json(d)
    c = run.load_cell(cell)
    old, new = R.shape_rules(tr.ops, c), R.name_rules(tr.ops)
    # one paged-attention call per layer in each of the two programs
    assert len(new["attention"]) == 2 * c.config["n_layers"]
    assert new["bnn"]
    for kind in ("attention", "bnn"):
        assert new[kind] == old[kind], kind
        assert T.device_time(new[kind]) == T.device_time(old[kind])
    assert d["pools"] == [R.dense_pool(c)]
    copies = R.pool_copies(tr.ops, d["paths"], d["pools"])
    assert copies
    assert copies == R.pool_copies(tr.ops, d["paths"], [R.dense_pool(c)])
    assert S.scope_time(tr.ops, d["paths"], "kv_update", d["pools"]) > \
        S.scope_time(tr.ops, d["paths"], "kv_update")
