"""A whole run of a tiny copy of a cell on the CPU: the result line, the
refusal to run without a TPU, and the correctness check against its
control and against faults planted in the timed path."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import run

CELL = bench_tiny.CELLS[0]
CELLS = bench_tiny.CELLS


@pytest.fixture(scope="module")
def honest():
    return bench_tiny.serve(bench_tiny.cell(CELL))


def test_result_line_keys_and_parse(honest):
    line = json.dumps(honest)
    back = json.loads(line)
    assert list(back)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(back)[-1] == "check"
    assert back["correct"] is True
    assert back["attempted"] > 0 and back["failed"] == 0
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["end_to_end"]}
    assert set(back["metrics"]) == names
    for m in back["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert back["device"]["platform"] == "cpu"
    for name, num in back["check"].items():
        assert set(num) == {"value", "limit"}, name


def test_traced_run_reports_per_layer_metrics():
    c = bench_tiny.cell(CELL)
    # shared documents, as a long-context mix has them, so that the prefix
    # cache's reader finds hits to count
    c.mix = dict(c.mix, shared={"share": 0.6, "documents": 2, "doc_len": 32,
                                "question": {"dist": "uniform", "min": 4,
                                             "max": 16}})
    c.per_layer = c.per_layer + [{"name": "pool.prefix_hit_share",
                                  "unit": "%"}]
    res = bench_tiny.serve(c, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert "output_tokens_per_s" not in m
    assert {"client.late_p95_ms", "sched.queue_wait_p95_ms",
            "sched.decode_rows_mean", "pool.prefix_hit_share"} <= set(m)
    assert 0 < m["pool.prefix_hit_share"]["value"] < 100
    # no device plane on the CPU: the device readers find nothing
    assert "bnn_gemm_roofline" not in m
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.BENCH, "run.py"),
         "--workload", CELL, "--seed", "5", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    import shutil
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "5",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference in bfloat16, put in the program's place and judged by
    the run's own check at the cell's own limit, reads not correct."""
    c = bench_tiny.cell(cell)
    res = bench_tiny.serve(c, control={"dtype": "bfloat16"})
    gap = res["check"]["gap_max"]
    assert gap["limit"] == run.load_cell(cell).spec["check"]["limits"][
        "gap_max"]
    assert gap["value"] > gap["limit"]
    assert res["correct"] is False


def _state_unchanged(eng):
    dec = eng._decode_fn

    def f(params, pools, *a):
        tok, logits, _ = dec(params, jax_copy(pools), *a)
        return tok, logits, pools
    eng._decode_fn = f


def _half_batch(eng):
    """Every family's decode program takes (params, pools, tokens, table,
    lengths, active, slots, sampling...): rows off in ``active`` are
    left out as padding is."""
    dec = eng._decode_fn

    def f(params, pools, tokens, table, lengths, active, *a):
        half = (jnp.arange(active.shape[0]) < (active.sum() + 1) // 2)
        return dec(params, pools, tokens, table, lengths, active & half, *a)
    eng._decode_fn = f


def _token_altered(eng):
    dec = eng._decode_fn
    vocab = eng.cfg.vocab

    def f(*a):
        tok, logits, pools = dec(*a)
        return (tok + 1) % vocab, logits, pools
    eng._decode_fn = f


def jax_copy(tree):
    import jax
    return jax.tree.map(jnp.copy, tree)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_in_the_timed_path_is_not_correct(fault, cell):
    res = bench_tiny.serve(bench_tiny.cell(cell), fault=fault)
    assert res["check"]["served_tokens"]["value"] >= 64
    assert res["correct"] is False
