"""mamba2 through the serving engine against plain references, on the CPU.

* In float, with ordinary random weights (decay, the conv tail and the
  gate all live): chunked prefill at an odd chunk size, decode through
  the recurrent slots and forced preemption by swap, teacher-forced
  through a model built from the layer's sequential recurrence
  (``mamba2.forward_reference``).
* On the 1-bit datapath, with the benchmark's seeded ssm weights at the
  family's tiny widths: every sign fed to a BNN GEMM is the one the
  benchmark's plain reference (``bench/families/ssm/reference.py``)
  feeds it, and the benchmark's check reads a gap of exactly 0.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import reduced
from repro.layers import common as C
from repro.layers import mamba2
from repro.models import transformer as M
from repro.serving import Engine, EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CELL = "mamba2-bnn.chat-burst"

# float32 reassociation between the chunked dual form, the one-token
# recurrence and the sequential reference moves logits by ~1e-6 of their
# spread; a lost or foreign state moves the served token by whole
# standard deviations
GAP_TOL = 1e-3


def _reference_logits(params, cfg, tokens):
    """(T, vocab) logits of one sequence: each layer's mixer is the
    sequential recurrence, nothing chunked, no slots."""
    x = params["embed"]["w"][jnp.asarray(tokens)][None]
    for _mix, _f, p in M._iter_layers(cfg, params):
        h = C.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
        x = x + mamba2.forward_reference(p["attn"], cfg, h)
    x = C.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return np.asarray(x[0] @ params["embed"]["w"].T, np.float64)


def _gaps(params, cfg, prompt, out):
    seq = np.concatenate([prompt, out])
    lg = _reference_logits(params, cfg, seq[:-1])[len(prompt) - 1:]
    best = lg.max(-1)
    return (best - lg[np.arange(len(out)), out]) / lg.std(-1)


@pytest.fixture(scope="module")
def float_model():
    cfg = reduced(configs.get_config("mamba2-1.3b")).replace(
        precision="bf16")
    params, _ = M.init(jax.random.PRNGKey(3), cfg)
    return cfg, params


def test_float_engine_matches_recurrence_with_preemption(float_model):
    cfg, params = float_model
    a = params["segments"][0]["l0"]["attn"]
    assert float(jnp.abs(a["A_log"]).max()) > 0      # decay live
    assert float(jnp.abs(a["conv_w"][:, :-1]).max()) > 0   # conv tail live
    eng = Engine(params, cfg, EngineConfig(
        block_size=4, num_blocks=33, max_batch=3, prefill_chunk=7,
        max_batched_tokens=10, max_model_len=64, preempt_policy="swap"))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (5, 13, 22, 30)]
    rids = [eng.submit(p, 12) for p in prompts]
    for i in range(40):
        eng.step()
        if i in (6, 14):
            eng.scheduler._preempt_one(eng.step_count, None)
    out = eng.run()
    st = eng.stats()
    assert st["preemptions"] >= 2
    assert st["mixer"]["slots"]["swapped_slots"] >= 1
    for rid, p in zip(rids, prompts):
        served = np.asarray(out[rid][len(p):])
        assert len(served) == 12
        assert _gaps(params, cfg, p, served).max() <= GAP_TOL


# ------------------------------------------------- the 1-bit datapath


@pytest.fixture(scope="module")
def bench_modules():
    for p in (BENCH, os.path.join(BENCH, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import bench_tiny
    import loader
    import run
    return bench_tiny, loader, run


@pytest.fixture(scope="module")
def tiny_bnn(bench_modules):
    bench_tiny, loader, run = bench_modules
    conf = bench_tiny.cell(CELL).config
    cfg = run.arch_config(conf)
    params = loader.family(conf, "weights").make(conf, 2 ** 33 + 5)
    return conf, cfg, params, loader.family(conf, "reference")


def _record_program_signs(monkeypatch, cfg, params, prompt, n_decode,
                          chunk=16):
    """Serve one row eagerly through the program's chunked prefill and
    slot decode; returns the fed tokens and, per BNN GEMM call in layer
    order, the signs of its input rows."""
    calls = []
    dense = C.dense

    def recording(x, p, precision="bf16"):
        if precision == "bnn":
            calls.append(np.asarray(x >= 0).reshape(-1, x.shape[-1]))
        return dense(x, p, precision)

    monkeypatch.setattr(C, "dense", recording)
    pools = M.init_paged_state(cfg, 1, 4, num_slots=2)
    slots = jnp.ones((1,), jnp.int32)
    per_step, fed = [], []
    for s in range(0, len(prompt), chunk):
        part = prompt[s:s + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        calls.clear()
        logits, pools = M.prefill_chunk(
            params, cfg, jnp.asarray(toks), pools, None, None,
            jnp.asarray([len(part)], jnp.int32), slots)
        per_step.append([c[:len(part)] for c in calls])
        fed.extend(part)
    tok = int(jnp.argmax(logits[0, len(part) - 1]))
    for _ in range(n_decode):
        calls.clear()
        logits, pools = M.paged_decode_step(
            params, cfg, jnp.asarray([[tok]], jnp.int32), pools, None, None,
            jnp.ones((1,), bool), slots)
        per_step.append(list(calls))
        fed.append(tok)
        tok = int(jnp.argmax(logits[0, 0]))
    monkeypatch.setattr(C, "dense", dense)
    return np.asarray(fed), [np.concatenate(c) for c in zip(*per_step)]


def _record_reference_signs(monkeypatch, reference, conf, params, tokens):
    calls = []
    proj = reference._proj

    def recording(x, w, precision):
        calls.append(np.asarray(x >= 0))
        return proj(x, w, precision)

    monkeypatch.setattr(reference, "_proj", recording)
    with jax.disable_jit():
        reference._forward(params, jnp.asarray(tokens, jnp.int32),
                           jnp.asarray([len(tokens) - 1], jnp.int32),
                           frozen=reference.frozen_config(conf),
                           dtype=jnp.float32)
    monkeypatch.setattr(reference, "_proj", proj)
    return calls


def test_every_bnn_sign_matches_the_reference(monkeypatch, tiny_bnn):
    conf, cfg, params, reference = tiny_bnn
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, 37)
    fed, prog = _record_program_signs(monkeypatch, cfg, params, prompt,
                                      n_decode=24)
    ref = _record_reference_signs(monkeypatch, reference, conf, params,
                                  fed)
    assert len(prog) == len(ref) == 2 * cfg.n_layers
    for i, (a, b) in enumerate(zip(prog, ref)):
        assert a.shape == b.shape == (len(fed), a.shape[1]), i
        assert np.array_equal(a, b), f"GEMM {i}: {np.sum(a != b)} signs"
    # the state decides signs: the SSD's output feeds out_proj, and its
    # signs are not fixed along the sequence
    assert all(0 < b.mean() < 1 for b in ref[1::2])


def test_tiny_cell_reads_no_gap(bench_modules):
    bench_tiny, _, _ = bench_modules
    res = bench_tiny.serve(bench_tiny.cell(CELL))
    assert res["correct"] is True
    assert res["check"]["gap_max"]["value"] == 0.0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert CELL in {w["name"] for w in json.load(f)["workloads"]}
