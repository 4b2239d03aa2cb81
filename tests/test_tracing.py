"""Structured tracing + hardware-in-the-loop replay tests.

Covers the observability contract (docs/observability.md): trace schema
and JSONL round-trip, the disabled-path zero-overhead guard, the
stats==span-sum invariant that replaced the ad-hoc perf_counter
accumulators, the Perfetto exporter's track structure, the replay
driver's analytic-vs-simulated report (incl. the sublinear batched
decode cost curve), the BENCH_serving.json schema gate, and the pinned
jamba paged-vs-legacy divergence (ROADMAP known bug) with its
logit-level dump filed in a trace.
"""
import json

import numpy as np
import pytest

from repro.serving import (TRACE_SCHEMA_VERSION, SamplingParams, Tracer,
                           read_trace, replay_trace, validate_trace)
from repro.serving.tracing import RECORD_TYPES
from test_serving import _engine  # bnn_cfg/bnn_params from conftest.py


def _traced_run(cfg, params, tmp_path, *, capture_logits=False, **kw):
    """Small smoke serve: enough requests to overlap prefill+decode."""
    eng = _engine(cfg, params, **kw)
    path = str(tmp_path / "trace.jsonl")
    eng.start_trace(path, capture_logits=capture_logits)
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(rng.integers(0, cfg.vocab, 4 + i), 6)
    eng.run()
    eng.stop_trace()
    return eng, path


# ------------------------------------------------------------- schema

def test_trace_jsonl_roundtrip_and_schema(bnn_cfg, bnn_params, tmp_path):
    eng, path = _traced_run(bnn_cfg, bnn_params, tmp_path)
    records = read_trace(path)          # validates en route
    assert records[0]["type"] == "meta"
    assert records[0]["schema"] == TRACE_SCHEMA_VERSION
    # the meta record is self-describing: full flat arch config
    assert records[0]["config"]["name"] == bnn_cfg.name
    assert records[0]["config"]["n_layers"] == bnn_cfg.n_layers
    types = {r["type"] for r in records}
    assert {"meta", "step", "request"} <= types <= set(RECORD_TYPES)
    # file contents == in-memory ring (ring large enough here)
    assert records == eng.tracer.events()

    steps = [r for r in records if r["type"] == "step"]
    assert steps and all(r["dur_s"] >= 0 for r in steps)
    kinds = {r["kind"] for r in steps}
    assert any("prefill" in k for k in kinds)
    assert any("decode" in k for k in kinds)
    dec = next(r["decode"] for r in steps if "decode" in r)
    assert dec["rows"] == dec["fed_tokens"] == dec["committed"] \
        == len(dec["rids"])
    assert dec["bucket"] >= dec["rows"]

    # request lifecycle: every request submits, admits, and finishes,
    # in that order, and reaches a first token
    reqs = [r for r in records if r["type"] == "request"]
    for rid in range(5):
        seq = [r["event"] for r in reqs if r["rid"] == rid]
        assert seq.index("submit") < seq.index("admit") \
            < seq.index("first_token") <= seq.index("finish")


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="empty"):
        validate_trace([])
    with pytest.raises(ValueError, match="meta"):
        validate_trace([{"type": "step", "step": 0, "dur_s": 0.1}])
    meta = {"type": "meta", "schema": TRACE_SCHEMA_VERSION}
    with pytest.raises(ValueError, match="schema"):
        validate_trace([{"type": "meta", "schema": 999}])
    with pytest.raises(ValueError, match="unknown type"):
        validate_trace([meta, {"type": "bogus"}])
    with pytest.raises(ValueError, match="missing field 'dur_s'"):
        validate_trace([meta, {"type": "step", "step": 0}])
    validate_trace([meta])              # minimal valid trace


# --------------------------------------------------- disabled overhead

def test_disabled_tracing_is_inert(bnn_cfg, bnn_params, monkeypatch):
    """Tracing off (the default): the hot path never builds or emits a
    record — emit() raising proves no call site reaches it."""
    eng = _engine(bnn_cfg, bnn_params)
    assert not eng.tracer.enabled and eng.tracer.ring is None

    def boom(self, record):
        raise AssertionError(f"emit() called while disabled: {record}")
    monkeypatch.setattr(Tracer, "emit", boom)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(rng.integers(0, bnn_cfg.vocab, 4), 4)
    eng.run()
    assert eng.tracer.ring is None
    # span accounting still ran (it backs stats() either way)
    assert eng.stats()["wall_s"] > 0


def test_tracing_off_matches_on_token_for_token(bnn_cfg, bnn_params,
                                                tmp_path):
    """Observability never changes results: same tokens traced or not,
    and under a profiler session or not."""
    import jax
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, bnn_cfg.vocab, 5) for _ in range(3)]
    plain = _engine(bnn_cfg, bnn_params)
    rids = [plain.submit(p, 6) for p in prompts]
    out_plain = plain.run()
    traced = _engine(bnn_cfg, bnn_params)
    traced.start_trace(str(tmp_path / "t.jsonl"), capture_logits=True)
    rids_t = [traced.submit(p, 6) for p in prompts]
    out_traced = traced.run()
    traced.stop_trace()
    for ra, rb in zip(rids, rids_t):
        np.testing.assert_array_equal(out_plain[ra], out_traced[rb])
    profiled = _engine(bnn_cfg, bnn_params)
    rids_p = [profiled.submit(p, 6) for p in prompts]
    jax.profiler.start_trace(str(tmp_path / "xplane"))
    try:
        out_profiled = profiled.run()
    finally:
        jax.profiler.stop_trace()
    for ra, rb in zip(rids, rids_p):
        np.testing.assert_array_equal(out_plain[ra], out_profiled[rb])


# ------------------------------------------------ spans in the profiler

PHASES = ("prepare", "dispatch", "sync", "commit")


def _engine_events(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(span name, start ns, end ns, stats) of every ``engine.*`` host
    event in the newest xplane under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name[len("engine."):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: int(v) for k, v in ev.stats}))
    return out


def test_profiler_trace_holds_engine_phase_spans(bnn_cfg, bnn_params,
                                                 tmp_path):
    """Under a profiler session every step is an ``engine.step`` host
    event whose phases (schedule, then prepare / dispatch / sync /
    commit of each call) lie inside it, all stamped with its step."""
    import jax
    eng = _engine(bnn_cfg, bnn_params)
    rng = np.random.default_rng(4)
    eng.submit(rng.integers(0, bnn_cfg.vocab, 4), 3)
    eng.run()                          # compiles outside the trace
    for i in range(3):
        eng.submit(rng.integers(0, bnn_cfg.vocab, 4), 3 + i)
    first = eng.step_count
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    evs = _engine_events(tmp_path)
    roots = [e for e in evs if e[0] == "step"]
    assert [e[3]["step"] for e in roots] == list(range(first,
                                                       eng.step_count))
    assert all("step" in e[3] for e in evs)
    for name, s0, s1, st in roots:
        kids = [e for e in evs if e[0] != "step" and e[3]["step"]
                == st["step"]]
        assert all(s0 <= k[1] <= k[2] <= s1 for k in kids)
        names = {k[0] for k in kids}
        assert "schedule" in names
        for phase in PHASES:
            assert any(n.endswith("." + phase) for n in names), \
                (st["step"], phase, names)
    for name, _, _, st in evs:
        if name.startswith("decode."):
            assert st["bucket"] >= st["rows"] >= 1
        if name.startswith("prefill."):
            assert {"rid", "tokens", "chunk"} <= set(st)
            assert st["chunk"] == eng.ecfg.prefill_chunk >= st["tokens"]


def test_disabled_tracing_still_times_every_phase(bnn_cfg, bnn_params,
                                                  monkeypatch):
    """Tracing off: the phase spans accumulate their totals (and annotate
    the profiler) but never reach ``emit``."""
    def boom(self, record):
        raise AssertionError(f"emit() called while disabled: {record}")
    monkeypatch.setattr(Tracer, "emit", boom)
    eng = _engine(bnn_cfg, bnn_params)
    eng.submit(np.arange(4, dtype=np.int32), 4)
    eng.run()
    tr = eng.tracer
    assert tr.open_span is None and tr.step == eng.step_count
    for name in ("step", "schedule", "prefill.prepare", "prefill.dispatch",
                 "prefill.sync", "prefill.commit", "decode.prepare",
                 "decode.dispatch", "decode.sync", "decode.commit"):
        assert tr.span_total(name) > 0, name
    assert not hasattr(tr, "span_counts")


def test_phase_span_totals_equal_trace_span_sums(bnn_cfg, bnn_params,
                                                 tmp_path):
    """Every span name's accumulated total is the sum of its emitted
    records; the root ``step`` span is the step records' sum."""
    eng, path = _traced_run(bnn_cfg, bnn_params, tmp_path)
    records = read_trace(path)
    by_name: dict[str, float] = {}
    for r in records:
        if r["type"] == "span":
            by_name[r["name"]] = by_name.get(r["name"], 0.0) + r["dur_s"]
    assert "step" not in by_name
    assert {"schedule", "decode.sync", "prefill.commit"} <= set(by_name)
    for name, total in by_name.items():
        assert np.isclose(eng.tracer.span_total(name), total,
                          rtol=1e-9), name
    steps = [r for r in records if r["type"] == "step"]
    assert np.isclose(eng.tracer.span_total("step"),
                      sum(r["dur_s"] for r in steps), rtol=1e-9)


def test_span_records_carry_step_and_parent(bnn_cfg, bnn_params,
                                            tmp_path):
    """Schema v5: every span record names its step and the span that
    encloses it; a step's phases are children of the root ``step`` and
    copies are children of the phase they ran in."""
    eng = _engine(bnn_cfg, bnn_params, block_size=2, num_blocks=9,
                  max_batch=2, max_model_len=12, prefill_chunk=4,
                  preempt_policy="swap")
    path = str(tmp_path / "trace.jsonl")
    eng.start_trace(path)
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(rng.integers(0, bnn_cfg.vocab, 4), 8)
    eng.run()
    eng.stop_trace()
    records = read_trace(path)
    assert records[0]["schema"] == TRACE_SCHEMA_VERSION == 5
    steps = {r["step"] for r in records if r["type"] == "step"}
    spans = [r for r in records if r["type"] == "span"]
    assert spans and all(r["step"] in steps for r in spans)
    for r in spans:
        if r["name"] == "schedule" or r["name"].rsplit(".", 1)[-1] \
                in PHASES:
            assert r["parent"] == "step", r
    copies = [r for r in spans if r["name"].startswith(("swap_",
                                                        "snapshot_"))]
    assert copies, "forced preemption must emit swap spans"
    assert all(r["parent"] == "schedule"
               or r["parent"].endswith(".prepare") for r in copies)
    with pytest.raises(ValueError, match="missing field 'parent'"):
        validate_trace([records[0], {"type": "span", "name": "x",
                                     "dur_s": 0.0, "step": 0}])


def test_dispatch_spans_count_sampled_rows(bnn_cfg, bnn_params, tmp_path):
    """Each ``<kind>.dispatch`` span's ``sampled`` is the number of its
    call's rows with temperature > 0: the rows the sampling filter
    decides."""
    eng = _engine(bnn_cfg, bnn_params, max_batch=4)
    path = str(tmp_path / "trace.jsonl")
    eng.start_trace(path)
    rng = np.random.default_rng(2)
    hot = set()
    for i in range(4):
        sp = SamplingParams(temperature=0.7 * (i % 2), top_p=0.9, seed=i)
        rid = eng.submit(rng.integers(0, bnn_cfg.vocab, 4 + i), 5,
                         sampling=sp)
        if i % 2:
            hot.add(rid)
    eng.run()
    eng.stop_trace()
    records = read_trace(path)
    decode_rids = {r["step"]: r["decode"]["rids"] for r in records
                   if r["type"] == "step" and "decode" in r}
    spans = [r for r in records if r["type"] == "span"
             and r["name"].endswith(".dispatch")]
    assert {r["name"] for r in spans} == {"prefill.dispatch",
                                          "decode.dispatch"}
    mixed = False
    for r in spans:
        if r["name"] == "prefill.dispatch":
            assert r["sampled"] == (r["rid"] in hot)
        else:
            rids = decode_rids[r["step"]]
            assert r["sampled"] == len(hot.intersection(rids)) <= r["rows"]
            mixed |= 0 < r["sampled"] < r["rows"]
    assert mixed


# ------------------------------------------------- stats == span sums

def test_stats_totals_equal_trace_span_sums(bnn_cfg, bnn_params,
                                            tmp_path):
    """The migrated accounting invariant: stats() wall/swap totals are
    exactly the sum of the emitted trace records (single source of
    truth — no second accumulator to drift)."""
    # forced swap pressure: tiny pool, two growing requests
    eng = _engine(bnn_cfg, bnn_params, block_size=2, num_blocks=9,
                  max_batch=2, max_model_len=12, prefill_chunk=4,
                  preempt_policy="swap")
    path = str(tmp_path / "trace.jsonl")
    eng.start_trace(path)
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(rng.integers(0, bnn_cfg.vocab, 4), 8)
    eng.run()
    eng.stop_trace()
    records = read_trace(path)
    st = eng.stats()

    steps = [r for r in records if r["type"] == "step"]
    assert np.isclose(st["wall_s"], sum(r["dur_s"] for r in steps),
                      rtol=1e-9)
    spans = [r for r in records if r["type"] == "span"]
    assert spans, "forced preemption must emit swap spans"
    by_name = {}
    for s in spans:
        by_name[s["name"]] = by_name.get(s["name"], 0.0) + s["dur_s"]
    sw = st["swap"]
    assert sw["swap_outs"] >= 1
    assert np.isclose(sw["swap_out_s"],
                      by_name.get("swap_out", 0.0)
                      + by_name.get("snapshot_out", 0.0), rtol=1e-9)
    assert np.isclose(sw["swap_in_s"],
                      by_name.get("swap_in", 0.0)
                      + by_name.get("snapshot_in", 0.0), rtol=1e-9)
    # swap actions also land on the step records they happened in
    acts = [r.get("actions", {}) for r in steps]
    assert sum(a.get("swap_outs", 0) for a in acts) == sw["swap_outs"]
    assert sum(a.get("preempts", 0) for a in acts) == st["preemptions"]


def test_reset_stats_clears_span_accumulators(bnn_cfg, bnn_params):
    eng = _engine(bnn_cfg, bnn_params)
    eng.submit(np.arange(4, dtype=np.int32), 4)
    eng.run()
    assert eng.stats()["wall_s"] > 0
    eng.reset_stats()
    assert eng.stats()["wall_s"] == 0.0


# ------------------------------------------------------------- replay

def test_replay_reports_analytic_vs_simulated(bnn_cfg, bnn_params,
                                              tmp_path):
    eng, path = _traced_run(bnn_cfg, bnn_params, tmp_path)
    rep = replay_trace(path)            # config comes from the meta line
    assert rep["schema_version"] == 1
    assert rep["arch"] == bnn_cfg.name
    assert rep["steps"] == len([r for r in read_trace(path)
                                if r["type"] == "step"])
    assert {"prefill", "decode"} <= set(rep["by_kind"])
    for t in rep["by_kind"].values():
        assert t["analytic_s"] > 0 and t["simulated_s"] > 0
        assert np.isfinite(t["analytic_over_simulated"])
    assert rep["finished_requests"] == 5
    assert rep["committed_tokens"] == sum(
        t["committed_tokens"] for t in rep["by_kind"].values())
    assert rep["simulated_tokens_per_s"] > 0
    assert rep["simulated_fps"] > 0

    # the tentpole claim: mapping decode rows onto DWDM wavelengths /
    # OXG arrays makes batching SUBLINEAR (rows share fills + TUNE),
    # unlike the analytic model's sequential-tokens assumption
    curve = rep["decode_batch_curve"]
    assert "1" in curve and len(curve) >= 2
    per_tok = [curve[b]["token_latency_s"] for b in curve]
    assert all(a > b for a, b in zip(per_tok, per_tok[1:]))
    bmax = max(curve, key=int)
    assert curve[bmax]["step_latency_s"] \
        < int(bmax) * curve["1"]["step_latency_s"]
    # in-memory records replay identically to the file
    rep2 = replay_trace(read_trace(path))
    assert rep2["simulated_s"] == rep["simulated_s"]


def test_replay_formats_report(bnn_cfg, bnn_params, tmp_path):
    from repro.serving import format_report
    _, path = _traced_run(bnn_cfg, bnn_params, tmp_path)
    text = format_report(replay_trace(path))
    assert "analytic" in text and "simulated" in text
    assert "decode" in text and "TOTAL" in text


# ----------------------------------------------------------- perfetto

def test_perfetto_export_track_structure(bnn_cfg, bnn_params, tmp_path):
    from repro.launch.trace_view import export_perfetto
    _, path = _traced_run(bnn_cfg, bnn_params, tmp_path)
    out = str(tmp_path / "trace.perfetto.json")
    n = export_perfetto(path, out)
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert n == len(evs) > 0
    records = read_trace(path)

    # golden track structure: engine steps + one named track per rid
    names = {(e["pid"], e["args"]["name"]) for e in evs if e["ph"] == "M"
             and e["name"] in ("process_name", "thread_name")}
    assert (1, "engine") in names and (1, "steps") in names
    assert (2, "requests") in names
    for rid in range(5):
        assert (2, f"rid {rid}") in names

    slices = [e for e in evs if e["ph"] == "X"]
    n_steps = len([r for r in records if r["type"] == "step"])
    assert len([e for e in slices if e["pid"] == 1 and e["tid"] == 1]) \
        == n_steps
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in slices)
    # every request shows a queued and a running slice
    for rid in range(5):
        tid = rid + 1
        mine = {e["name"] for e in slices
                if e["pid"] == 2 and e["tid"] == tid}
        assert {"queued", "running"} <= mine
    # step slices are named by kind and carry the step payload
    step_names = {e["name"] for e in slices if e["pid"] == 1}
    assert any("decode" in n for n in step_names)
    # the phases of each step get a track of their own, apart from the
    # copies, one slice per phase span record with its step and parent
    assert (1, "phases") in names
    phases = [e for e in slices if e["pid"] == 1 and e["tid"] == 3]
    n_phase = len([r for r in records if r["type"] == "span"
                   and r["name"] != "swap_out" and r["name"] != "swap_in"
                   and not r["name"].startswith(("snapshot_", "handoff_"))])
    assert len(phases) == n_phase > 0
    assert {"schedule", "decode.dispatch", "decode.sync"} <= \
        {e["name"] for e in phases}
    assert all(e["args"]["parent"] == "step" and "step" in e["args"]
               for e in phases)
    assert not [e for e in slices if e["pid"] == 1 and e["tid"] == 2
                and e["name"] in {p["name"] for p in phases}]


# ------------------------------------------------------ bench schema

def test_bench_json_schema_gate(tmp_path):
    import sys
    sys.path.insert(0, "benchmarks")
    try:
        from serving_bench import (BENCH_SCHEMA_VERSION, check_bench_json,
                                   write_bench_json)
    finally:
        sys.path.pop(0)
    row = {"arch": "bnn-lm-100m", "decode_tokens_per_s": 1.0,
           "total_tokens_per_s": 2.0, "p50_latency_s": 0.1,
           "p99_latency_s": 0.2, "p50_first_token_s": 0.05,
           "p99_first_token_s": 0.08, "modeled_tokens_per_s": 1e6,
           "replay": {"schema_version": 1, "simulated_tokens_per_s": 1e6,
                      "simulated_fps": 10.0, "analytic_s": 1.0,
                      "simulated_s": 0.5}}
    path = str(tmp_path / "BENCH_serving.json")
    doc = write_bench_json(path, [row], {"smoke": True})
    assert doc["schema_version"] == BENCH_SCHEMA_VERSION
    assert check_bench_json(path) == []

    bad = dict(doc)
    bad["rows"] = [{k: v for k, v in row.items()
                    if k != "p99_latency_s"}]
    bad_path = str(tmp_path / "bad.json")
    json.dump(bad, open(bad_path, "w"))
    problems = check_bench_json(bad_path)
    assert any("p99_latency_s" in p for p in problems)
    json.dump({"schema_version": 999}, open(bad_path, "w"))
    assert check_bench_json(bad_path)

    # disaggregated rows (--roles P:D) must carry the handoff report
    # and a passing token-identity verdict
    dis = dict(row, disaggregated=True)
    json.dump(dict(doc, rows=[dis]), open(bad_path, "w"))
    problems = check_bench_json(bad_path)
    assert any("roles" in p for p in problems)
    assert any("handoff" in p for p in problems)
    dis.update(roles=["prefill", "decode"],
               token_identical_to_mixed=True,
               handoff={"handoffs": 1, "handoff_bytes": 10,
                        "link_gbps": 100.0, "modeled_transfer_s": 1e-6,
                        "modeled_transfer_ms_per_handoff": 1e-3})
    json.dump(dict(doc, rows=[dis]), open(bad_path, "w"))
    assert check_bench_json(bad_path) == []
    dis["token_identical_to_mixed"] = False
    json.dump(dict(doc, rows=[dis]), open(bad_path, "w"))
    assert any("diverged" in p for p in check_bench_json(bad_path))


# ------------------------------------- jamba hybrid differential

@pytest.mark.slow  # jamba hybrid compile
def test_jamba_paged_matches_legacy_engine_level(jamba_models):
    """The hybrid family's engine-level differential: paged engine vs
    the dense-slot legacy oracle, token-identical (no mesh context —
    the serve-level pair below covers the mesh path)."""
    from test_prefix_swap import legacy_greedy
    cfg, params = jamba_models
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (2, 5)).astype(np.int32)
    eng = _engine(cfg, params, max_model_len=16)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    got = np.stack([out[r] for r in rids])
    want = legacy_greedy(cfg, params, prompts, 5)
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # two serve() runs end-to-end
def test_jamba_serve_paged_matches_legacy(tmp_path):
    """Regression for the once-pinned serve()-level divergence: jamba
    hybrid paged vs legacy at batch 2, prompt 5 / gen 5.  Root cause
    was never the mesh — the MoE layer's finite expert capacity
    dropped a real token at padded prefill-chunk widths 5-7 (see
    layers/moe.py: inference now dispatches drop-free).  The logit
    capture that located it stays exercised here."""
    from repro.launch.serve import serve
    kw = dict(smoke=True, batch=2, prompt_len=5, gen=5, precision="bnn")
    trace_path = str(tmp_path / "jamba_logits.jsonl")
    got = serve("jamba-1.5-large-398b", engine="paged", verbose=False,
                trace=trace_path, capture_logits=True, **kw)
    dumped = [r for r in read_trace(trace_path) if r["type"] == "step"]
    assert any("logits" in r.get("decode", {}) for r in dumped)
    want = serve("jamba-1.5-large-398b", engine="legacy", **kw)
    np.testing.assert_array_equal(got, want)
