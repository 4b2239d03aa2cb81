"""Serving benchmark: continuous batching under Poisson arrivals.

For each arch, an open-loop client submits requests with exponential
inter-arrival times while the engine steps; a fraction of the stream
(``--shared-frac``) shares one of a few prompt prefixes, the pattern
prefix caching exploits.  The arch table covers one row per mixer
family — paged-KV (dense GQA), recurrent slots (mamba2), paged latents
(deepseek MLA), ring buffers (mixtral SWA).  Reported per arch:

  * wall-clock decode and total (prefill+decode) tokens/s
  * nearest-rank p50 / p99 request latency (arrival -> last token)
  * max concurrent decode rows (continuous batching actually engaged)
  * speculative-decode draft acceptance rate, committed tokens per
    decode row-step, and the modeled photonic verify speedup
    (--spec-k enables prompt-lookup speculation; --temperature samples
    per request instead of greedy)
  * prefix-cache hit-rate, ring-buffer block-reuse rate, and total
    swap time (out+in)
  * per-mixer-family state-pool occupancy (peak used blocks/slots over
    pool capacity)
  * modeled OXBNN accelerator tokens/s (photonic cost model, with
    skipped-prefill credit) — mapped for every family, incl. SSD chunk
    matmuls and MLA latent projections

--slo adds a per-arch scheduler-policy comparison row: one mixed
latency+throughput+scoring trace run under the slo policy, under fcfs,
and as a scoring-only baseline, measured in engine steps so the
--require-slo CI gate (latency-class p99 first-token beats fcfs;
scoring retains >= 90% of its isolated throughput) is deterministic.

With --trace DIR each arch's measured window is recorded to
``DIR/trace_<arch>.jsonl`` (schema: docs/observability.md); with
--replay-photonic the recorded steps are re-priced through the
transaction-level photonic simulator and simulated tokens/s + FPS join
the report.  --bench-json persists everything as a schema-versioned
``BENCH_serving.json``; --check-json validates such a file (CI gate).

Usage (CPU smoke, reduced configs):
  PYTHONPATH=src python benchmarks/serving_bench.py --smoke --prefix-cache
  PYTHONPATH=src python benchmarks/serving_bench.py --smoke \
      --archs bnn-lm-100m --trace /tmp/tr --replay-photonic \
      --bench-json BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import configs
from repro.configs.base import reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as M
from repro.serving import (Engine, EngineConfig, SamplingParams,
                           ShardedEngine, layer_layouts, nearest_rank,
                           replay_trace)

# v3: adds slo-policy comparison rows (--slo / --require-slo) — mixed
# latency+throughput+scoring trace run under slo vs fcfs vs a
# scoring-only baseline, per-class first-token percentiles in engine
# steps (deterministic), and the scoring-throughput retention ratio
BENCH_SCHEMA_VERSION = 3

# BENCH_serving.json contract (CI fails the smoke job on violation)
BENCH_REQUIRED_KEYS = ("schema_version", "bench", "params", "rows")
BENCH_REQUIRED_ROW_KEYS = ("arch", "decode_tokens_per_s",
                           "total_tokens_per_s", "p50_latency_s",
                           "p99_latency_s", "p50_first_token_s",
                           "p99_first_token_s", "modeled_tokens_per_s")
BENCH_REQUIRED_REPLAY_KEYS = ("schema_version", "simulated_tokens_per_s",
                              "simulated_fps", "analytic_s", "simulated_s")
# sharded rows (shards > 1) additionally carry per-host breakdowns
BENCH_REQUIRED_SHARD_KEYS = ("shard", "role", "alive", "decoded_tokens",
                             "wall_s", "decode_tokens_per_s", "swap_losts")
# disaggregated rows (--roles P:D) carry the handoff report and the
# token-identity verdict against the equal-shard mixed baseline
BENCH_REQUIRED_ROLE_KEYS = ("roles", "handoff", "token_identical_to_mixed")
BENCH_REQUIRED_HANDOFF_KEYS = ("handoffs", "handoff_bytes", "link_gbps",
                               "modeled_transfer_s",
                               "modeled_transfer_ms_per_handoff")
# slo comparison rows (--slo) replace the standard row columns with the
# policy A/B: per-class first-token percentiles in ENGINE STEPS
# (wall-free, so the CI gate is deterministic) plus the scoring
# throughput retention vs a scoring-only run of the same engine
BENCH_REQUIRED_SLO_KEYS = ("arch", "slo", "tenants", "classes",
                           "slo_latency_p50_first_token_steps",
                           "slo_latency_p99_first_token_steps",
                           "fcfs_latency_p50_first_token_steps",
                           "fcfs_latency_p99_first_token_steps",
                           "scoring_tokens_per_step_mixed",
                           "scoring_tokens_per_step_only",
                           "scoring_retention", "scored_tokens",
                           "modeled_scoring_tokens_per_s")

# one row per mixer family: paged KV, slot (ssm), paged latent (mla),
# ring buffer (sliding window), hybrid (slots + paged KV per layer)
SMOKE_ARCHS = ["bnn-lm-100m", "qwen1.5-0.5b", "llama3.2-3b",
               "mamba2-1.3b", "deepseek-v2-lite-16b", "mixtral-8x7b",
               "jamba-1.5-large-398b"]


def make_prompts(rng, vocab: int, n_requests: int, prompt_len: int,
                 shared_frac: float, n_prefixes: int = 2) -> np.ndarray:
    """Synthetic prompt stream: ``shared_frac`` of requests reuse one
    of ``n_prefixes`` common prompt heads (half the prompt), the rest
    are fully random — the access pattern prefix caching targets."""
    prompts = rng.integers(0, vocab, (n_requests, prompt_len),
                           dtype=np.int32)
    half = prompt_len // 2
    if half and shared_frac > 0:
        heads = rng.integers(0, vocab, (n_prefixes, half), dtype=np.int32)
        for i in range(n_requests):
            if rng.random() < shared_frac:
                prompts[i, :half] = heads[rng.integers(n_prefixes)]
    return prompts


def bench_arch(arch: str, *, smoke: bool, n_requests: int, rate_hz: float,
               prompt_len: int, gen: int, max_batch: int,
               precision: str = "bnn", seed: int = 0,
               accelerator: str = "OXBNN_50", prefix_cache: bool = False,
               preempt_policy: str = "swap",
               shared_frac: float = 0.5, spec_k: int = 0,
               temperature: float = 0.0,
               trace_path: str | None = None,
               replay_photonic: bool = False, n_shards: int = 1,
               roles: str | None = None) -> dict:
    cfg = configs.get_config(arch)
    if smoke:
        cfg = reduced(cfg)
    cfg = cfg.replace(precision=precision)
    params, _ = M.init(jax.random.PRNGKey(seed), cfg)

    max_len = prompt_len + gen
    # block size <= half the prompt, so the shared heads make_prompts
    # writes (prompt_len // 2 tokens) span at least one FULL block —
    # otherwise the prefix cache has nothing it is allowed to match
    bs = max(4, min(16, prompt_len // 2))
    if prefix_cache and prompt_len // 2 < bs:
        print(f"[bench] warning: prompt_len={prompt_len} gives a "
              f"{prompt_len // 2}-token shared head < block_size={bs}; "
              "no full shared block can form, hit% will read 0")
    # slot snapshots are only capturable at prefill-chunk ends that are
    # also block boundaries, so SSM/hybrid rows align the chunk to the
    # block — a chunk spanning the whole prompt would leave nothing
    # shareable below full-prompt depth and hit% would read 0
    has_slots = "slot" in layer_layouts(cfg)
    prefill_chunk = bs if (prefix_cache and has_slots) \
        else min(16, prompt_len)
    ecfg = EngineConfig(
        block_size=bs,
        num_blocks=1 + max_batch * (-(-max_len // bs) + 1),
        max_batch=max_batch, prefill_chunk=prefill_chunk,
        max_model_len=max_len, accelerator=accelerator,
        prefix_cache=prefix_cache, preempt_policy=preempt_policy,
        spec_k=spec_k)
    if n_shards > 1:
        # weak scaling: each simulated host carries the single-shard
        # offered load (requests and arrival rate scale with the shard
        # count), so the aggregate — the sum of per-host decode rates,
        # each over ITS OWN stepped wall — measures fleet capacity the
        # way N concurrent hosts would deliver it.  The open-loop
        # tokens/s column does NOT scale in this single-process
        # simulation (shards step sequentially); the per-shard rows do.
        n_requests *= n_shards
        rate_hz *= n_shards
        eng = ShardedEngine(params, cfg, ecfg, n_shards, roles=roles)
    else:
        eng = Engine(params, cfg, ecfg)

    def sampling(i: int) -> SamplingParams:
        return SamplingParams(temperature=temperature, seed=seed + i)

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n_requests))
    # same trace whether the cache is on or off — only the engine differs
    prompts = make_prompts(rng, cfg.vocab, n_requests, prompt_len,
                           shared_frac)

    # warm the jits outside the measured window (compile >> smoke steps):
    # generations must be long enough (2 + max_batch) that the warm
    # requests overlap in decode and walk the batch through every
    # power-of-two bucket — a 2-token request finishes straight off its
    # prefill logits before a second prefill completes, which would
    # leave the multi-row decode shapes to compile mid-measurement
    if n_shards > 1:
        # every shard walks its own jit buckets through warmup
        warm = [eng.submit(prompts[0], 2 + max_batch, shard=i)
                for i in range(n_shards) for _ in range(max_batch)]
        eng.run()
        for w in warm:
            # a warm request may have crossed shards (prefill->decode
            # handoff), so evict it from every engine it touched
            eng.shard_of.pop(w)
            for e in eng.engines:
                e.requests.pop(w, None)
            eng.requests.pop(w)
    else:
        warm = [eng.submit(prompts[0], 2 + max_batch)
                for _ in range(max_batch)]
        eng.run()
        for w in warm:
            eng.requests.pop(w)
    # warmup polluted every counter (and cached its prompt): the
    # engine's lifetime token/wall totals feed the modeled-accelerator
    # report, so measure the open-loop window from a clean slate
    eng.reset_stats(flush_prefix=True)
    # tracing starts AFTER warmup so the trace covers exactly the
    # measured window (replay then prices only measured steps)
    if trace_path or replay_photonic:
        # no file: keep a ring big enough that replay sees every step
        if n_shards > 1:
            # per-shard files: {prefix}.shard{i}.jsonl
            prefix = (trace_path[:-len(".jsonl")]
                      if trace_path and trace_path.endswith(".jsonl")
                      else trace_path)
            eng.start_trace(prefix, ring=1 << 16)
        else:
            eng.start_trace(trace_path, ring=1 << 16)

    is_idle = ((lambda: eng.idle) if n_shards > 1
               else (lambda: eng.scheduler.idle))
    pending = list(range(n_requests))
    submitted: dict[int, float] = {}       # rid -> arrival offset
    t0 = time.perf_counter()
    while pending or not is_idle():
        now = time.perf_counter() - t0
        while pending and arrivals[pending[0]] <= now:
            i = pending.pop(0)
            rid = eng.submit(prompts[i], gen, arrival_s=arrivals[i],
                             sampling=sampling(i))
            submitted[rid] = arrivals[i]
        if is_idle():
            if pending:
                time.sleep(min(arrivals[pending[0]] - now, 0.01))
            continue
        eng.step()
    wall = time.perf_counter() - t0

    replay = None
    replay_per_shard = None
    if trace_path or replay_photonic:
        if n_shards > 1:
            shard_records = [e.tracer.events() for e in eng.engines]
            eng.stop_trace()
            if replay_photonic:
                replay_per_shard = [
                    replay_trace(rs, cfg=cfg, accelerator=accelerator)
                    for rs in shard_records]
        else:
            records = eng.tracer.events()
            eng.stop_trace()
            if replay_photonic:
                src = trace_path if trace_path else records
                replay = replay_trace(src, cfg=cfg, accelerator=accelerator)

    lats = sorted((eng.requests[rid].finish_s - t0) - arr
                  for rid, arr in submitted.items()
                  if eng.requests[rid].finish_s is not None)
    # time-to-first-token (arrival -> first decoded token): THE number
    # disaggregation moves — dedicated prefill shards keep fresh
    # prompts out of the decode batches' way, at the cost of one
    # modeled link transfer per request
    ft_lats = sorted((eng.requests[rid].first_token_s - t0) - arr
                     for rid, arr in submitted.items()
                     if eng.requests[rid].first_token_s is not None)
    # generated tokens per request, for the mixed-vs-disaggregated
    # identity gate (underscore keys are stripped from the bench JSON)
    outputs = {rid: list(eng.requests[rid].out) for rid in submitted
               if eng.requests[rid].finish_s is not None}
    if n_shards > 1:
        row = _sharded_row(arch, eng, n_requests, wall, lats, n_shards,
                           trace_path, replay_per_shard)
        row["p50_first_token_s"] = nearest_rank(ft_lats, 50)
        row["p99_first_token_s"] = nearest_rank(ft_lats, 99)
        row["_outputs"] = outputs
        return row
    st = eng.stats()
    pc, sw, mx, sp = (st["prefix_cache"], st["swap"], st["mixer"],
                      st["speculative"])
    blk, slt = mx.get("blocks"), mx.get("slots")
    return {
        "arch": arch, "requests": n_requests, "shards": 1,
        # per-host span-wall rate — the number the sharded rows
        # aggregate, so 1-vs-N scaling compares like with like
        "aggregate_decode_tokens_per_s": st["decode_tokens_per_s"],
        # decode tokens over the OPEN-LOOP wall (arrival waits included);
        # the engine's decode/total split over compute wall is in `st`
        "decode_tokens_per_s": st["decoded_tokens"] / wall,
        "total_tokens_per_s":
            (st["decoded_tokens"] + st["prefill_tokens"]) / wall,
        "p50_latency_s": nearest_rank(lats, 50),
        "p99_latency_s": nearest_rank(lats, 99),
        "p50_first_token_s": nearest_rank(ft_lats, 50),
        "p99_first_token_s": nearest_rank(ft_lats, 99),
        "_outputs": outputs,
        "max_concurrent": st["max_concurrent_decode"],
        "acceptance_rate": sp["acceptance_rate"],
        "tokens_per_decode_step": sp["tokens_per_decode_step"],
        "modeled_spec_speedup": st["photonic"]["modeled_spec_speedup"],
        "preemptions": st["preemptions"],
        "prefix_hit_rate": pc["hit_rate"],
        "skipped_prefill_tokens": pc["skipped_prefill_tokens"],
        "snapshot_hits": pc["snapshot_hits"],
        "snapshot_occupancy": (pc["snapshot_occupancy"] if slt
                               else float("nan")),
        "ring_reuse_rate": blk["ring_reuse_rate"] if blk else 0.0,
        "block_occupancy": blk["occupancy"] if blk else float("nan"),
        "slot_occupancy": slt["occupancy"] if slt else float("nan"),
        "families": "+".join(f"{k}:{v['layout']}" for k, v in mx.items()),
        "swap_s": sw["swap_out_s"] + sw["swap_in_s"],
        "swaps": sw["swap_outs"] + sw["swap_ins"],
        "modeled_tokens_per_s": st["photonic"]["modeled_tokens_per_s"],
        "modeled_effective_tokens_per_s":
            st["photonic"]["modeled_effective_tokens_per_s"],
        "accelerator": st["photonic"]["accelerator"],
        "trace_path": trace_path,
        "replay": replay,
    }


def _sharded_row(arch: str, eng, n_requests: int, wall: float, lats,
                 n_shards: int, trace_path, replay_per_shard) -> dict:
    """Assemble a bench row for a ShardedEngine run: the standard
    columns aggregate across shards (rates and counters sum, pool
    occupancies take the worst shard, modeled accelerator rates sum to
    the fleet figure), plus per-shard rows and the aggregate per-host
    decode tokens/s the scaling gate reads."""
    sst = eng.stats()
    sub = [e.stats() for e in eng.engines]

    def ssum(*path):
        out = 0
        for s in sub:
            v = s
            for k in path:
                v = v[k]
            out += v
        return out

    def occ_max(fam, key="occupancy"):
        vals = [m[fam][key] for m in (s["mixer"] for s in sub)
                if fam in m and not np.isnan(m[fam][key])]
        return max(vals) if vals else float("nan")

    drafted = ssum("speculative", "draft_tokens")
    accepted = ssum("speculative", "accepted_tokens")
    produced = sum(e._decode_produced for e in eng.engines)
    rows_ = sum(e._decode_rows for e in eng.engines)
    pq = ssum("prefix_cache", "queries")
    phits = ssum("prefix_cache", "hits")
    has_slots = any("slots" in s["mixer"] for s in sub)
    has_blocks = any("blocks" in s["mixer"] for s in sub)
    return {
        "arch": arch, "requests": n_requests, "shards": n_shards,
        "aggregate_decode_tokens_per_s":
            sst["aggregate_decode_tokens_per_s"],
        "per_shard": sst["per_shard"],
        "roles": sst["roles"],
        "handoff": sst["handoff"],
        "migrations": sst["migrations"],
        "requeued_lost": sst["requeued_lost"],
        "decode_tokens_per_s": sst["decoded_tokens"] / wall,
        "total_tokens_per_s":
            (sst["decoded_tokens"] + sst["prefill_tokens"]) / wall,
        "p50_latency_s": nearest_rank(lats, 50),
        "p99_latency_s": nearest_rank(lats, 99),
        "max_concurrent": max(s["max_concurrent_decode"] for s in sub),
        "acceptance_rate": accepted / drafted if drafted else 0.0,
        "tokens_per_decode_step": produced / rows_ if rows_ else 0.0,
        # prefill-role shards compile no spec graph (speedup reads 1),
        # so take the decode shards' figure
        "modeled_spec_speedup":
            max(s["photonic"]["modeled_spec_speedup"] for s in sub),
        "preemptions": ssum("preemptions"),
        "prefix_hit_rate": phits / pq if pq else 0.0,
        "skipped_prefill_tokens":
            ssum("prefix_cache", "skipped_prefill_tokens"),
        "snapshot_hits": ssum("prefix_cache", "snapshot_hits"),
        "snapshot_occupancy": (
            max(s["prefix_cache"].get("snapshot_occupancy", 0.0)
                for s in sub) if has_slots else float("nan")),
        "ring_reuse_rate": (occ_max("blocks", "ring_reuse_rate")
                            if has_blocks else 0.0),
        "block_occupancy": (occ_max("blocks") if has_blocks
                            else float("nan")),
        "slot_occupancy": (occ_max("slots") if has_slots
                           else float("nan")),
        "families": "+".join(f"{k}:{v['layout']}"
                             for k, v in sub[0]["mixer"].items()),
        "swap_s": ssum("swap", "swap_out_s") + ssum("swap", "swap_in_s"),
        "swaps": ssum("swap", "swap_outs") + ssum("swap", "swap_ins"),
        # fleet figure: N modeled accelerators decode concurrently
        "modeled_tokens_per_s":
            ssum("photonic", "modeled_tokens_per_s"),
        "modeled_effective_tokens_per_s":
            ssum("photonic", "modeled_effective_tokens_per_s"),
        "accelerator": sub[0]["photonic"]["accelerator"],
        "trace_path": trace_path,
        "replay": None,
        "replay_per_shard": replay_per_shard,
    }


def bench_slo(arch: str, *, smoke: bool, prompt_len: int, gen: int,
              seed: int = 0, precision: str = "bnn",
              accelerator: str = "OXBNN_50") -> dict:
    """SLO-policy A/B on one mixed trace: the same closed-loop workload
    — a bulk generation burst (throughput class, tenant budget capping
    it to one concurrent request), a batch of teacher-forced scoring
    requests (throughput class), and short interactive requests
    (latency class), submitted in that order so arrival order is the
    latency class's worst case — runs once under ``slo`` and once under
    ``fcfs``, plus a scoring-only baseline.

    Everything is measured in ENGINE STEPS (first_token_step /
    finish_step request marks), not wall-clock: greedy decoding makes
    the step sequence deterministic, so the --require-slo CI gate never
    flakes on machine speed.  Reported: per-class first-token p50/p99
    under both policies, and scoring throughput (scored tokens per step
    over the scoring span, first admit -> last finish) in the mixed run
    vs the scoring-only run — the backfill-retention figure."""
    cfg = configs.get_config(arch)
    if smoke:
        cfg = reduced(cfg)
    cfg = cfg.replace(precision=precision)
    params, _ = M.init(jax.random.PRNGKey(seed), cfg)

    chunk = min(16, prompt_len)
    n_bulk, n_score, n_lat = 3, 4, 3
    bulk_gen = 3 * gen                     # long enough to hog fcfs slots
    lat_gen = max(2, gen // 2)             # short interactive answers
    score_len = 6 * chunk                  # several chunks per pass
    max_len = max(score_len, prompt_len + bulk_gen)
    bulk_budget = prompt_len + bulk_gen + lat_gen // 2   # 1 concurrent
    tenants = (f"bulk=throughput:{bulk_budget},"
               f"score=throughput:0,web=latency:0")

    rng = np.random.default_rng(seed)
    bulk_prompts = rng.integers(0, cfg.vocab, (n_bulk, prompt_len),
                                dtype=np.int32)
    score_prompts = rng.integers(0, cfg.vocab, (n_score, score_len),
                                 dtype=np.int32)
    lat_prompts = rng.integers(0, cfg.vocab, (n_lat, prompt_len),
                               dtype=np.int32)

    bs = max(4, chunk // 2)

    def run(policy: str, scoring_only: bool):
        ecfg = EngineConfig(
            block_size=bs,
            num_blocks=1 + 4 * (-(-max_len // bs) + 1),
            max_batch=2, prefill_chunk=chunk, max_model_len=max_len,
            accelerator=accelerator, prefix_cache=False,
            policy=policy, tenants=tenants)
        eng = Engine(params, cfg, ecfg)
        rids: dict[str, list[int]] = {"bulk": [], "score": [], "web": []}
        t0 = time.perf_counter()
        if not scoring_only:
            for p in bulk_prompts:
                rids["bulk"].append(eng.submit(p, bulk_gen, tenant="bulk"))
        for p in score_prompts:
            rids["score"].append(eng.submit(p, 0, tenant="score",
                                            score=True))
        if not scoring_only:
            for p in lat_prompts:
                rids["web"].append(eng.submit(p, lat_gen, tenant="web"))
        eng.run()
        return eng, rids, time.perf_counter() - t0

    def ft_steps(eng, rids):
        return sorted(eng.requests[r].first_token_step
                      - eng.requests[r].submit_step for r in rids)

    def score_tps(eng, rids):
        reqs = [eng.requests[r] for r in rids]
        span = (max(r.finish_step for r in reqs)
                - min(r.admit_step for r in reqs) + 1)
        return sum(len(r.logprobs) for r in reqs) / max(span, 1), span

    slo_eng, slo_rids, slo_wall = run("slo", scoring_only=False)
    fcfs_eng, fcfs_rids, fcfs_wall = run("fcfs", scoring_only=False)
    only_eng, only_rids, only_wall = run("slo", scoring_only=True)

    slo_lat = ft_steps(slo_eng, slo_rids["web"])
    fcfs_lat = ft_steps(fcfs_eng, fcfs_rids["web"])
    mixed_tps, mixed_span = score_tps(slo_eng, slo_rids["score"])
    only_tps, only_span = score_tps(only_eng, only_rids["score"])
    st = slo_eng.stats()
    return {
        "arch": arch, "slo": True, "tenants": tenants,
        "classes": {"latency": n_lat, "throughput": n_bulk,
                    "scoring": n_score},
        "slo_latency_p50_first_token_steps": nearest_rank(slo_lat, 50),
        "slo_latency_p99_first_token_steps": nearest_rank(slo_lat, 99),
        "fcfs_latency_p50_first_token_steps": nearest_rank(fcfs_lat, 50),
        "fcfs_latency_p99_first_token_steps": nearest_rank(fcfs_lat, 99),
        "slo_throughput_p99_first_token_steps": nearest_rank(
            ft_steps(slo_eng, slo_rids["bulk"]), 99),
        "fcfs_throughput_p99_first_token_steps": nearest_rank(
            ft_steps(fcfs_eng, fcfs_rids["bulk"]), 99),
        "scoring_tokens_per_step_mixed": mixed_tps,
        "scoring_tokens_per_step_only": only_tps,
        "scoring_retention": mixed_tps / only_tps if only_tps else 0.0,
        "scoring_span_steps": {"mixed": mixed_span, "only": only_span},
        "scored_tokens": st["scoring"]["scored_tokens"],
        "score_passes": st["scoring"]["score_passes"],
        "modeled_scoring_tokens_per_s":
            st["photonic"]["modeled_scoring_tokens_per_s"],
        "wall_s": {"slo": slo_wall, "fcfs": fcfs_wall,
                   "scoring_only": only_wall},
    }


def write_bench_json(path: str, rows: list[dict], params: dict):
    """Persist the run as schema-versioned BENCH_serving.json."""
    doc = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "bench": "serving",
        "generated_by": "benchmarks/serving_bench.py",
        "params": params,
        "rows": [{k: v for k, v in r.items()
                  if not k.startswith("_")} for r in rows],
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, default=float)
    return doc


def check_bench_json(path: str) -> list[str]:
    """Validate a BENCH_serving.json against the schema contract;
    returns a list of problems (empty == valid)."""
    problems = []
    try:
        doc = json.load(open(path))
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable ({e})"]
    for k in BENCH_REQUIRED_KEYS:
        if k not in doc:
            problems.append(f"missing top-level key {k!r}")
    if doc.get("schema_version") != BENCH_SCHEMA_VERSION:
        problems.append(f"schema_version {doc.get('schema_version')!r} != "
                        f"{BENCH_SCHEMA_VERSION}")
    rows = doc.get("rows") or []
    if not rows:
        problems.append("no rows")
    for i, row in enumerate(rows):
        if row.get("slo"):
            # slo comparison rows carry the policy A/B columns instead
            # of the standard open-loop row contract
            for k in BENCH_REQUIRED_SLO_KEYS:
                if k not in row:
                    problems.append(
                        f"row {i} ({row.get('arch')}): slo row missing {k!r}")
            continue
        for k in BENCH_REQUIRED_ROW_KEYS:
            if k not in row:
                problems.append(f"row {i} ({row.get('arch')}): missing {k!r}")
        rep = row.get("replay")
        if rep is not None:
            for k in BENCH_REQUIRED_REPLAY_KEYS:
                if k not in rep:
                    problems.append(f"row {i} replay: missing {k!r}")
        if row.get("shards", 1) > 1:
            per = row.get("per_shard") or []
            if len(per) != row["shards"]:
                problems.append(
                    f"row {i} ({row.get('arch')}): {len(per)} per_shard "
                    f"entries for shards={row['shards']}")
            if "aggregate_decode_tokens_per_s" not in row:
                problems.append(f"row {i}: missing "
                                "'aggregate_decode_tokens_per_s'")
            for j, p in enumerate(per):
                for k in BENCH_REQUIRED_SHARD_KEYS:
                    if k not in p:
                        problems.append(
                            f"row {i} per_shard[{j}]: missing {k!r}")
            for j, rp in enumerate(row.get("replay_per_shard") or []):
                for k in BENCH_REQUIRED_REPLAY_KEYS:
                    if k not in rp:
                        problems.append(
                            f"row {i} replay_per_shard[{j}]: missing {k!r}")
        if row.get("disaggregated"):
            for k in BENCH_REQUIRED_ROLE_KEYS:
                if k not in row:
                    problems.append(
                        f"row {i} ({row.get('arch')}): disaggregated "
                        f"row missing {k!r}")
            for k in BENCH_REQUIRED_HANDOFF_KEYS:
                if k not in (row.get("handoff") or {}):
                    problems.append(
                        f"row {i} ({row.get('arch')}): handoff report "
                        f"missing {k!r}")
            if row.get("token_identical_to_mixed") is not True:
                problems.append(
                    f"row {i} ({row.get('arch')}): disaggregated tokens "
                    "diverged from the mixed baseline")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs, tiny request stream")
    ap.add_argument("--archs", default=None,
                    help="comma-separated arch ids")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--precision", default="bnn")
    ap.add_argument("--accelerator", default="OXBNN_50")
    ap.add_argument("--prefix-cache", default=False,
                    action=argparse.BooleanOptionalAction,
                    help="content-addressed prompt prefix reuse")
    ap.add_argument("--preempt-policy", default="swap",
                    choices=["swap", "recompute"])
    ap.add_argument("--shared-frac", type=float, default=0.5,
                    help="fraction of requests drawing a shared prefix")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0 = off)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--require-snapshot-hits", action="store_true",
                    help="exit non-zero unless every SSM/hybrid row "
                         "reports snapshot hits and skipped prefill "
                         "tokens (CI smoke assertion)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record each arch's measured window to "
                         "DIR/trace_<arch>.jsonl")
    ap.add_argument("--replay-photonic", action="store_true",
                    help="re-price recorded steps through the photonic "
                         "simulator; adds simulated tok/s + FPS")
    ap.add_argument("--shards", type=int, default=1,
                    help="decode shards over the data axis (simulate "
                         "hosts with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--roles", default=None, metavar="P:D",
                    help="disaggregated prefill/decode comparison: run "
                         "each arch once as P+D mixed shards and once "
                         "as P prefill + D decode workers over the "
                         "same prompt stream; reports decode tok/s, "
                         "p99 first-token latency and modeled transfer "
                         "ms side by side, and FAILS unless the two "
                         "topologies emit identical tokens (overrides "
                         "--shards/--shard-sweep)")
    ap.add_argument("--shard-sweep", default=None, metavar="N,N,...",
                    help="run each arch at several shard counts, one "
                         "row per count (e.g. 1,2,4); overrides "
                         "--shards")
    ap.add_argument("--require-scaling", type=float, default=None,
                    metavar="X",
                    help="CI gate over a --shard-sweep: aggregate "
                         "per-host decode tok/s must be monotone "
                         "nondecreasing in the shard count (2%% "
                         "tolerance) and the 2-shard factor over "
                         "1 shard must reach X")
    ap.add_argument("--slo", action="store_true",
                    help="add a per-arch slo-policy comparison row: a "
                         "mixed latency+throughput+scoring trace run "
                         "under slo vs fcfs vs scoring-only (steps-"
                         "based, deterministic)")
    ap.add_argument("--require-slo", action="store_true",
                    help="CI gate (implies --slo): the slo policy's "
                         "latency-class p99 first-token must beat "
                         "fcfs's, and mixed-trace scoring throughput "
                         "must retain >= 90%% of the scoring-only run")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="persist results as schema-versioned JSON")
    ap.add_argument("--check-json", default=None, metavar="PATH",
                    help="validate an existing bench JSON and exit "
                         "(CI schema gate; no benchmark is run)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.check_json:
        problems = check_bench_json(args.check_json)
        if problems:
            raise SystemExit("bench JSON schema violations:\n  "
                             + "\n  ".join(problems))
        print(f"[bench] {args.check_json}: schema v{BENCH_SCHEMA_VERSION} OK")
        return

    if args.trace:
        os.makedirs(args.trace, exist_ok=True)

    archs = args.archs.split(",") if args.archs else SMOKE_ARCHS
    n = args.requests or (6 if args.smoke else 32)
    rate = args.rate or (4.0 if args.smoke else 2.0)
    plen = args.prompt_len or (8 if args.smoke else 64)
    gen = args.gen or (8 if args.smoke else 64)

    def occ(v):
        return "   -" if np.isnan(v) else f"{100 * v:>3.0f}%"

    print(f"{'arch':<22} {'dec tok/s':>9} {'tot tok/s':>9} {'p50(s)':>8} "
          f"{'p99(s)':>8} {'maxconc':>8} {'evict':>6} {'hit%':>6} "
          f"{'acc%':>6} {'tok/step':>9} {'reuse%':>7} "
          f"{'blk-occ':>8} {'slot-occ':>9} {'snap-occ':>9} "
          f"{'swap(ms)':>9} "
          f"{'modeled tok/s':>14} {'eff tok/s':>12} {'spec-x':>7}")
    shard_counts = ([int(x) for x in args.shard_sweep.split(",")]
                    if args.shard_sweep else [args.shards])
    if args.roles:
        # mixed oracle first, disaggregated second — the identity gate
        # compares the second run's tokens against the first's
        p_n, d_n = (int(x) for x in args.roles.split(":"))
        total = p_n + d_n
        variants = [(f"@{total}sh-mixed", total, None),
                    (f"@roles{p_n}p{d_n}d", total, args.roles)]
    else:
        variants = [
            (f"@{n_sh}sh" if len(shard_counts) > 1 or n_sh > 1 else "",
             n_sh, None)
            for n_sh in shard_counts]
    failures = []
    diverged = []
    slo_bad = []
    rows = []
    for arch in archs:
      mixed_row = None
      for suffix, n_sh, role_spec in variants:
        tpath = (os.path.join(
                     args.trace,
                     f"trace_{arch.replace('/', '_')}"
                     f"{suffix.replace('@', '_')}.jsonl")
                 if args.trace else None)
        r = bench_arch(arch, smoke=args.smoke, n_requests=n, rate_hz=rate,
                       prompt_len=plen, gen=gen, max_batch=args.max_batch,
                       precision=args.precision,
                       accelerator=args.accelerator,
                       prefix_cache=args.prefix_cache,
                       preempt_policy=args.preempt_policy,
                       shared_frac=args.shared_frac,
                       spec_k=args.spec_k, temperature=args.temperature,
                       trace_path=tpath,
                       replay_photonic=args.replay_photonic,
                       n_shards=n_sh, roles=role_spec)
        rows.append(r)
        if args.roles and role_spec is None:
            mixed_row = r
        elif role_spec is not None:
            ident = r["_outputs"] == mixed_row["_outputs"]
            r["disaggregated"] = True
            r["token_identical_to_mixed"] = ident
            ho = r["handoff"]
            print(f"[bench] {arch} roles={role_spec} vs mixed@{n_sh}: "
                  f"decode tok/s "
                  f"{r['aggregate_decode_tokens_per_s']:.1f} vs "
                  f"{mixed_row['aggregate_decode_tokens_per_s']:.1f} | "
                  f"p99 first-token "
                  f"{1e3 * r['p99_first_token_s']:.1f}ms vs "
                  f"{1e3 * mixed_row['p99_first_token_s']:.1f}ms | "
                  f"transfer "
                  f"{ho['modeled_transfer_ms_per_handoff']:.4f}ms/handoff "
                  f"x{ho['handoffs']} | tokens "
                  f"{'identical' if ident else 'DIVERGED'}")
            if not ident:
                diverged.append(arch)
        if n_sh > 1:
            per = "  ".join(
                f"s{p['shard']}({p['role'][0]}):"
                f"{p['decode_tokens_per_s']:.1f}"
                for p in r["per_shard"])
            print(f"{arch + suffix:<22} aggregate per-host decode tok/s="
                  f"{r['aggregate_decode_tokens_per_s']:>9.1f}  [{per}]")
        print(f"{r['arch'] + suffix:<22} {r['decode_tokens_per_s']:>9.1f} "
              f"{r['total_tokens_per_s']:>9.1f} "
              f"{r['p50_latency_s']:>8.3f} {r['p99_latency_s']:>8.3f} "
              f"{r['max_concurrent']:>8d} {r['preemptions']:>6d} "
              f"{100 * r['prefix_hit_rate']:>6.1f} "
              f"{100 * r['acceptance_rate']:>6.1f} "
              f"{r['tokens_per_decode_step']:>9.2f} "
              f"{100 * r['ring_reuse_rate']:>7.1f} "
              f"{occ(r['block_occupancy']):>8} "
              f"{occ(r['slot_occupancy']):>9} "
              f"{occ(r['snapshot_occupancy']):>9} "
              f"{1e3 * r['swap_s']:>9.2f} "
              f"{r['modeled_tokens_per_s']:>14.0f} "
              f"{r['modeled_effective_tokens_per_s']:>12.0f} "
              f"{r['modeled_spec_speedup']:>7.2f}")
        if args.require_snapshot_hits and \
                not np.isnan(r["snapshot_occupancy"]) and (
                    r["snapshot_hits"] == 0
                    or r["skipped_prefill_tokens"] == 0):
            failures.append(arch)
      if args.slo or args.require_slo:
        sr = bench_slo(arch, smoke=args.smoke, prompt_len=plen, gen=gen,
                       precision=args.precision,
                       accelerator=args.accelerator)
        rows.append(sr)
        print(f"[bench] {arch} slo-vs-fcfs: latency-class first-token "
              f"p50/p99 {sr['slo_latency_p50_first_token_steps']}/"
              f"{sr['slo_latency_p99_first_token_steps']} steps vs "
              f"{sr['fcfs_latency_p50_first_token_steps']}/"
              f"{sr['fcfs_latency_p99_first_token_steps']} | scoring "
              f"retention {100 * sr['scoring_retention']:.1f}% "
              f"({sr['scoring_tokens_per_step_mixed']:.1f} vs "
              f"{sr['scoring_tokens_per_step_only']:.1f} tok/step, "
              f"{sr['scored_tokens']} scored) | modeled scoring "
              f"{sr['modeled_scoring_tokens_per_s']:.0f} tok/s")
        if args.require_slo:
            if not (sr["slo_latency_p99_first_token_steps"]
                    < sr["fcfs_latency_p99_first_token_steps"]):
                slo_bad.append(
                    f"{arch}: slo latency p99 first-token "
                    f"{sr['slo_latency_p99_first_token_steps']} steps "
                    f">= fcfs {sr['fcfs_latency_p99_first_token_steps']}")
            if sr["scoring_retention"] < 0.9:
                slo_bad.append(
                    f"{arch}: mixed-trace scoring retained only "
                    f"{100 * sr['scoring_retention']:.1f}% of the "
                    "scoring-only throughput (< 90%)")
    if args.replay_photonic:
        from repro.serving import format_report
        for r in rows:
            if r.get("replay") is not None:
                print(format_report(r["replay"]))
            for rep in r.get("replay_per_shard") or []:
                print(f"[replay] shard {rep.get('shard')}:")
                print(format_report(rep))
    if args.require_scaling is not None and len(shard_counts) > 1:
        bad = []
        for arch in archs:
            series = sorted((r["shards"], r["aggregate_decode_tokens_per_s"])
                            for r in rows
                            if r["arch"] == arch and not r.get("slo"))
            for (a, ra), (b, rb) in zip(series, series[1:]):
                if rb < 0.98 * ra:
                    bad.append(f"{arch}: {rb:.1f} tok/s at {b} shards < "
                               f"{ra:.1f} at {a} (not monotone)")
            by_n = dict(series)
            if 1 in by_n and 2 in by_n and by_n[1] > 0:
                factor = by_n[2] / by_n[1]
                if factor < args.require_scaling:
                    bad.append(f"{arch}: 2-shard factor {factor:.2f}x < "
                               f"required {args.require_scaling}x")
                else:
                    print(f"[bench] {arch}: 2-shard scaling "
                          f"{factor:.2f}x >= {args.require_scaling}x OK")
        if bad:
            raise SystemExit("--require-scaling violations:\n  "
                             + "\n  ".join(bad))
    if args.bench_json:
        params = {"smoke": args.smoke, "requests": n, "rate_hz": rate,
                  "prompt_len": plen, "gen": gen,
                  "max_batch": args.max_batch,
                  "precision": args.precision,
                  "accelerator": args.accelerator,
                  "prefix_cache": bool(args.prefix_cache),
                  "shared_frac": args.shared_frac, "spec_k": args.spec_k,
                  "temperature": args.temperature,
                  "replay_photonic": args.replay_photonic,
                  "shards": shard_counts, "roles": args.roles,
                  "slo": bool(args.slo or args.require_slo)}
        write_bench_json(args.bench_json, rows, params)
        print(f"[bench] wrote {args.bench_json} "
              f"(schema v{BENCH_SCHEMA_VERSION}, {len(rows)} rows)")
    if slo_bad:
        raise SystemExit("--require-slo violations:\n  "
                         + "\n  ".join(slo_bad))
    if diverged:
        raise SystemExit(
            f"--roles: disaggregated tokens diverged from the mixed "
            f"baseline on {diverged} — the prefill->decode handoff must "
            "be bit-exact")
    if failures:
        raise SystemExit(
            f"--require-snapshot-hits: no snapshot reuse on {failures} "
            "(shared-prefix traffic should hit the slot snapshot index)")


if __name__ == "__main__":
    main()
