"""Serving launcher: thin CLI over the continuous-batching engine.

The OXBNN payoff path: with --precision bnn every projection runs the
packed XNOR-popcount GEMM (1-bit weights/activations), the paper's
inference mode.  Requests flow through repro.serving.Engine — paged
mixer-state cache, chunked prefill interleaved with decode, per-step
admission — and the photonic cost model reports modeled accelerator
tokens/s next to wall-clock.

Every arch family runs the paged engine: full-attention GQA pages KV
blocks, MLA pages compressed latents, sliding-window attention runs
ring-buffer block tables, and SSM keeps per-request recurrent slots
(see docs/serving.md "Mixer-state layouts").  ``engine="legacy"`` keeps
the original token-by-token batch loop ONLY as the differential-test
oracle — tests assert the engine reproduces its greedy tokens exactly.

Per-request sampling (--temperature/--top-k/--top-p/--sampling-seed,
--stop-token for early termination) selects tokens inside the jitted
steps with (seed, position) PRNG keys; --spec-k enables prompt-lookup
speculative decoding (multi-token verify on the XNOR path, modeled
photonic speedup reported next to acceptance rate).

Streaming front-end (--stream): the same engine behind an asyncio
server loop (serving/frontend.py) — requests join mid-flight, committed
tokens stream per request (speculative commits arrive as bursts),
--cancel-after drops one request mid-decode, and --score runs
teacher-forced logprob/ppl scoring requests alongside generation.
--tenants "name=class:budget,..." enables the multi-tenant slo
scheduler policy (latency vs throughput classes, per-tenant token
budgets — serving/policy.py) and assigns requests round-robin.

Usage (CPU smoke, reduced widths):
  PYTHONPATH=src python -m repro.launch.serve --arch bnn-lm-100m --smoke \
      --batch 4 --prompt-len 16 --gen 16 --precision bnn
Usage (one TPU chip, published widths; --shards N puts each shard on
its own device when N devices exist):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --precision bnn
"""
from __future__ import annotations

import argparse
import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import device_mesh
from repro.dist import sharding as S
from repro.layers import common as C
from repro.models import transformer as M
from repro.serving import (Engine, EngineConfig, Frontend, SamplingParams,
                           parse_tenants, tenants_arg)


def _setup(arch, smoke, precision, seed):
    """Config (``reduced`` widths under ``smoke``), random params and
    the 1x1 mesh of the first device present: an engine's datapath is
    single-device (ShardedEngine places each shard on its own)."""
    cfg = configs.get_config(arch)
    if smoke:
        cfg = reduced(cfg)
    if precision:
        cfg = cfg.replace(precision=precision)
    C.set_sharding_context(device_mesh(jax.devices()[:1]),
                           S.rules_decode(False))
    params, _ = M.init(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _prompts(cfg, batch, prompt_len, seed):
    return jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (batch, prompt_len), 0, cfg.vocab)


def serve_legacy(arch: str, *, smoke: bool = False,
                 batch: int = 4, prompt_len: int = 16, gen: int = 16,
                 precision: str | None = None, seed: int = 0,
                 greedy: bool = True):
    """Reference loop: batched dense-slot cache, token-by-token prefill."""
    try:
        cfg, params = _setup(arch, smoke, precision, seed)
        max_len = prompt_len + gen
        caches = M.init_cache(cfg, batch, max_len)
        prompts = _prompts(cfg, batch, prompt_len, seed)
        decode = jax.jit(lambda p, c, tok, ln: M.decode_step(p, cfg, tok, c, ln))

        t0 = time.time()
        tok = prompts[:, :1]
        out_tokens = [tok]
        for i in range(max_len - 1):
            logits, caches = decode(params, caches, tok, jnp.int32(i))
            if i + 1 < prompt_len:
                tok = prompts[:, i + 1:i + 2]
            else:
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32) \
                    if greedy else jax.random.categorical(
                        jax.random.PRNGKey(i), logits[:, -1]).astype(jnp.int32)[:, None]
            out_tokens.append(tok)
        seqs = jnp.concatenate(out_tokens, axis=1)
        dt = time.time() - t0
        print(f"[serve:legacy] {arch} precision={cfg.precision} batch={batch} "
              f"tokens/s={batch * (max_len - 1) / dt:.1f}")
        return np.asarray(seqs)
    finally:
        C.clear_sharding_context()


def _serve_stream(eng, prompts, gen, sampling, *, tenants, score,
                  cancel_after, verbose):
    """Drive the engine through the asyncio front-end.

    Submits every prompt round-robin over the named tenants, consumes
    each request's committed-token stream concurrently, optionally
    cancels the LAST request mid-decode after ``cancel_after`` streamed
    tokens, and runs ``score`` teacher-forced scoring requests
    alongside.  Returns (rids, {rid: prompt+generated}) with cancelled
    requests omitted from the dict.
    """
    names = list(parse_tenants(tenants)) or ["default"]
    batch = len(prompts)

    async def go():
        got: dict[int, list[int]] = {}
        scored: list[dict] = []
        async with Frontend(eng) as fe:
            rids = [fe.submit(np.asarray(prompts[b], np.int32), gen,
                              sampling=sampling(b),
                              tenant=names[b % len(names)])
                    for b in range(batch)]
            cancel_rid = rids[-1] if cancel_after and rids else None

            async def consume(rid):
                toks: list[int] = []
                async for burst in fe.stream(rid):
                    toks.extend(burst)
                    if rid == cancel_rid and len(toks) >= cancel_after:
                        fe.cancel(rid)
                got[rid] = toks

            async def run_score(i):
                scored.append(await fe.score(
                    np.asarray(prompts[i % batch], np.int32),
                    tenant=names[i % len(names)]))

            await asyncio.gather(*(consume(r) for r in rids),
                                 *(run_score(i) for i in range(score)))
        return rids, got, scored

    rids, got, scored = asyncio.run(go())
    out: dict[int, np.ndarray] = {}
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        cancelled = req.state.name == "CANCELLED"
        if verbose:
            tag = " CANCELLED" if cancelled else ""
            print(f"[serve:stream] rid={rid} tenant={req.tenant} "
                  f"class={req.slo_class} streamed={len(got[rid])}{tag}")
        if not cancelled:
            out[rid] = np.concatenate(
                [np.asarray(prompts[b], np.int32),
                 np.asarray(got[rid], np.int32)])
    if verbose:
        for s in scored:
            print(f"[serve:stream] score rid={s['rid']} "
                  f"tokens={s['scored_tokens']} ppl={s['ppl']:.3f}")
    return rids, out


def serve(arch: str, *, smoke: bool = False,
          batch: int = 4, prompt_len: int = 16, gen: int = 16,
          precision: str | None = None, seed: int = 0,
          greedy: bool = True, engine: str = "paged",
          block_size: int | None = None, prefill_chunk: int | None = None,
          accelerator: str = "OXBNN_50", verbose: bool = True,
          prefix_cache: bool = True, preempt_policy: str = "swap",
          snapshot_slots: int = 0,
          temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
          sampling_seed: int = 0, stop: tuple[int, ...] = (),
          spec_k: int = 0, spec_ngram: int = 3,
          attn_impl: str = "auto", bnn_impl: str = "auto",
          trace: str | None = None, replay_photonic: bool = False,
          capture_logits: bool = False, shards: int = 1,
          roles: str | None = None, policy: str | None = None,
          tenants: str = "", stream: bool = False, score: int = 0,
          cancel_after: int = 0):
    """Serve ``batch`` synthetic requests; returns (batch, prompt+gen)
    token ids (prompt prefix included, matching the legacy loop).  With
    stop tokens the generations can end early — the result is then a
    ragged list instead of a stacked array.  ``shards > 1`` shards the
    decode batch over the data axis (one engine per shard — see
    serving/sharded.py); output stays token-identical to 1 shard.
    ``roles`` disaggregates the shards into prefill/decode workers
    ("P:D" counts, e.g. "1:2", or explicit comma names); tokens remain
    identical to the mixed topology.

    ``stream`` drives the same engine through the asyncio front-end:
    requests stream their committed tokens concurrently, ``score``
    extra teacher-forced scoring requests run alongside, and
    ``cancel_after`` cancels one request after that many streamed
    tokens.  ``tenants`` turns on the slo policy (unless ``policy``
    says otherwise) and spreads requests round-robin over the named
    tenants.  Uncancelled streamed output is byte-identical to the
    batch path for the same flags."""
    if engine == "legacy":
        return serve_legacy(arch, smoke=smoke,
                            batch=batch, prompt_len=prompt_len, gen=gen,
                            precision=precision, seed=seed, greedy=greedy)
    try:
        cfg, params = _setup(arch, smoke, precision, seed)
        max_len = prompt_len + gen
        bs = block_size or max(8, min(32, prompt_len))
        if policy is None:
            policy = "slo" if tenants else "fcfs"
        ecfg = EngineConfig(
            block_size=bs,
            num_blocks=1 + batch * (-(-max_len // bs) + 1),
            max_batch=max(batch, 1),
            prefill_chunk=prefill_chunk or min(16, prompt_len),
            max_model_len=max_len,
            accelerator=accelerator,
            prefix_cache=prefix_cache,
            preempt_policy=preempt_policy,
            snapshot_slots=snapshot_slots,
            spec_k=spec_k, spec_ngram=spec_ngram,
            attn_impl=attn_impl, bnn_impl=bnn_impl,
            policy=policy, tenants=tenants_arg(tenants))
        if shards > 1:
            from repro.serving import ShardedEngine
            eng = ShardedEngine(
                params, cfg, ecfg, shards,
                meshes=S.shard_meshes(shards),
                rules=S.rules_decode(False), roles=roles)
        else:
            eng = Engine(params, cfg, ecfg)
        if trace or replay_photonic:
            eng.start_trace(trace, ring=1 << 16,
                            capture_logits=capture_logits)
        prompts = np.asarray(_prompts(cfg, batch, prompt_len, seed))

        def _sampling(b):
            # temperature speaks for itself (0 == greedy); the
            # ``greedy`` flag only selects the legacy loop's mode above
            return SamplingParams(temperature=temperature, top_k=top_k,
                                  top_p=top_p, seed=sampling_seed + b,
                                  stop=stop)

        if stream:
            rids, out = _serve_stream(
                eng, prompts, gen, _sampling, tenants=tenants,
                score=score, cancel_after=cancel_after, verbose=verbose)
        else:
            rids = [eng.submit(prompts[b], gen, sampling=_sampling(b))
                    for b in range(batch)]
            out = eng.run()
        stats = eng.stats()
        if trace or replay_photonic:
            shard_records = ([e.tracer.events() for e in eng.engines]
                             if shards > 1 else [eng.tracer.events()])
            eng.stop_trace()
            if trace and verbose:
                print(f"[serve] trace -> {trace} "
                      f"(view: python -m repro.launch.trace_view {trace})")
            if replay_photonic:
                from repro.serving import format_report, replay_trace
                if shards > 1:
                    for recs in shard_records:
                        print(format_report(replay_trace(
                            recs, cfg=cfg, accelerator=accelerator)))
                else:
                    rep = replay_trace(trace if trace else shard_records[0],
                                       cfg=cfg, accelerator=accelerator)
                    print(format_report(rep))
        if verbose and shards > 1:
            for row in stats["per_shard"]:
                print(f"[serve] shard {row['shard']} ({row['role']})"
                      f"{'' if row['alive'] else ' (dead)'}: "
                      f"decoded={row['decoded_tokens']} "
                      f"decode-tokens/s={row['decode_tokens_per_s']:.1f} "
                      f"finished={row['finished']} "
                      f"swap_losts={row['swap_losts']}")
            print(f"[serve] {arch} precision={cfg.precision} "
                  f"shards={shards} batch={batch} aggregate "
                  f"decode-tokens/s="
                  f"{stats['aggregate_decode_tokens_per_s']:.1f} "
                  f"migrations={stats['migrations']} "
                  f"requeued_lost={stats['requeued_lost']}")
            ho = stats["handoff"]
            if ho["handoffs"]:
                print(f"[serve] handoffs={ho['handoffs']} "
                      f"bytes={ho['handoff_bytes']} "
                      f"modeled-transfer="
                      f"{1e3 * ho['modeled_transfer_s']:.3f}ms "
                      f"@{ho['link_gbps']:.0f}Gb/s "
                      f"(host-copy wall "
                      f"{1e3 * ho['host_copy_wall_s']:.1f}ms)")
        elif verbose:
            ph, pc, sw = (stats["photonic"], stats["prefix_cache"],
                          stats["swap"])
            print(f"[serve] {arch} precision={cfg.precision} batch={batch} "
                  f"decode-tokens/s={stats['decode_tokens_per_s']:.1f} "
                  f"total-tokens/s={stats['total_tokens_per_s']:.1f} "
                  f"steps={stats['steps']} "
                  f"max_concurrent={stats['max_concurrent_decode']}")
            sp = stats["speculative"]
            if sp["enabled"]:
                print(f"[serve] speculative k={sp['spec_k']}: "
                      f"acceptance={sp['acceptance_rate']:.2f} "
                      f"tokens/step={sp['tokens_per_decode_step']:.2f} "
                      f"modeled-speedup="
                      f"{ph['modeled_spec_speedup']:.2f}x")
            for fam, mx in stats["mixer"].items():
                occ = 100 * mx["occupancy"]
                extra = (f" ring_blocks={mx['ring_blocks']} "
                         f"reuse={100 * mx['ring_reuse_rate']:.0f}%"
                         if mx.get("ring_blocks") else "")
                print(f"[serve] mixer[{fam}] layout={mx['layout']} "
                      f"layers={mx['layers']} occupancy={occ:.0f}%{extra}")
            print(f"[serve] prefix-cache "
                  f"{'on' if pc['enabled'] else 'off'}: "
                  f"hit-rate={pc['hit_rate']:.2f} "
                  f"skipped_prefill={pc['skipped_prefill_tokens']} "
                  f"cow={pc['cow_copies']}; "
                  f"swaps out/in={sw['swap_outs']}/{sw['swap_ins']}")
            if eng.cache.ssm is not None and pc["enabled"]:
                print(f"[serve] slot-snapshots: "
                      f"hits={pc['snapshot_hits']} "
                      f"stores={pc['snapshot_stores']} "
                      f"cached={pc['cached_snapshots']} "
                      f"occupancy={100 * pc['snapshot_occupancy']:.0f}% "
                      f"readopted={sw['readopted_snapshots']}")
            print(f"[serve] modeled {ph['accelerator']}: "
                  f"{ph['modeled_tokens_per_s']:.0f} tokens/s "
                  f"(effective {ph['modeled_effective_tokens_per_s']:.0f} "
                  f"with pipelined prefill + prefix credit; bottleneck: "
                  f"{ph['bottleneck_stage']})")
        seqs = [out[r] for r in rids if r in out]   # cancelled omitted
        if len({len(s) for s in seqs}) > 1:      # early stop: ragged
            return seqs
        return np.stack(seqs)
    finally:
        C.clear_sharding_context()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bnn-lm-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced() widths (CPU tests); default: the "
                         "published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--engine", default="paged", choices=["paged", "legacy"])
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--accelerator", default="OXBNN_50")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="content-addressed prompt prefix reuse")
    ap.add_argument("--preempt-policy", default="swap",
                    choices=["swap", "recompute"],
                    help="swap-to-host (default) or recompute-on-resume")
    ap.add_argument("--snapshot-slots", type=int, default=0,
                    help="recurrent prefix-snapshot pool rows for "
                         "SSM/hybrid stacks (0 = 2 * batch; gated by "
                         "--prefix-cache)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus filter (1.0 = off)")
    ap.add_argument("--sampling-seed", type=int, default=0,
                    help="base per-request sampling seed")
    ap.add_argument("--stop-token", type=int, action="append", default=[],
                    help="stop/eos token id (repeatable)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0 = off)")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="paged-attention kernel: fused Pallas, XLA "
                         "oracle, or auto (pallas on TPU)")
    ap.add_argument("--bnn-impl", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="packed BNN GEMM: fused Pallas chain, XLA "
                         "oracle, or auto (pallas on TPU)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="max n-gram for prompt-lookup drafting")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a structured JSONL engine trace "
                         "(view with python -m repro.launch.trace_view)")
    ap.add_argument("--replay-photonic", action="store_true",
                    help="replay the recorded steps through the "
                         "photonic simulator (analytic-vs-simulated)")
    ap.add_argument("--shards", type=int, default=1,
                    help="decode shards over the data axis (1 = single "
                         "engine; simulate hosts with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--roles", default=None, metavar="P:D",
                    help="disaggregate the shards into prefill/decode "
                         "workers: 'P:D' counts (e.g. 1:2) or explicit "
                         "comma names (prefill,decode,mixed); must "
                         "cover --shards; default all-mixed")
    ap.add_argument("--stream", action="store_true",
                    help="drive the engine through the asyncio "
                         "front-end: per-request token streams, "
                         "mid-flight joins, cancellation, scoring")
    ap.add_argument("--policy", default=None,
                    choices=["fcfs", "priority", "slo"],
                    help="scheduler policy (default: slo when "
                         "--tenants is set, else fcfs)")
    ap.add_argument("--tenants", default="", metavar="NAME=CLASS:BUDGET",
                    help="comma-separated tenant spec, e.g. "
                         "'web=latency:0,bulk=throughput:2048'; "
                         "requests are assigned round-robin")
    ap.add_argument("--score", type=int, default=0,
                    help="teacher-forced scoring requests to run "
                         "alongside generation (requires --stream)")
    ap.add_argument("--cancel-after", type=int, default=0,
                    help="cancel the last request after this many "
                         "streamed tokens (requires --stream)")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, smoke=args.smoke,
          batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
          precision=args.precision, engine=args.engine,
          block_size=args.block_size, prefill_chunk=args.prefill_chunk,
          accelerator=args.accelerator, prefix_cache=args.prefix_cache,
          preempt_policy=args.preempt_policy,
          snapshot_slots=args.snapshot_slots,
          greedy=args.temperature <= 0,     # legacy-loop sampling mode
          temperature=args.temperature,
          top_k=args.top_k, top_p=args.top_p,
          sampling_seed=args.sampling_seed, stop=tuple(args.stop_token),
          spec_k=args.spec_k, spec_ngram=args.spec_ngram,
          attn_impl=args.attn_impl, bnn_impl=args.bnn_impl,
          trace=args.trace, replay_photonic=args.replay_photonic,
          shards=args.shards, roles=args.roles,
          policy=args.policy, tenants=args.tenants, stream=args.stream,
          score=args.score, cancel_after=args.cancel_after)


if __name__ == "__main__":
    main()
