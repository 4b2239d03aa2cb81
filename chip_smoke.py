#!/usr/bin/env python3
"""Smoke run of the serving engine on TPU at published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four ShardedEngine replicas

One chip: qwen1.5-0.5b (24 layers, d_model 1024, vocab 151936, random
weights from --seed) on the paper's datapath (precision="bnn": the
fused binarize->pack->XNOR-popcount GEMM and the paged-attention Pallas
kernel, both compiled) serves 4 requests through ``Engine`` — chunked
prefill, batched decode over several decode buckets.  Each kernel is
then checked against its XLA oracle on the same chip: the BNN GEMM for
bit-identical outputs at every qwen1.5-0.5b projection shape, the
attention kernel by comparing logits on the float ("bf16") datapath,
which serves one short request through ``Engine``.

--four-chips runs only the replica path: 4 ``ShardedEngine`` shards of
qwen1.5-0.5b/bnn, one per chip, checked for placement and for token
identity against one ``Engine`` on the same prompts.

Everything printed before the last line is a smoke-run report, not a
measurement.  The last line is one JSON object naming the device.  With
no TPU the script exits non-zero before doing anything: it never runs on
the CPU.  One process drives every chip it uses.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen1.5-0.5b"
N_REQUESTS = 4              # decode buckets 1, 2 and 4
PROMPT_LEN = 128
GEN = 16
MAX_MODEL_LEN = 160         # prompt + generation bound of every engine
PREFILL_CHUNK = 64          # 128-token prompts prefill in two chunks
BLOCK_SIZE = 16
TRACED_STEPS = 3            # steps whose logits are captured: the
                            # first request's two prefill chunks, then
                            # its first decode beside the next prefill
# Every one-chip phase runs float32 matmuls at full precision.  The
# TPU's default for a float32 matmul is one bfloat16 pass, whose rounding
# differs between a kernel and its XLA oracle; one setting for the whole
# run keeps each engine and its oracle on the same rounding.
PRECISION = "highest"
# Why the bnn engine's logits are not compared with an oracle engine's:
# one binarized activation whose float inputs differ in the last bits
# flips its sign, which moves every output of its GEMM by 2*alpha (~1/16
# of a 1024-term sum), flips more signs downstream, and leaves logits
# unrelated after 24 layers.  Two different step programs round some
# float op differently (measured on a v5e: swapping only the GEMM path
# left one request's logits unrelated from its first chunk while
# another's agreed to 2e-7).  So the BNN GEMM is checked by itself,
# where it must be bit-identical to the XLA packed math, and attention
# on the float datapath, where differences do not grow that way.
#
# Attention oracle: float datapath, attn_impl="xla".  What remains is
# float32 summation order, ~1e-6 of each attention output, carried
# through 24 residual layers.
FLOAT_RTOL = 1e-4
FLOAT_GEN = 4
FLOAT_TRACED_STEPS = 3      # two prefill chunks, one decode step
FOUR_CHIP_LAYERS = 4        # each shard compiles its own programs for
                            # its own chip, so compile time grows with
                            # shards x depth; identity needs no depth
FOUR_CHIP_REQUESTS = 8      # two per shard
FOUR_CHIP_GEN = 16


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


class CompileClock:
    """Seconds spent compiling (or reading the persistent cache), and
    cache hits/misses, per phase, from JAX's monitoring events."""

    def __init__(self, monitoring):
        self.phase = None
        self.secs: dict[str, float] = {}
        self.hits: dict[str, int] = {}
        self.misses: dict[str, int] = {}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if (self.phase
                and event == "/jax/core/compile/backend_compile_duration"):
            self.secs[self.phase] = self.secs.get(self.phase, 0.0) + secs

    def _event(self, event, **_):
        if not self.phase:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.hits[self.phase] = self.hits.get(self.phase, 0) + 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses[self.phase] = self.misses.get(self.phase, 0) + 1

    @contextlib.contextmanager
    def __call__(self, phase: str):
        self.phase = phase
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phase = None
            wall = time.perf_counter() - t0
            print(f"[smoke] phase {phase}: wall {wall:.1f}s "
                  f"compile {self.secs.get(phase, 0.0):.1f}s "
                  f"cache hits {self.hits.get(phase, 0)} "
                  f"misses {self.misses.get(phase, 0)}", flush=True)


def _prompts(vocab: int, seed: int, n: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n, PROMPT_LEN)).astype(np.int32)


def _engine_config(n: int, **kw):
    """Engine for ``n`` concurrent requests, block pool sized to hold
    them all at ``MAX_MODEL_LEN``."""
    from repro.serving import EngineConfig
    return EngineConfig(
        block_size=BLOCK_SIZE,
        num_blocks=1 + n * -(-MAX_MODEL_LEN // BLOCK_SIZE),
        max_batch=n, prefill_chunk=PREFILL_CHUNK,
        max_model_len=MAX_MODEL_LEN, **kw)


def _captured(records) -> list[dict]:
    """The traced steps' logits as float32 arrays, keyed by kind."""
    import numpy as np
    out = []
    for rec in records:
        row = {}
        for kind in ("prefill", "decode"):
            if kind in rec:
                row[kind] = (rec[kind].get("rid", rec[kind].get("rids")),
                             np.asarray(rec[kind]["logits"], np.float32))
        out.append(row)
    return out


def traced_steps(eng, n: int) -> list[dict]:
    """Step ``n`` times with logit capture on; returns the step records
    (logits as arrays)."""
    eng.start_trace(None, ring=1 << 16, capture_logits=True)
    for _ in range(n):
        eng.step()
    recs = eng.tracer.events("step")
    eng.stop_trace()
    return recs


def serve(eng, prompts, gens, n_traced: int):
    """Submit every prompt, capture logits over the first ``n_traced``
    steps, run to completion.  Returns (outputs, captured logits,
    {"buckets": decode buckets used, "prefills": prefill steps})."""
    rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    recs = traced_steps(eng, n_traced)
    captured = _captured(recs)
    eng.start_trace(None, ring=1 << 16)          # step kinds, no logits
    out = eng.run()
    recs += eng.tracer.events("step")
    eng.stop_trace()
    summary = {"buckets": {r["decode"]["bucket"] for r in recs
                           if "decode" in r},
               "prefills": sum("prefill" in r for r in recs)}
    return [out.get(r) for r in rids], captured, summary


def compare(got: list[dict], ref: list[dict]) -> float:
    """Largest |logit difference| / largest |oracle logit| over every
    captured array; the two runs must have scheduled identically."""
    import numpy as np
    if len(got) != len(ref):
        raise AssertionError(f"traced {len(got)} vs {len(ref)} steps")
    worst = 0.0
    for step, (a, b) in enumerate(zip(got, ref)):
        if a.keys() != b.keys():
            raise AssertionError(f"step {step}: {sorted(a)} vs {sorted(b)}")
        for kind in a:
            (ids_a, la), (ids_b, lb) = a[kind], b[kind]
            if ids_a != ids_b or la.shape != lb.shape:
                raise AssertionError(f"step {step} {kind}: rows differ")
            if not np.all(np.isfinite(la)):
                raise AssertionError(f"step {step} {kind}: non-finite")
            rel = float(np.max(np.abs(la - lb)) / np.max(np.abs(lb)))
            agree = float(np.mean(la.argmax(-1) == lb.argmax(-1)))
            print(f"[smoke] step {step} {kind} rows {la.shape[0]}: "
                  f"max |dlogit|/max|logit| {rel:.3e} "
                  f"argmax agreement {agree:.3f}", flush=True)
            worst = max(worst, rel)
    return worst


def decode_program(eng, bucket: int):
    """The engine's compiled decode step at one bucket (a persistent
    cache hit after the run compiled it)."""
    import jax.numpy as jnp
    from repro.serving.sampling import sampling_rows
    c = eng.cache
    args = (jnp.zeros((bucket, 1), jnp.int32),
            jnp.asarray(c.table_rows([], bucket)),
            jnp.zeros((bucket,), jnp.int32), jnp.zeros((bucket,), bool),
            jnp.asarray(c.slot_rows([], bucket)),
            *sampling_rows([], bucket).as_args())
    return eng._decode_fn.lower(eng.params, c.pools, *args).compile()


def one_chip(jax, clock, seed: int):
    with jax.default_matmul_precision(PRECISION):
        _one_chip(jax, clock, seed)


def _one_chip(jax, clock, seed: int):
    import numpy as np
    from repro import configs
    from repro.kernels import ops as kops
    from repro.kernels import paged_attention as pa
    from repro.models import transformer as M
    from repro.serving import Engine

    impls = (kops.resolve_impl("auto"), pa.resolve_impl("auto"))
    if impls != ("pallas", "pallas"):
        raise AssertionError(f"auto impls resolve to {impls} on TPU")
    cfg = configs.get_config(ARCH).replace(precision="bnn")
    print(f"[smoke] {ARCH}: layers {cfg.n_layers} d_model {cfg.d_model} "
          f"heads {cfg.n_heads}x{cfg.head_dim} d_ff {cfg.d_ff} "
          f"vocab {cfg.vocab}; {N_REQUESTS} requests x {PROMPT_LEN} "
          f"prompt + {GEN} generated", flush=True)
    with clock("init"):
        params, _ = M.init(jax.random.PRNGKey(seed), cfg)
        jax.block_until_ready(params)
    prompts = _prompts(cfg.vocab, seed, N_REQUESTS)
    gens = [GEN] * N_REQUESTS

    with clock("bnn"):
        eng = Engine(params, cfg, _engine_config(N_REQUESTS))
        outs, got, run = serve(eng, prompts, gens, TRACED_STEPS)
        st = eng.stats()
        text = decode_program(eng, 1).as_text()
        del eng
    produced = sum(len(o) - PROMPT_LEN for o in outs if o is not None)
    print(f"[smoke] bnn: finished {st['finished']} requests, {produced} "
          f"tokens in {st['steps']} steps, prefill chunks "
          f"{run['prefills']}, decode buckets {sorted(run['buckets'])}",
          flush=True)
    if any(o is None for o in outs) or produced != N_REQUESTS * GEN:
        raise AssertionError("not every request finished in full")
    if run["prefills"] < 2 * N_REQUESTS:
        raise AssertionError("prompts did not prefill in chunks")
    if len(run["buckets"]) < 2:
        raise AssertionError(f"decode buckets {run['buckets']}")
    if "tpu_custom_call" not in text:
        raise AssertionError("compiled decode step has no Pallas kernel")

    for rec in got:
        for kind, (_ids, lg) in rec.items():
            if not np.all(np.isfinite(lg)):
                raise AssertionError(f"bnn {kind} logits not finite")

    with clock("bnn-gemm-oracle"):
        shapes = bnn_gemm_check(jax, cfg, seed)
    print(f"[smoke] BNN GEMM vs XLA packed math: bit-identical at "
          f"{shapes} (M, K, N) shapes", flush=True)

    cfg_f = cfg.replace(precision="bf16")
    with clock("float"):
        eng = Engine(params, cfg_f, _engine_config(N_REQUESTS))
        outs, got, _ = serve(eng, prompts[:1], [FLOAT_GEN],
                             FLOAT_TRACED_STEPS)
        del eng
    if outs[0] is None or len(outs[0]) != PROMPT_LEN + FLOAT_GEN:
        raise AssertionError("float pass did not finish")
    with clock("float-attention-oracle"):
        oracle = Engine(params, cfg_f,
                        _engine_config(N_REQUESTS, attn_impl="xla"))
        oracle.submit(prompts[0], FLOAT_GEN)
        ref = _captured(traced_steps(oracle, FLOAT_TRACED_STEPS))
        del oracle
    check("attention", got, ref, FLOAT_RTOL)


def bnn_gemm_check(jax, cfg, seed: int) -> int:
    """``bnn_dense`` through the fused Pallas GEMM and through the XLA
    packed math, at every projection shape of the model (decode and
    prefill rows): the outputs must be bit-identical.  Returns the
    number of shapes checked."""
    import numpy as np
    from repro.kernels import ops as kops
    d, hd = cfg.d_model, cfg.n_heads * cfg.head_dim
    kn = sorted({(d, hd), (hd, d), (d, cfg.d_ff), (cfg.d_ff, d)})
    key = jax.random.PRNGKey(seed + 1)
    n = 0
    for m in (N_REQUESTS, PREFILL_CHUNK):
        for k, n_out in kn:
            kx, kw, key = jax.random.split(key, 3)
            x = jax.random.normal(kx, (m, k), jax.numpy.float32)
            w = jax.random.normal(kw, (k, n_out), jax.numpy.float32)
            a, b = (np.asarray(kops.bnn_dense(x, w, precision="bnn",
                                              impl=impl))
                    for impl in ("pallas", "xla"))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"BNN GEMM ({m}, {k}, {n_out}): "
                    f"{int(np.sum(a != b))} outputs differ")
            n += 1
    return n


def check(what: str, got, ref, rtol: float):
    worst = compare(got, ref)
    print(f"[smoke] {what} vs XLA oracle: largest logit difference "
          f"{worst:.3e} of the largest logit (tolerance {rtol})", flush=True)
    if not worst <= rtol:
        raise AssertionError(f"{what}: logits differ from the oracle "
                             f"by {worst:.3e}")


def four_chips(jax, clock, seed: int):
    from repro import configs
    from repro.dist import sharding as S
    from repro.models import transformer as M
    from repro.serving import Engine, ShardedEngine

    n = 4
    if len(jax.devices()) < n:
        raise AssertionError(f"--four-chips needs {n} chips, "
                             f"found {len(jax.devices())}")
    published = configs.get_config(ARCH)
    cfg = published.replace(precision="bnn", n_layers=FOUR_CHIP_LAYERS)
    print(f"[smoke] {ARCH}: {n} shards, layers {cfg.n_layers} (cut from "
          f"{published.n_layers}), d_model {cfg.d_model}, vocab {cfg.vocab}",
          flush=True)
    with clock("init"):
        params, _ = M.init(jax.random.PRNGKey(seed), cfg)
        jax.block_until_ready(params)
    prompts = _prompts(cfg.vocab, seed, FOUR_CHIP_REQUESTS)
    ecfg = _engine_config(FOUR_CHIP_REQUESTS)

    with clock("sharded"):
        sharded = ShardedEngine(params, cfg, ecfg, n,
                                meshes=S.shard_meshes(n))
        placed = []
        for e in sharded.engines:
            devs = {d for leaf in jax.tree.leaves((e.params, e.cache.pools))
                    for d in leaf.devices()}
            if len(devs) != 1:
                raise AssertionError(f"shard {e.shard} spans {devs}")
            placed.append(devs.pop())
        if len(set(placed)) != n:
            raise AssertionError(f"shards share devices: {placed}")
        rids = [sharded.submit(p, FOUR_CHIP_GEN) for p in prompts]
        out = sharded.run()
        got = [out[r] for r in rids]
    per_shard = [row["finished"] for row in sharded.stats()["per_shard"]]
    print(f"[smoke] shard devices {[d.id for d in placed]}, finished per "
          f"shard {per_shard}", flush=True)

    with clock("one-engine"):
        eng = Engine(params, cfg, ecfg)
        rids = [eng.submit(p, FOUR_CHIP_GEN) for p in prompts]
        out = eng.run()
        ref = [out[r] for r in rids]
    same = sum(bool((a == b).all()) for a, b in zip(got, ref))
    print(f"[smoke] token-identical requests: {same}/{len(ref)}", flush=True)
    if same != len(ref):
        raise AssertionError("sharded tokens differ from one engine")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard replica path and its "
                         "one-engine comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU found (JAX platform {devices[0].platform!r});"
                     " this smoke run never falls back to the CPU")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return _fail(f"no repro package under {ROOT}/src")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from jax import monitoring
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    print(f"[smoke] smoke run, not a measurement: {len(devices)} x "
          f"{dev.device_kind}; compile cache {cache}", flush=True)
    clock = CompileClock(monitoring)
    (four_chips if args.four_chips else one_chip)(jax, clock, args.seed)
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
