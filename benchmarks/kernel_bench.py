"""Kernel microbenchmarks: XNOR-popcount GEMM + paged-attention decode.

On this CPU harness the Pallas kernels run in interpret mode (NOT
representative of TPU throughput — every row is labeled with its
backend/mode), so the timed subjects are:

  * dense f32 GEMM vs the XLA packed XNOR path — the same math the
    fused kernel computes — at the paper's S=4608 and LM-projection
    shapes, with bit-ops/s and the 32x weight compression derived;
  * the fused binarize->pack->XNOR chain (kernels/fused_bnn.py) vs the
    UNFUSED two-kernel chain (binarize_pack + xnor_popcount_matmul,
    packed activations round-tripping between calls) — the before/after
    the fusion tentpole claims;
  * the paged-attention decode kernel (kernels/paged_attention.py) vs
    its XLA oracle (gather_blocks + chunked flash) over a batch x
    table-depth sweep.

--bench-json persists everything as schema-versioned
``BENCH_kernels.json`` (same contract shape as BENCH_serving.json);
--check-json validates such a file (the CI kernels job gate).

Usage:
  PYTHONPATH=src python benchmarks/kernel_bench.py --bench-json BENCH_kernels.json
  PYTHONPATH=src python benchmarks/kernel_bench.py --check-json BENCH_kernels.json
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core import packing, xnor
from repro.kernels import binarize_pack as bp
from repro.kernels import fused_bnn as fb
from repro.kernels import paged_attention as pa
from repro.kernels import xnor_popcount as xp
from repro.launch.compile_cache import enable_compile_cache
from repro.layers import attention as attn_mod
from repro.layers import attn_block

BENCH_SCHEMA_VERSION = 1

# BENCH_kernels.json contract (the CI kernels job fails on violation)
BENCH_REQUIRED_KEYS = ("schema_version", "bench", "params", "rows")
BENCH_REQUIRED_ROW_KEYS = ("table", "name", "backend", "us_per_call",
                           "derived")


def _time(f, *args, iters: int = 5) -> float:
    """Median per-call microseconds.  One warmup call (compile +
    first-run effects), then per-iteration wall times — the median, not
    the mean, so a stray scheduler hiccup cannot skew a 5-sample run."""
    jax.block_until_ready(f(*args))          # single warmup invocation
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples) * 1e6  # us


def _row(table: str, name: str, backend: str, us: float, **derived) -> dict:
    return {"table": table, "name": name, "backend": backend,
            "us_per_call": us,
            "derived": {k: v for k, v in derived.items()}}


# ----------------------------------------------------------------- XNOR GEMM


def bench_xnor_gemm(iters: int = 5) -> list[dict]:
    rows = []
    shapes = [(256, 256, 4608), (512, 2048, 2048), (128, 8192, 1024)]
    for m, n, s in shapes:
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k1, (m, s), jnp.float32)
        w = jax.random.normal(k2, (s, n), jnp.float32)
        ip = packing.pack_pm1(x)
        wp = jnp.swapaxes(packing.pack_pm1(w, axis=0), 0, 1)

        f_dense = jax.jit(lambda a, b: a @ b)
        f_xnor = jax.jit(lambda a, b: xnor.xnor_matmul_packed(a, b, s))

        t_dense = _time(f_dense, x, w, iters=iters)
        t_xnor = _time(f_xnor, ip, wp, iters=iters)
        rows.append(_row("kernel", f"dense_f32_{m}x{n}x{s}", "xla", t_dense,
                         flops_per_s=2 * m * n * s / (t_dense * 1e-6)))
        rows.append(_row("kernel", f"xnor_packed_{m}x{n}x{s}", "xla", t_xnor,
                         bitops_per_s=2 * m * n * s / (t_xnor * 1e-6),
                         weight_bytes_ratio=32.0))
    return rows


def bench_fused_bnn(iters: int = 3) -> list[dict]:
    """Fused one-kernel chain vs the unfused two-kernel chain.

    Off-TPU both run in interpret mode — the numbers are a correctness
    path, not TPU throughput — but the fused/unfused STRUCTURE (packed
    activations materialized between calls or not) is the same one the
    photonic cost model prices (cost_model.pack_pass_s_per_token)."""
    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    rows = []
    m, n, s = 128, 128, 2048
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (m, s), jnp.float32)
    w = jax.random.normal(k2, (s, n), jnp.float32)
    wp = jnp.swapaxes(packing.pack_pm1(w, axis=0), 0, 1)

    def unfused(a, b):
        ip = bp.binarize_pack(a)
        return xp.xnor_popcount_matmul(ip, b, s, mode="dot")

    def fused(a, b):
        return fb.fused_bnn_matmul(a, b, s, mode="dot")

    t_unfused = _time(unfused, x, wp, iters=iters)
    t_fused = _time(fused, x, wp, iters=iters)
    rows.append(_row("fused_bnn", f"unfused_pack+xnor_{m}x{n}x{s}",
                     f"pallas-{mode}", t_unfused,
                     packed_hbm_roundtrip=True))
    rows.append(_row("fused_bnn", f"fused_bnn_{m}x{n}x{s}",
                     f"pallas-{mode}", t_fused,
                     packed_hbm_roundtrip=False,
                     fused_over_unfused=t_fused / t_unfused))
    return rows


# ------------------------------------------------------------- paged decode


def bench_paged_decode(iters: int = 3) -> list[dict]:
    """Batch x table-depth sweep of one-token paged decode: the Pallas
    kernel walking the block table in-kernel vs the XLA oracle
    (gather_blocks + chunked flash attention)."""
    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    rows = []
    h, hkv, dh, bs = 4, 2, 16, 8
    for b, mb in [(1, 4), (4, 4), (4, 16), (8, 16)]:
        nb = b * mb + 1
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
        pool_k = jax.random.normal(ks[1], (nb, bs, hkv, dh), jnp.float32)
        pool_v = jax.random.normal(ks[2], (nb, bs, hkv, dh), jnp.float32)
        table = (jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb) + 1)
        kv_len = jnp.full((b,), mb * bs - 3, jnp.int32)
        q_off = kv_len - 1

        def f_pallas(q, pk, pv, tab, kl, qo):
            return pa.paged_attention(q, pk, pv, tab, kv_len=kl,
                                      q_offset=qo, layout="gqa")

        def f_xla(q, pk, pv, tab, kl, qo):
            keys = attn_block.gather_blocks(pk, tab)
            vals = attn_block.gather_blocks(pv, tab)
            return attn_mod.attention(q, keys, vals, causal=False,
                                      kv_len=kl, q_offset=qo, q_chunk=1)

        args = (q, pool_k, pool_v, table, kv_len, q_off)
        t_pl = _time(f_pallas, *args, iters=iters)
        t_x = _time(jax.jit(f_xla), *args, iters=iters)
        name = f"b{b}_mb{mb}_bs{bs}"
        rows.append(_row("paged_decode", f"paged_attn_{name}",
                         f"pallas-{mode}", t_pl,
                         batch=b, table_depth=mb, block_size=bs,
                         kv_slots=mb * bs))
        rows.append(_row("paged_decode", f"gather+flash_{name}", "xla", t_x,
                         batch=b, table_depth=mb, block_size=bs,
                         kv_slots=mb * bs))
    return rows


# ------------------------------------------------------------------- driver


def bench_rows(iters: int = 5) -> list[dict]:
    return (bench_xnor_gemm(iters=iters)
            + bench_fused_bnn(iters=max(2, iters // 2))
            + bench_paged_decode(iters=max(2, iters // 2)))


def _fmt(r: dict) -> str:
    derived = ";".join(f"{k}={v:.3e}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in r["derived"].items())
    return (f"{r['table']},{r['name']},{r['backend']},"
            f"{r['us_per_call']:.1f},{derived}")


def run(iters: int = 5) -> list[str]:
    """benchmarks/run.py entry point: CSV-ish lines."""
    return (["table,name,backend,us_per_call,derived"]
            + [_fmt(r) for r in bench_rows(iters=iters)])


def write_bench_json(path: str, rows: list[dict], params: dict) -> dict:
    """Persist the run as schema-versioned BENCH_kernels.json."""
    doc = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "bench": "kernels",
        "generated_by": "benchmarks/kernel_bench.py",
        "params": params,
        "rows": rows,
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, default=float)
    return doc


def check_bench_json(path: str) -> list[str]:
    """Validate a BENCH_kernels.json against the schema contract;
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
    if doc.get("bench") != "kernels":
        problems.append(f"bench {doc.get('bench')!r} != 'kernels'")
    rows = doc.get("rows") or []
    if not rows:
        problems.append("no rows")
    for i, row in enumerate(rows):
        for k in BENCH_REQUIRED_ROW_KEYS:
            if k not in row:
                problems.append(f"row {i} ({row.get('name')}): missing {k!r}")
    tables = {r.get("table") for r in rows}
    for required in ("kernel", "fused_bnn", "paged_decode"):
        if required not in tables:
            problems.append(f"missing bench table {required!r}")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=5)
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

    rows = bench_rows(iters=args.iters)
    print("table,name,backend,us_per_call,derived")
    for r in rows:
        print(_fmt(r))
    if args.bench_json:
        write_bench_json(args.bench_json, rows, {
            "iters": args.iters,
            "jax_backend": jax.default_backend(),
        })
        print(f"[bench] wrote {args.bench_json}")


if __name__ == "__main__":
    main()
