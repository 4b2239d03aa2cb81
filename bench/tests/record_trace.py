#!/usr/bin/env python3
"""Record a small trace fixture of one cell on the chip, and compare the
rules that find kernels and pool copies in a trace.

    python3 bench/tests/record_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --out <file.json.gz>

Makes one traced run of the cell, as ``bench/run.py --trace 1`` does,
and prints its result line.  Then, over the whole traced window, it
prints the device time and the ops that each of two rules selects:

* by name, as the readers match: the ``paged_attention`` kernel, the
  ``bnn_xnor_gemm`` and ``bnn_binarize_pack`` kernels
  (``engine_spans.kernel_calls``), and unscoped copies of a pool the
  built engine holds (``ctx.pools``);
* by shape (``shape_rules``): a kernel whose operands include a dense KV
  pool of the configured shape is paged attention and every other kernel
  is a BNN kernel; a copy of that pool shape is a pool copy.

Last it writes the fixture: one decode and one prefill program run of
device 0 with every kernel call, every copy and the 40 longest other
ops inside them, each op's scope path, and the engine's pool labels.
Labels are cut to the instruction, its operand types and
``tpu_custom_call``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import engine_spans as S  # noqa: E402
import run  # noqa: E402
import trace_reduce as T  # noqa: E402

ATTN = ("paged_attention",)
BNN = ("bnn_xnor_gemm", "bnn_binarize_pack")
SHAPE = re.compile(r"\b(?:pred|[a-z]+[0-9]+)\[[0-9,]*\]")


def dense_pool(cell) -> str:
    """The label text of a dense KV pool of the cell's configured shape:
    (blocks, block size, KV heads, head size) in float32."""
    e, c = cell.spec["engine"], cell.config
    return (f"f32[{e['num_blocks']},{e['block_size']},{c['n_kv_heads']},"
            f"{c['head_dim']}]")


def shape_rules(ops: list, cell) -> dict:
    """The kernels told apart by operand shapes."""
    pool = dense_pool(cell)
    kernels = [ev for ev in ops if "tpu_custom_call" in ev.label]
    return {"attention": [ev for ev in kernels if pool in ev.label],
            "bnn": [ev for ev in kernels if pool not in ev.label]}


def name_rules(ops: list) -> dict:
    return {"attention": S.kernel_calls(ops, ATTN),
            "bnn": S.kernel_calls(ops, BNN)}


def pool_copies(ops: list, paths: list[str], pools) -> list:
    return [ev for ev, p in zip(ops, paths) if S.whole_pool_copy(ev, p, pools)]


def compare(tr, paths: list[str], pools, cell) -> dict:
    """Per rule, the ops each selects and their device time."""
    old, new = shape_rules(tr.ops, cell), name_rules(tr.ops)
    old["pool_copies"] = pool_copies(tr.ops, paths, [dense_pool(cell)])
    new["pool_copies"] = pool_copies(tr.ops, paths, pools)
    out = {}
    for k in old:
        out[k] = {"ops": [len(old[k]), len(new[k])],
                  "same_ops": [id(e) for e in old[k]]
                  == [id(e) for e in new[k]],
                  "device_s": [T.device_time(old[k]),
                               T.device_time(new[k])]}
    out["kv_update_s"] = [
        S.scope_time(tr.ops, paths, "kv_update", [dense_pool(cell)]),
        S.scope_time(tr.ops, paths, "kv_update", pools)]
    return out


def cut_label(ev) -> str:
    shapes = list(dict.fromkeys(SHAPE.findall(ev.label)))
    tail = ["tpu_custom_call"] if "tpu_custom_call" in ev.label else []
    return " ".join(["%" + S.instruction(ev)] + shapes + tail)


def fixture(tr, paths: list[str], pools, keep_other: int = 40) -> dict:
    """One decode and one prefill program run of device 0, reduced."""
    runs = []
    for key in ("_decode", "_prefill"):
        ms = [m for m in tr.modules if m.device == 0 and key in m.name]
        runs.append(ms[-1])
    t0 = min(m.start for m in runs)
    ops, other = [], []
    for ev, path in zip(tr.ops, paths):
        if ev.device or not any(m.start <= ev.start < m.end for m in runs):
            continue
        if "tpu_custom_call" in ev.label or \
                S.instruction(ev).startswith("copy"):
            ops.append((ev, path))
        else:
            other.append((ev, path))
    other.sort(key=lambda x: -x[0].dur)
    ops += other[:keep_other]
    ops.sort(key=lambda x: x[0].start)
    return {"devices": 1,
            "ops": [["%" + S.instruction(e), cut_label(e), e.start - t0,
                     e.dur, 0] for e, _ in ops],
            "paths": [p for _, p in ops],
            "modules": [[m.name, m.name, m.start - t0, m.dur, 0]
                        for m in runs],
            "host": [], "pools": list(pools)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seen = {}
    per_layer = run.per_layer

    def keep(ctx):
        seen["ctx"] = ctx
        return per_layer(ctx)
    run.per_layer = keep
    cell = run.load_cell(args.workload)
    print(json.dumps(run.run(cell, args.seed, args.seconds, True)),
          flush=True)
    ctx = seen["ctx"]
    tr = ctx.trace
    xplane = T.newest_xplane(S.trace_dir(ctx))
    paths = S.op_paths(tr.ops, tr.modules, S.program_op_names(xplane))
    got = compare(tr, paths, ctx.pools, cell)
    got["pools"] = ctx.pools
    got["dense_pool"] = dense_pool(cell)
    print(json.dumps(got), flush=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(fixture(tr, paths, ctx.pools), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
