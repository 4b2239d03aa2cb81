#!/usr/bin/env python3
"""Calibration on the chip, in one process: the knee of a cell's traffic,
and the readings its correctness limit is set from.

    python3 bench/calibrate.py sweep   --workload <cell> --rates 1,2,3 --seconds 20
    python3 bench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 20

``sweep`` offers the cell's traffic at each mean rate and reports the
backlog (requests sent and not finished) at the window's start and end,
tokens/s and the TTFT tail: the knee is the highest rate whose backlog
does not grow.  ``readings`` serves the cell at its own rate and size for
each seed and reads, over the same sample of greedy requests that a run
compares, the numbers of the program and of the control put in its
place (the reference in bfloat16, putting its own token first), each
judged at the cell's limits as a run judges.  Both write their rows as
JSON to ``--out``
(``.bench_out/`` by default).
The benchmark's own runs never run either.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

import numpy as np       # noqa: E402

import run               # noqa: E402
import stats             # noqa: E402
import traffic_gen       # noqa: E402


class Server:
    """Builds engines for one cell, compiling the step programs once."""

    def __init__(self, cell, require_tpu=True):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        if require_tpu and jax.devices()[0].platform != "tpu":
            raise run.BenchError("no TPU found")
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.cell = cell
        self.cfg = run.arch_config(cell.config)
        self.ecfg = run.engine_config(cell.spec)
        self.fns = None
        self.clock = run.CompileClock()

    def engine(self, params):
        from repro.serving import Engine
        eng = Engine(params, self.cfg, self.ecfg)
        if self.fns is None:
            run.warm_up(eng, self.cfg.vocab)
            self.fns = (eng._prefill_fn, eng._decode_fn)
        else:
            eng._prefill_fn, eng._decode_fn = self.fns
        return eng


def backlog(eng, win, at: float) -> int:
    """Requests sent by ``at`` that had not finished by then."""
    n = 0
    for r in win.records:
        if r.sent <= at:
            toks = r.tokens
            req = eng.requests[r.rid]
            if len(toks) < req.max_new or toks[-1] > at:
                n += 1
    return n


def sweep(cell, rates, seconds, seed):
    import jax
    import weights
    srv = Server(cell)
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        params = weights.make(cell.config, seed)
        rows = []
        for rate in rates:
            eng = srv.engine(params)
            arr = traffic_gen.schedule(cell.mix, rate,
                                       cell.spec["lead_in_s"] + seconds,
                                       srv.cfg.vocab, seed)
            win = run.serve(eng, arr, cell.spec["lead_in_s"], seconds,
                            srv.clock)
            recs = [r.as_dict() for r in win.records]
            done, waiting = stats.ttft_ms(recs, win.w0, win.w1)
            row = {"rate": rate,
                   "backlog_start": backlog(eng, win, win.w0),
                   "backlog_end": backlog(eng, win, win.w1),
                   "due": len(done) + len(waiting),
                   "no_first_token": len(waiting),
                   "tokens_per_s": stats.tokens_in(recs, win.w0, win.w1)
                   / seconds,
                   "ttft_p50_ms": stats.censored_percentile(done, waiting, 50),
                   "ttft_p95_ms": stats.censored_percentile(done, waiting, 95),
                   "itl_p95_ms": stats.nearest_rank(
                       sorted(stats.inter_token_ms(recs, win.w0, win.w1)),
                       95),
                   "decode_rows_mean": (sum(win.decode_rows)
                                        / max(len(win.decode_rows), 1)),
                   "compiles_in_window": win.compiles}
            st = eng.stats()
            row["max_concurrent_decode"] = st["max_concurrent_decode"]
            row["preemptions"] = st["preemptions"]
            row["pool"] = {k: v for k, v in st["mixer"]["blocks"].items()
                           if isinstance(v, (int, float))}
            print(json.dumps(row), flush=True)
            rows.append(row)
            del eng, win
            gc.collect()
    return rows


def readings(cell, seeds, seconds, rate=None):
    """Per seed, the numbers a run compares and its verdict at the cell's
    limits, for the program and for the control put in its place (the
    reference in bfloat16, putting its own token first)."""
    import jax
    import weights
    conf = cell.config
    rows = []
    with jax.default_matmul_precision(conf["matmul_precision"]):
        srv = Server(cell)
        for seed in seeds:
            params = weights.make(conf, seed)
            eng = srv.engine(params)
            arr = traffic_gen.schedule(cell.mix,
                                       rate or cell.spec["rate_per_s"],
                                       cell.spec["lead_in_s"] + seconds,
                                       srv.cfg.vocab, seed)
            win = run.serve(eng, arr, cell.spec["lead_in_s"], seconds,
                            srv.clock)
            run.drain(eng, win, cell.spec)
            sample = run.sample_greedy(eng, win, cell.spec, seed)
            del eng
            gc.collect()
            gaps = {"program": [], "control_bf16": []}
            finite = True
            for req in sample:
                g = run.served_gaps(params, conf, req, dtype="bfloat16")
                gaps["program"].append(g["gap"])
                gaps["control_bf16"].append(g["control_gap"])
                finite &= g["finite"]
            row = {"seed": seed, "requests": len(sample),
                   "prompt_max": max((len(r["prompt"]) for r in sample),
                                     default=0)}
            for name, gs in gaps.items():
                nums = run.numbers(gs, finite, cell.spec)
                a = np.concatenate(gs) if gs else np.zeros(0)
                row[name] = {"gap_max": nums["gap_max"]["value"],
                             "tokens": nums["served_tokens"]["value"],
                             "share_over_1e-3": (float(np.mean(a > 1e-3))
                                                 if a.size else None),
                             "correct": run.judge(nums)}
            print(json.dumps(row), flush=True)
            rows.append(row)
            del params
            gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rate", type=float, default=None,
                    help="readings: offer this mean rate, not the cell's")
    ap.add_argument("--out", default=run.OUT_DIR,
                    help="directory for the JSON rows")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.what == "sweep":
        rows = sweep(cell, [float(r) for r in args.rates.split(",")],
                     args.seconds, seeds[0])
    else:
        rows = readings(cell, seeds, args.seconds, args.rate)
    out = args.out
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{args.what}_{args.workload}.json"),
              "w") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
