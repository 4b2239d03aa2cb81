#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); the cell's own file
(``workloads/<cell>.json``) holds its fixed rate, its engine sizes and the
limits of its correctness check.  A run:

1. builds the seeded weights on the device in one jitted call, one
   ``Engine`` with the cell's sizes, and warms up the prefill chunk and
   every decode bucket the cell uses (set-up);
2. offers the cell's open-loop traffic, a lead-in (set-up) and then a
   window of ``--seconds``, driving ``Engine.submit`` and ``Engine.step``
   from this process and stamping each token when ``step()`` returns;
3. reads the device's peak memory and the window's numbers, keeps the
   engine stepping (untimed, no arrivals) until enough greedy requests
   have finished (``drain``), frees the engine, and teacher-forces a
   seeded sample of the finished greedy requests through the plain
   reference (``reference.py``): ``correct`` holds when no served token's
   reference logit lies further below the reference's best than the
   cell's limit;
4. prints one JSON line: the cell's end-to-end metrics with ``--trace 0``,
   its per-layer metrics (``metrics/<name>.py``, read from a profiler
   trace of the window's last ``trace_s`` seconds) with ``--trace 1``.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()        # set-up is timed from here

import argparse                        # noqa: E402
import contextlib                      # noqa: E402
import gc                              # noqa: E402
import importlib.util                  # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

import numpy as np                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import stats                           # noqa: E402
import traffic_gen                     # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")


class BenchError(RuntimeError):
    """A run that cannot produce a result."""


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # configs/<name>.json
    mix: dict                    # traffic/<name>.json
    spec: dict                   # workloads/<cell>.json
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    try:
        w = next(x for x in bench["workloads"] if x["name"] == name)
    except StopIteration:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json") from None
    c = next(x for x in bench["configs"] if x["name"] == w["config"])
    here = os.path.join(root, "bench")

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=w["chips"],
                config=_json(os.path.join(root, c["file"])),
                mix=_json(os.path.join(here, "traffic",
                                       w["traffic"] + ".json")),
                spec=_json(os.path.join(here, "workloads", name + ".json")),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file: the repo's own
    config with the file's ``changed`` keys applied; every other key of
    the file that names a config field must equal what runs."""
    import dataclasses
    from repro import configs
    cfg = configs.get_config(conf["arch"]).replace(**conf["changed"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    for k, v in conf.items():
        if k in fields and getattr(cfg, k) != v:
            raise BenchError(f"{conf['arch']}: {k}={getattr(cfg, k)!r} runs, "
                             f"the configuration file says {v!r}")
    return cfg


def engine_config(spec: dict):
    from repro.serving import EngineConfig
    return EngineConfig(**spec["engine"])


class CompileClock:
    """Seconds spent compiling, and the number of compiles, from JAX's
    monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        from jax import monitoring
        self.secs = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1


def check_params(params, cfg):
    """The benchmark's weight tree has the program's layout."""
    import jax
    from repro.models import transformer as M
    shapes, _ = M.abstract_init(cfg)
    if jax.tree.structure(shapes) != jax.tree.structure(params):
        raise BenchError("weight tree differs from the program's layout")
    for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise BenchError(f"weight {a.shape}/{a.dtype} vs "
                             f"{b.shape}/{b.dtype}")


def warm_up(eng, vocab: int):
    """Run every shape and host-to-device path a window can meet: the
    prefill chunk, each decode bucket up to ``max_batch`` (requests join
    and leave one by one), sampled and greedy rows, and a prefix hit (a
    prompt that extends a cached one).  The same work every run."""
    from repro.serving.sampling import SamplingParams
    e = eng.ecfg
    rng = np.random.default_rng(0)
    n_tok = min(e.prefill_chunk, 2 * e.block_size)
    prompts = [rng.integers(0, vocab, n_tok) for _ in range(e.max_batch)]
    for i, p in enumerate(prompts):
        sp = SamplingParams(temperature=0.7 if i % 2 else 0.0, top_p=0.9,
                            seed=i)
        eng.submit(p, e.max_batch + 2, sampling=sp)
    eng.run()
    eng.submit(np.concatenate([prompts[0], prompts[1][:3]]), 2)
    eng.run()
    eng.reset_stats(flush_prefix=True)


@dataclass
class Record:
    """One request of the window, on the client's clock (seconds)."""
    rid: int
    due: float
    sent: float
    greedy: bool
    prompt_len: int
    tokens: list[float] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {"due": self.due, "tokens": self.tokens}


@dataclass
class Window:
    t0: float                    # traffic start
    w0: float                    # window start
    w1: float                    # window end
    records: list[Record]
    failed: int
    step0: int                   # the engine's step count at traffic start
    step_start: list[float]      # host time each engine step began
    decode_rows: list[int]       # rows of each decode call in the window
    compiles: int                # compiles inside the window
    trace_span: tuple[float, float] | None = None
    # steps run while the profiler traced: {"prefill": [(new tokens,
    # context after them)], "decode": [(1, context after it), ...]}
    traced_steps: list[dict] = field(default_factory=list)


def _annotated(fn, name: str):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def _advanced(eng, before: dict) -> dict:
    """What one step fed: per request that moved, (new tokens, context
    after them), split into the prefill chunk and the decode rows."""
    from repro.serving.request import State
    out = {"prefill": [], "decode": []}
    for rid, (state, pos) in before.items():
        req = eng.requests[rid]
        if state == State.QUEUED:          # admitted past a cached prefix
            pos = max(pos, req.skipped_prefill)
        if req.pos > pos:
            kind = "decode" if state == State.DECODE else "prefill"
            out[kind].append((req.pos - pos, req.pos))
    return out


def _live(eng) -> dict:
    from repro.serving.request import State
    return {rid: (r.state, r.pos) for rid, r in eng.requests.items()
            if r.state in (State.QUEUED, State.PREFILL, State.DECODE)}


def _stop_trace(eng, span: tuple) -> tuple:
    """Wait for the device, close the traced span, then stop the profiler
    (whose export is not part of the span)."""
    import jax
    jax.block_until_ready(eng.cache.pools)
    end = time.perf_counter()
    jax.profiler.stop_trace()
    return (span[0], end)


def serve(eng, arrivals, lead_in: float, seconds: float, clock,
          trace_dir: str | None = None, trace_s: float = 0.0) -> Window:
    """Offer ``arrivals`` open loop and measure the window."""
    import jax
    from repro.serving.sampling import SamplingParams

    committed: list[tuple[int, int]] = []
    eng.set_commit_callback(lambda rid, new, done:
                            committed.append((rid, len(new))))
    step_fn, submit_fn = eng.step, eng.submit
    if trace_dir:
        step_fn = _annotated(eng.step, "bench.step")
        submit_fn = _annotated(eng.submit, "bench.submit")
        eng.scheduler.schedule = _annotated(eng.scheduler.schedule,
                                            "bench.schedule")
    t0 = time.perf_counter()
    step0 = eng.step_count
    w0, w1 = t0 + lead_in, t0 + lead_in + seconds
    # the last trace_s seconds of the window: writing the trace out takes
    # seconds, which then fall after the window, not inside it
    tr0 = w0 + max(0.0, seconds - trace_s)
    tr1 = w1
    tracing = False
    trace_span = None
    traced: list[dict] = []
    by_rid: dict[int, Record] = {}
    records: list[Record] = []
    steps: list[float] = []
    rows: list[int] = []
    failed = 0
    compiles0 = None
    i, n = 0, len(arrivals)
    while True:
        now = time.perf_counter()
        if compiles0 is None and now >= w0:
            compiles0 = clock.count
        if trace_dir and not tracing and trace_span is None and now >= tr0:
            jax.profiler.start_trace(trace_dir)
            tracing, trace_span = True, (time.perf_counter(), None)
        if tracing and now >= tr1:
            trace_span = _stop_trace(eng, trace_span)
            tracing = False
        if now >= w1:
            break
        while i < n and t0 + arrivals[i].due_s <= now:
            a = arrivals[i]
            sp = SamplingParams(temperature=a.temperature, top_p=a.top_p,
                                seed=a.seed)
            sent = time.perf_counter()
            i += 1
            try:
                rid = submit_fn(a.prompt, a.max_new, sampling=sp)
            except ValueError as err:
                log(f"request refused: {err}")
                failed += 1
                continue
            rec = Record(rid, t0 + a.due_s, sent, a.greedy, len(a.prompt))
            records.append(rec)
            by_rid[rid] = rec
        if eng.scheduler.idle:
            marks = [t0 + arrivals[i].due_s if i < n else w1, w1]
            if trace_dir and trace_span is None:
                marks.append(tr0)
            if tracing:
                marks.append(tr1)
            wait = min(marks) - time.perf_counter()
            if wait > 0:
                with (jax.profiler.TraceAnnotation("bench.idle")
                      if trace_dir else contextlib.nullcontext()):
                    time.sleep(wait)
            continue
        before = _live(eng) if tracing else None
        steps.append(time.perf_counter())
        committed.clear()
        step_fn()
        t = time.perf_counter()
        decoded = 0
        for rid, k in committed:
            rec = by_rid[rid]
            if rec.tokens:
                decoded += 1
            rec.tokens.extend([t] * k)
        if decoded and w0 <= steps[-1] < w1:
            rows.append(decoded)
        if before is not None:
            traced.append(_advanced(eng, before))
    if tracing:
        trace_span = _stop_trace(eng, trace_span)
    eng.set_commit_callback(None)
    return Window(t0, w0, w1, records, failed, step0, steps, rows,
                  clock.count - (compiles0 or clock.count), trace_span,
                  traced)


# ------------------------------------------------------------ correctness


def _greedy_done_tokens(eng, win: Window) -> int:
    from repro.serving.request import State
    n = 0
    for rec in win.records:
        req = eng.requests.get(rec.rid)
        if rec.greedy and req is not None and req.state == State.FINISHED:
            n += len(req.out)
    return n


def drain(eng, win: Window, spec: dict) -> float:
    """After the window, with no new arrivals: keep stepping the same
    engine until the greedy requests finished so far hold
    ``sample_tokens`` served tokens, or ``drain_s`` seconds have passed.
    Nothing here is timed; the window's records end at its close."""
    t0 = time.perf_counter()
    want = spec["check"]["sample_tokens"]
    budget = spec["check"].get("drain_s", 0.0)
    while (_greedy_done_tokens(eng, win) < want
           and not eng.scheduler.idle
           and time.perf_counter() - t0 < budget):
        eng.step()
    return time.perf_counter() - t0


def sample_greedy(eng, win: Window, spec: dict, seed: int) -> list[dict]:
    """A seeded sample of the greedy requests finished by the window's
    end, the longest of them always in it, until ``sample_tokens``
    served tokens are covered."""
    from repro.serving.request import State
    done = []
    for rec in win.records:
        req = eng.requests.get(rec.rid)
        if rec.greedy and req is not None and req.state == State.FINISHED:
            done.append({"rid": rec.rid, "prompt": np.array(req.prompt),
                         "served": np.array(req.out, np.int32)})
    if not done:
        return []
    done.sort(key=lambda r: r["rid"])
    longest = max(range(len(done)),
                  key=lambda j: len(done[j]["prompt"]) + len(done[j]["served"]))
    order = [longest] + [int(j) for j in
                         np.random.default_rng(seed).permutation(len(done))
                         if j != longest]
    out, n_tok = [], 0
    for j in order:
        if n_tok >= spec["check"]["sample_tokens"]:
            break
        out.append(done[j])
        n_tok += len(done[j]["served"])
    return out


def ref_bucket(n: int) -> int:
    """Reference sequence lengths are padded to a multiple of 1024 so a
    handful of programs serve every request (padding sits after the
    last real token and never reaches it)."""
    return max(1024, -(-n // 1024) * 1024)


def served_gaps(params, conf: dict, req: dict, dtype=None) -> dict:
    """Teacher-force one request's prompt and served tokens through the
    reference: per served token, how far its reference logit lies below
    the reference's best, in units of the standard deviation of the
    reference's logits at that position.  With ``dtype`` (the control's,
    below float32) also ``control_gap``: the same for the token that the
    reference in that dtype puts first."""
    import jax.numpy as jnp
    import reference
    seq = np.concatenate([req["prompt"], req["served"]])
    p = len(req["prompt"])
    toks = np.zeros(ref_bucket(len(seq) - 1), np.int32)
    toks[:len(seq) - 1] = seq[:-1]
    pos = np.arange(p - 1, len(seq) - 1)
    lg = np.asarray(reference.logits_at(params, conf, toks, pos),
                    np.float64)
    best = lg.max(-1)
    std = lg.std(-1)
    rows = np.arange(len(pos))
    out = {"gap": (best - lg[rows, req["served"]]) / std,
           "finite": bool(np.isfinite(lg).all())}
    if dtype is not None:
        lo = np.asarray(reference.logits_at(params, conf, toks, pos,
                                            dtype=getattr(jnp, dtype)))
        out["control_gap"] = (best - lg[rows, lo.argmax(-1)]) / std
    return out


def check(params, conf: dict, sample: list[dict], spec: dict,
          control: dict | None = None) -> dict:
    """The numbers compared, each with its limit (None: no limit set).
    With ``control`` ({"dtype": "bfloat16"}) the numbers are those of the
    reference in that dtype put in the program's place: the tokens it
    puts first instead of the served ones."""
    gaps, finite = [], True
    for req in sample:
        g = served_gaps(params, conf, req, **(control or {}))
        gaps.append(g["control_gap"] if control else g["gap"])
        finite &= g["finite"]
    return numbers(gaps, finite, spec)


def numbers(gaps: list, finite: bool, spec: dict) -> dict:
    """The numbers compared, from per-request arrays of gaps."""
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    lim = spec["check"]["limits"]
    return {"served_tokens": {"value": int(allg.size),
                              "limit": spec["check"]["sample_tokens"]},
            "gap_max": {"value": float(allg.max()) if allg.size else None,
                        "limit": lim.get("gap_max")},
            "finite": {"value": finite, "limit": True}}


def judge(numbers: dict) -> bool:
    n = numbers
    return (n["finite"]["value"] is True
            and n["served_tokens"]["value"] >= n["served_tokens"]["limit"]
            and n["gap_max"]["value"] is not None
            and n["gap_max"]["limit"] is not None
            and n["gap_max"]["value"] <= n["gap_max"]["limit"])


# ---------------------------------------------------------------- metrics


def load_reader(name: str, root: str = HERE):
    path = os.path.join(root, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    """The cell's end-to-end metrics.  ``ttft_p<q>_ms`` and ``itl_p<q>_ms``
    take the percentile named in them."""
    recs = [r.as_dict() for r in win.records]
    done, waiting = stats.ttft_ms(recs, win.w0, win.w1)
    itl = sorted(stats.inter_token_ms(recs, win.w0, win.w1))

    def value(name: str) -> float:
        if name == "output_tokens_per_s":
            return stats.tokens_in(recs, win.w0, win.w1) / (win.w1 - win.w0)
        if name == "setup_s":
            return setup_s
        kind, q, _ = name.split("_")
        q = float(q[1:])
        if kind == "ttft":
            return stats.censored_percentile(done, waiting, q)
        return stats.nearest_rank(itl, q)
    return {m["name"]: {"value": value(m["name"]), "unit": m["unit"]}
            for m in cell.end_to_end}


@dataclass
class LayerContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    window: Window
    trace: object                # trace_reduce.Trace, or None
    peaks: dict
    peak_bytes: int
    requests: dict               # rid -> the engine's Request


def per_layer(ctx: LayerContext) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ------------------------------------------------------------------- run


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        *, t_process: float = T_PROCESS, require_tpu: bool = True,
        fault=None, control: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``fault``
    (tests only) is called with the built engine before the window;
    ``control`` (tests and calibration only) scores the reference at a
    lower precision in the program's place (``check``)."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform "
                         f"{devices[0].platform!r}); never runs on the CPU")
    if len(devices) < cell.chips:
        raise BenchError(f"the cell needs {cell.chips} chips, "
                         f"found {len(devices)}")
    cache = None
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    conf = cell.config
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if require_tpu else {}
    with jax.default_matmul_precision(conf["matmul_precision"]):
        return _run(cell, seed, seconds, trace, t_process, clock, conf,
                    devices, dev, peaks, cache, fault, control)


def _run(cell, seed, seconds, trace, t_process, clock, conf, devices, dev,
         peaks, cache, fault, control):
    import jax
    import weights
    from repro.serving import Engine
    spec, mix = cell.spec, cell.mix
    cfg = arch_config(conf)
    params = weights.make(conf, seed)
    jax.block_until_ready(params)
    check_params(params, cfg)
    ecfg = engine_config(spec)
    if traffic_gen.longest(mix) > ecfg.max_model_len:
        raise BenchError("max_model_len is shorter than the mix's longest "
                         "request")
    eng = Engine(params, cfg, ecfg)
    warm_up(eng, cfg.vocab)
    if fault is not None:
        fault(eng)
    arrivals = traffic_gen.schedule(mix, spec["rate_per_s"],
                                    spec["lead_in_s"] + seconds, cfg.vocab,
                                    seed)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_compile = clock.secs
    win = serve(eng, arrivals, spec["lead_in_s"], seconds, clock,
                trace_dir, spec.get("trace_s", 6.0))
    setup_s = win.w0 - t_process
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"set-up {setup_s:.3f}s (compiling {setup_compile:.3f}s, "
        f"cache {cache}); {len(win.records)} requests sent, compiles "
        f"inside the window {win.compiles}; peak bytes {peak_bytes}")

    reduced = None
    if trace:
        import trace_reduce
        path = trace_reduce.newest_xplane(trace_dir)
        if path is None:
            raise BenchError("the profiler wrote no trace")
        reduced = trace_reduce.load(path)
    ctx = LayerContext(cell, win, reduced, peaks, peak_bytes,
                       dict(eng.requests))
    if trace:
        metrics = per_layer(ctx)
    else:
        metrics = end_to_end(cell, win, setup_s)
    drained = drain(eng, win, spec)
    log(f"drained {drained:.3f}s after the window for the check's sample")
    sample = sample_greedy(eng, win, spec, seed)
    del eng
    gc.collect()

    numbers = check(params, conf, sample, spec, control)
    attempted = sum(1 for r in win.records if win.w0 <= r.due < win.w1) \
        + win.failed
    result = {
        "correct": judge(numbers) and win.failed == 0,
        "attempted": attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak_bytes},
    }
    if trace:
        import trace_reduce
        span = win.trace_span
        result["device"]["busy_s"] = trace_reduce.busy_s(reduced)
        result["device"]["window_s"] = span[1] - span[0]
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                               "idle_gaps": trace_reduce.idle_gaps(reduced)}
    result["check"] = numbers
    for k, v in numbers.items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        log("--seed must be a non-negative whole number")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"the program is not beside the benchmark (no src/repro under "
            f"{ROOT})")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as err:
        log(str(err))
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
