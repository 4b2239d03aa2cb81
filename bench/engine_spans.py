"""The engine's own spans and the step programs' named scopes, read from
the profiler trace of a ``--trace 1`` run, beside the device's ops.

Host side: every span of the engine's tracer (``serving/tracing.py``)
opens a ``jax.profiler.TraceAnnotation`` named ``engine.<span>`` that
carries the span's stats.  ``engine.step`` is the root of one
``Engine.step()``; its phases are ``engine.schedule`` and, per kind
(``prefill``, ``decode``, ``spec``), ``engine.<kind>.prepare``,
``.dispatch``, ``.sync`` and ``.commit``.  Every span carries ``step``;
prefill spans carry ``rid``, ``tokens`` and ``chunk`` (the padded
width), decode and spec spans ``rows`` and ``bucket``.

Device side: the step programs put ``jax.named_scope`` names on their
pieces (``attn``, ``kv_update``, ``ffn``, ``head``, ``sample``,
``weight_pack``) and ``name=`` on their Pallas kernels, which names
each call's instruction (``paged_attention.45``; ``kernel_calls``).
XLA keeps the scope path as each HLO instruction's ``op_name``; the
profiler stores each program's HLO in its ``/host:metadata`` plane,
which is read here with a few lines of protobuf wire decoding, and an
op is matched to its instruction through the program run ("XLA
Modules" event) that holds it.
(On a TPU v5e the "XLA Ops" events carry no scope of their own: an
event's name is the instruction's HLO text, ``%copy.12 = f32[...]
copy(...)``, and its stats are times.)

"Per step" means per ``engine.step`` span in the traced window.  A
program without these spans (one that predates them) reads ``None``.
"""
from __future__ import annotations

import bisect
import os
from dataclasses import dataclass, field

import trace_reduce

PHASES = ("schedule", "prepare", "dispatch", "sync", "commit")
SCOPES = ("attn", "kv_update", "ffn", "head", "sample", "weight_pack")


@dataclass
class Span:
    name: str                    # without the "engine." prefix
    start: float
    dur: float
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def phase(self) -> str | None:
        p = self.name.rsplit(".", 1)[-1]
        return p if p in PHASES else None


def _number(v):
    if isinstance(v, (int, float)):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        try:
            return float(v)
        except (TypeError, ValueError):
            return v


def load_spans(path: str) -> list[Span]:
    """Every ``engine.*`` host event of one xplane, with its stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    stats = {k: _number(v) for k, v in ev.stats}
                    out.append(Span(ev.name[len("engine."):],
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9, stats))
    out.sort(key=lambda s: s.start)
    return out


# ------------------------------------------------------ protobuf wire


def _varint(b, i: int) -> tuple[int, int]:
    shift = r = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one serialized message:
    varints as ints, everything else as a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list[int]:
    """A repeated int64 field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of one serialized ``HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2, id 35, operand_ids 36).  An instruction that
    XLA made without an ``op_name`` (a layout copy, the pieces of an
    expanded sort or scatter) takes the path of its first operand that
    has one."""
    name_of: dict[int, str] = {}
    own: dict[str, str] = {}
    operands: dict[str, list[int]] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            for f3, ins in _fields(comp):
                if f3 != 2:
                    continue
                name, op, iid, ids = None, "", None, []
                for f4, v in _fields(ins):
                    if f4 == 1:
                        name = _text(v)
                    elif f4 == 7:
                        for f5, w in _fields(v):
                            if f5 == 2:
                                op = _text(w)
                    elif f4 == 35:
                        iid = v
                    elif f4 == 36:
                        ids += _ints(v)
                if name:
                    name_of[iid] = name
                    own[name] = op
                    operands[name] = ids
    out: dict[str, str] = {}
    for start in own:
        stack, seen = [start], {start}
        while stack:
            cur = stack[-1]
            if cur in out:
                stack.pop()
                continue
            if own[cur]:
                out[cur] = own[cur]
                stack.pop()
                continue
            ins = [name_of[i] for i in operands[cur] if i in name_of]
            nxt = next((n for n in ins if n not in out and n not in seen),
                       None)
            if nxt is not None:        # resolve the operands first
                seen.add(nxt)
                stack.append(nxt)
                continue
            out[cur] = next((out[n] for n in ins if out.get(n)), "")
            stack.pop()
    return {k: v for k, v in out.items() if v}


def program_op_names(path: str) -> dict[str, dict[str, str]]:
    """Program name (``jit__decode(<id>)``, as "XLA Modules" events
    name it) -> instruction name -> ``op_name``, from the xplane's
    ``/host:metadata`` plane (XSpace planes 1; XPlane name 2,
    event_metadata 4, stat_metadata 5; a map entry's value 2; event
    metadata name 2, stats 5; stat metadata_id 1, bytes_value 6)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, metas, stat_names = None, [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = _text(v)
                if name != "/host:metadata":
                    break
            elif f2 == 4:
                metas.append(v)
            elif f2 == 5:
                for f3, sm in _fields(v):
                    if f3 == 2:
                        d = dict(_fields(sm))
                        stat_names[d.get(1)] = _text(d.get(2, b""))
        if name != "/host:metadata":
            continue
        for entry in metas:
            for f3, em in _fields(entry):
                if f3 != 2:
                    continue
                prog, protos = None, []
                for f4, w in _fields(em):
                    if f4 == 2:
                        prog = _text(w)
                    elif f4 == 5:
                        st = dict(_fields(w))
                        if stat_names.get(st.get(1)) == "Hlo Proto" \
                                and 6 in st:
                            protos.append(st[6])
                for p in protos:
                    out[prog] = hlo_op_names(p)
    return out


# --------------------------------------------------------- reductions


def _program_key(name: str) -> str:
    """``jit__decode(1234)`` -> ``1234``: the program id both planes
    put in parentheses (the whole name where there is none)."""
    if name.endswith(")") and "(" in name:
        return name[name.rindex("(") + 1:-1]
    return name


def instruction(ev) -> str:
    """The HLO instruction an op event ran: ``%copy.12 = f32[...] ...``
    and ``%copy.12`` both name ``copy.12``."""
    return ev.name.lstrip("%").split(" ", 1)[0]


def op_paths(ops: list, modules: list,
             programs: dict[str, dict[str, str]]) -> list[str]:
    """Each op's scope path: its instruction's ``op_name`` within the
    program run that holds it ('' outside every program run)."""
    by_key = {_program_key(k): v for k, v in programs.items()}
    runs: dict[int, list] = {}
    for m in sorted(modules, key=lambda m: m.start):
        runs.setdefault(m.device, []).append(m)
    starts = {d: [m.start for m in ms] for d, ms in runs.items()}
    out = []
    for ev in ops:
        path = ""
        ms = runs.get(ev.device, [])
        j = bisect.bisect_right(starts.get(ev.device, []), ev.start) - 1
        if j >= 0 and ev.start < ms[j].end:
            names = by_key.get(_program_key(ms[j].name), {})
            path = names.get(instruction(ev), "")
        out.append(path)
    return out


def kernel_calls(ops: list, names) -> list:
    """The Pallas kernel calls among ``ops`` made by a kernel of one of
    ``names`` (its ``name=``; XLA numbers each call, ``name.<n>``)."""
    return [ev for ev in ops if "tpu_custom_call" in ev.label
            and instruction(ev).rsplit(".", 1)[0] in names]


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def hlo_type(dtype) -> str:
    """HLO's name of a dtype: ``f32``, ``bf16``, ``s8``, ``pred``."""
    name = str(dtype)
    if name == "bool":
        return "pred"
    for long, short in (("bfloat", "bf"), ("float", "f"), ("uint", "u"),
                        ("int", "s")):
        if name.startswith(long):
            return short + name[len(long):]
    return name


def pool_labels(leaves) -> list[str]:
    """The label text of each distinct shape among the engine's state
    pools (``f32[2561,16,16,64]``), as an op's label spells an operand."""
    out = []
    for a in leaves:
        lab = f"{hlo_type(a.dtype)}[{','.join(str(d) for d in a.shape)}]"
        if lab not in out:
            out.append(lab)
    return out


def whole_pool_copy(ev, path: str, pools) -> bool:
    """An XLA-inserted copy of a whole state pool (one of the label
    texts ``pools``) that carries no scope: counted with the pool update
    (``kv_update``)."""
    return (instruction(ev).startswith("copy")
            and any(p in ev.label for p in pools)
            and not any(in_scope(path, s) for s in SCOPES))


def scope_time(ops: list, paths: list[str], scope: str,
               pools=()) -> float:
    """Device seconds of the ops in ``scope``, plus the unscoped copies
    of a whole pool of ``pools``."""
    t = 0.0
    for ev, path in zip(ops, paths):
        if in_scope(path, scope) or (
                pools and whole_pool_copy(ev, path, pools)):
            t += ev.dur
    return t


def idle_by_phase(ops: list, spans: list[Span],
                  device: int = 0) -> dict[str, float]:
    """Seconds of device idle (the gaps between the merged intervals of
    the device's ops) covered by each phase; an idle instant goes to the
    innermost covering phase span (the one that started last), so a
    swap or snapshot copy nested in a phase counts toward that phase.
    ``"none"`` takes idle time that no phase covers."""
    iv = trace_reduce.union([ev for ev in ops if ev.device == device])
    gaps = [(a1, b0) for (_, a1), (b0, _) in zip(iv, iv[1:]) if b0 > a1]
    phases = [s for s in spans if s.phase is not None]
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        cover = [s for s in phases if s.start < g1 and s.end > g0]
        cuts = sorted({g0, g1} | {t for s in cover for t in (s.start, s.end)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [s for s in cover if s.start <= mid < s.end]
            key = max(inner, key=lambda s: s.start).phase if inner \
                else "none"
            out[key] = out.get(key, 0.0) + (b - a)
    return out


def n_steps(spans: list[Span]) -> int:
    return sum(1 for s in spans if s.name == "step")


def fill_share(spans: list[Span]) -> float | None:
    """Real token slots fed over padded ones, %, over the dispatch
    spans: a prefill chunk feeds ``tokens`` of ``chunk``, a decode call
    ``rows`` of ``bucket`` (a spec call ``tokens`` of ``chunk``)."""
    real = padded = 0
    for s in spans:
        if s.phase != "dispatch":
            continue
        st = s.stats
        if "chunk" in st:
            real += st.get("tokens", 0)
            padded += st["chunk"]
        elif "bucket" in st:
            real += st.get("rows", 0)
            padded += st["bucket"]
    return 100.0 * real / padded if padded else None


# ---------------------------------------------------- a run's context


def trace_dir(ctx) -> str:
    import run
    return os.path.join(run.OUT_DIR, "trace", ctx.cell.name)


_CACHE: dict[str, dict] = {}


def _loaded(ctx) -> dict | None:
    """Spans, per-step count and op paths of the run's newest xplane,
    read once per file; None with no trace or no ``engine.step`` span."""
    if ctx.trace is None:
        return None
    path = trace_reduce.newest_xplane(trace_dir(ctx))
    if path is None:
        return None
    if path not in _CACHE:
        spans = load_spans(path)
        got = {"spans": spans, "steps": n_steps(spans), "paths": None,
               "path": path}
        _CACHE.clear()
        _CACHE[path] = got
    got = _CACHE[path]
    return got if got["steps"] else None


def spans(ctx) -> list[Span] | None:
    got = _loaded(ctx)
    return got["spans"] if got else None


def idle_ms_per_step(ctx, phase: str) -> float | None:
    """Device idle covered by ``phase`` spans, ms per step."""
    got = _loaded(ctx)
    if got is None or not any(ev.device == 0 for ev in ctx.trace.ops):
        return None
    if "idle" not in got:
        got["idle"] = idle_by_phase(ctx.trace.ops, got["spans"])
    return 1e3 * got["idle"].get(phase, 0.0) / got["steps"]


def scope_ms_per_step(ctx, scope: str, with_pool_copies: bool = False
                      ) -> float | None:
    """Device time of the ops in ``scope``, ms per step (with the
    unscoped copies of a whole pool the engine holds, ``ctx.pools``,
    when asked)."""
    got = _loaded(ctx)
    if got is None or not ctx.trace.ops:
        return None
    if got["paths"] is None:
        paths = op_paths(ctx.trace.ops, ctx.trace.modules,
                         program_op_names(got["path"]))
        # a program without named scopes reads nothing
        scoped = any(in_scope(p, s) for p in paths for s in SCOPES)
        got["paths"] = paths if scoped else []
    if not got["paths"]:
        return None
    pools = ctx.pools if with_pool_copies else ()
    return 1e3 * scope_time(ctx.trace.ops, got["paths"], scope, pools) \
        / got["steps"]
