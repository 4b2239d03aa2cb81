"""Reduce one profiler trace to device busy time, per-op and per-program
device time, and the longest idle gaps labelled by the host's spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.  Device planes are those named ``/device:TPU:<n>``; their "XLA Ops"
line holds one event per operation (a Pallas kernel is one event) and
their "XLA Modules" line one event per program run.  Host spans are the
``bench.*`` ``TraceAnnotation`` events the benchmark puts around
``Engine.submit``, ``Engine.step``, the scheduler and its own waits.
Times are in seconds.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str
    label: str          # name plus the event's string stats, for matching
    start: float
    dur: float
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: list[Event] = field(default_factory=list)       # device ops
    modules: list[Event] = field(default_factory=list)   # device programs
    host: list[Event] = field(default_factory=list)      # bench.* spans
    devices: int = 0


def newest_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _label(ev) -> str:
    parts = [ev.name]
    try:
        for _k, v in ev.stats:
            if isinstance(v, str):
                parts.append(v)
    except Exception:           # stats are optional decoration
        pass
    return " ".join(parts)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = tr.devices
            tr.devices += 1
            for line in plane.lines:
                dest = {"XLA Ops": tr.ops,
                        "XLA Modules": tr.modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append(Event(ev.name, _label(ev),
                                      ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.host.append(Event(ev.name, ev.name,
                                             ev.start_ns * 1e-9,
                                             ev.duration_ns * 1e-9))
    return tr


def union(events: list[Event]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals covered by ``events``."""
    out: list[list[float]] = []
    for s, e in sorted((ev.start, ev.end) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    n = max(tr.devices, 1)
    return sum(e - s for d in range(n)
               for s, e in union([ev for ev in tr.ops if ev.device == d])) / n


def matching(events: list[Event], names) -> list[Event]:
    return [ev for ev in events if any(n in ev.label for n in names)]


def device_time(events: list[Event]) -> float:
    return sum(ev.dur for ev in events)


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    by = defaultdict(float)
    for ev in tr.ops:
        by[ev.name] += ev.dur
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between device operations, each labelled by
    the innermost host span covering the gap's middle ("host.none" when
    no ``bench.*`` span does)."""
    iv = union([ev for ev in tr.ops if ev.device == 0])
    gaps = [(b0 - a1, a1, b0) for (_, a1), (b0, _) in zip(iv, iv[1:])
            if b0 > a1]
    gaps.sort(reverse=True)
    out = []
    for dur, s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [h for h in tr.host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.dur).name if cover else "host.none"
        out.append([name, dur])
    return out


def to_json(tr: Trace, t0: float | None = None,
            t1: float | None = None) -> dict:
    """The trace's events (within [t0, t1] when given) as plain data."""
    def keep(ev):
        return (t0 is None or ev.start >= t0) and (t1 is None or ev.end <= t1)

    def rows(evs):
        return [[e.name, e.label, e.start, e.dur, e.device]
                for e in evs if keep(e)]
    return {"devices": tr.devices, "ops": rows(tr.ops),
            "modules": rows(tr.modules), "host": rows(tr.host)}


def from_json(d: dict) -> Trace:
    def evs(rows):
        return [Event(*r) for r in rows]
    return Trace(evs(d["ops"]), evs(d["modules"]), evs(d["host"]),
                 d["devices"])
