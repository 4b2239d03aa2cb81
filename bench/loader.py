"""Where a run finds the files of a cell: by the names that
``BENCHMARK.json`` and the cell's own files give, under ``BENCH`` (this
directory).  Nothing here names a configuration, a family, a mix or a
metric, so each of them is brought as new files alone:

    configs/<config>.json       the sizes as run (``BENCHMARK.json`` names
                                the file); its ``family`` picks the family
    traffic/<traffic>.json      a mix, read by ``traffic_gen``; its
                                ``arrivals.process`` picks the process
    workloads/<cell>.json       the cell's rate, engine sizes and limits
    arrivals/<process>.py       ``times(spec, n, rate, horizon_s, rng)``:
                                ``n`` ascending arrival times in
                                [0, horizon_s), the seed's order from rng
    families/<family>/          the program's ``ArchConfig.family``:
      weights.py                ``make(conf, seed)``: the seeded weight tree
                                in the program's layout, one jitted call
      reference.py              ``logits_at(params, conf, tokens, positions,
                                *, dtype)``: plain float32 ``jax.numpy`` at
                                the highest precision, importing nothing of
                                the program
      costs.py                  ``projections(c)``, ``float_ops(c, tokens)``
                                and ``mixer(c, rows)``: the family's share of
                                the work counts (``costs.py``)
      tiny.json                 the widths the CPU tests cut any cell of the
                                family to (``tests/bench_tiny.py``)
    metrics/<name>.py           ``read(ctx)``: one per-layer metric

Each module is executed from its path once and kept.
"""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))

_MODULES: dict[str, object] = {}


class BenchError(RuntimeError):
    """A run that cannot produce a result."""


def module(path: str):
    """The module in ``path``, executed once."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise BenchError(f"no file {path}")
        name = "bench:" + path
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
        _MODULES[path] = mod
    return _MODULES[path]


def family_dir(conf: dict) -> str:
    """``families/<family>/`` of a configuration."""
    d = os.path.join(BENCH, "families", conf["family"])
    if not os.path.isdir(d):
        raise BenchError(f"no family {conf['family']!r}: no directory {d}")
    return d


def family(conf: dict, part: str):
    """The configuration's family's module ``part`` (``weights``,
    ``reference`` or ``costs``)."""
    return module(os.path.join(family_dir(conf), part + ".py"))


def arrivals(process: str):
    """The arrival process ``arrivals/<process>.py``."""
    path = os.path.join(BENCH, "arrivals", process + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no arrival process {process!r}: no file {path}")
    return module(path)
