"""A configuration's family, a mix's arrival process and a cell given as
new files alone are found by name and run: a toy family (a copy of the
dense family's files under another name), a toy process (a copy of
Poisson) and a cell of their own, in a directory of their own, run end
to end with ``correct`` true; a name with no files behind it is an
error that names the path it looked for."""
import json
import os
import re
import shutil

import numpy as np
import pytest

import bench_tiny
import costs
import loader
import run
import traffic_gen

QWEN = "qwen-bnn.chat-steady"


def _write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A checkout holding only the toy's files, searched by the loader.
    The program runs the toy as the dense family it copies."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(loader.BENCH, "families", "dense"),
                    bench / "families" / "toy",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "arrivals").mkdir()
    shutil.copy(os.path.join(loader.BENCH, "arrivals", "poisson.py"),
                bench / "arrivals" / "toy_poisson.py")
    qwen = run.load_cell(QWEN)
    _write(bench / "configs" / "toy.json", dict(qwen.config, family="toy"))
    _write(bench / "traffic" / "toy-chat.json",
           dict(qwen.mix, arrivals={"process": "toy_poisson"}))
    _write(bench / "workloads" / "toy.chat.json", qwen.spec)
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        e2e = json.load(f)["end_to_end"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.chat", "config": "toy",
                       "traffic": "toy-chat", "chips": 1}],
        "end_to_end": e2e, "per_layer": []})
    monkeypatch.setattr(loader, "BENCH", str(bench))
    arch = run.arch_config
    monkeypatch.setattr(run, "arch_config",
                        lambda conf: arch(dict(conf, family="dense")))
    return tmp_path


def test_a_family_given_as_new_files_runs_correct(toy):
    c = bench_tiny.cut(run.load_cell("toy.chat", root=str(toy)))
    assert c.config["family"] == "toy"
    res = bench_tiny.serve(c)
    assert res["correct"] is True and res["attempted"] > 0
    for part in ("weights", "reference", "costs"):
        assert loader.family(c.config, part).__file__.startswith(str(toy))
    assert loader.arrivals("toy_poisson").__file__.startswith(str(toy))
    dense = loader.module(os.path.join(bench_tiny.BENCH, "families",
                                       "dense", "costs.py"))
    rows = [(1, 50), (16, 16)]
    assert costs.mixer(c.config, rows) == dense.mixer(c.config, rows)
    assert costs.projections(c.config) == dense.projections(c.config)


@pytest.mark.parametrize("what,path", [
    ("family", os.path.join("families", "no-such")),
    ("process", os.path.join("arrivals", "no-such.py"))])
def test_an_unknown_name_names_the_missing_path(tmp_path, monkeypatch,
                                                what, path):
    monkeypatch.setattr(loader, "BENCH", str(tmp_path))
    with pytest.raises(run.BenchError,
                       match=re.escape(str(tmp_path / path))):
        if what == "family":
            loader.family({"family": "no-such"}, "weights")
        else:
            traffic_gen.arrival_times({"process": "no-such"}, 1.0, 10.0,
                                      np.random.default_rng(0))
