"""BENCHMARK.json resolves, by name, to files of the benchmark; the
command fails with no result where there is no chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    c = harness.resolve(BENCH, cell)
    assert c.cfg["chips"] == c.chips
    for kind, name in (("systems", c.cfg["system"]),
                       ("references", c.cfg["reference"]),
                       ("drivers", c.traffic["driver"])):
        assert harness.load_module(kind, name)
    names = [m["name"] for m in c.end_to_end]
    assert harness.SETUP in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        if m["name"] != harness.SETUP:
            assert callable(harness.load_module("metrics", m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in names           # it moves a metric the cell has


def test_names_units_and_paths_keep_to_the_format():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run(ROOT, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
