"""BENCHMARK.json against the rules every later PR is held to, and the
data-driven layout: a cell is added by adding files only."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        assert harness.kind_module(cell.traffic) is not None
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
            assert m["moves"] in e2e


def test_a_cell_defined_in_new_files_only(tmp_path, bench):
    """A new configuration, traffic mix and per-layer metric, added as
    files and entries, load without editing any existing file."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = json.loads(json.dumps(bench))
    (root / "perfbench/configs/graph-tiny.json").write_text(json.dumps(
        {"vertices": 512, "edges": 1024, "k": 4, "p_in": 0.9,
         "limits": {}}))
    (root / "perfbench/traffic/graph.once.json").write_text(json.dumps(
        {"kind": "fit_graph"}))
    (root / "perfbench/metrics/jobs_done.py").write_text(
        "def read(ctx):\n    return len(ctx.counters.get('x', [1]))\n")
    new["configs"].append({"name": "graph-tiny", "source": "a test",
                           "file": "perfbench/configs/graph-tiny.json",
                           "reduced": [], "why": "a test"})
    new["workloads"].append({"name": "fit.tiny", "config": "graph-tiny",
                             "traffic": "graph.once", "chips": 1,
                             "why": "a test"})
    new["per_layer"].append({"name": "jobs_done", "unit": "jobs",
                             "better": "higher", "source": "host_clock",
                             "layer": "a test", "moves": "setup_s",
                             "workloads": ["fit.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = harness.find_cell(harness.load_benchmark(str(root)), "fit.tiny",
                             root=str(root))
    assert cell.config["vertices"] == 512
    assert cell.traffic["kind"] == "fit_graph"
    assert [m["name"] for m in cell.per_layer] == ["jobs_done"]
    read = harness.metric_reader("jobs_done", root=str(root))
    assert read(harness.Context(cell=cell, seed=0)) == 1
    before = harness.find_cell(bench, "fit.paper-graph")
    assert before.config["vertices"] == 10_029


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "fit.paper-graph",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_off_the_tpu_fails_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_run_without_the_program_fails_without_a_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
