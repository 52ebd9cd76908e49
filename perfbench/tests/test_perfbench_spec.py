"""BENCHMARK.json, the files it names, and the entry point's refusals."""
import copy
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(REPO), str(HERE)]

import fixture  # noqa: E402
from perfbench import harness, spec  # noqa: E402

BENCH = spec.load_benchmark(REPO)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
READERS = sorted(p.stem for p in (REPO / "perfbench" / "metrics").glob("*.py"))


def test_benchmark_is_valid():
    assert spec.validate(BENCH) == []


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = harness.load_cell(REPO, cell)
    assert callable(c.traffic.setup) and callable(c.traffic.window)
    assert callable(c.traffic.release) and callable(c.traffic.compare)
    assert c.reference.Reference is not None
    assert set(c.cell["limits"]) == {"max_rel_err"}
    if c.cfg_file.get("registry"):
        harness.build_config(c.cfg_file["fields"], c.cfg_file["registry"])


@pytest.mark.parametrize("name", READERS)
def test_metric_reader_found_by_name(name):
    assert callable(spec.load_module(REPO, "metrics", name).read)


def test_every_metric_has_a_reader():
    assert set(METRICS) <= set(READERS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_metrics_move(cell):
    e2e = {m["name"] for m in spec.e2e_for(BENCH, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer_for(BENCH, cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("where,key,bad", [
    ("workloads", "name", "has space"),
    ("workloads", "name", "a/b"),
    ("workloads", "traffic", "x" * 65),
    ("end_to_end", "unit", "tokens per second"),
    ("end_to_end", "unit", "µs"),
    ("per_layer", "name", "no,comma"),
    ("per_layer", "moves", "not_a_metric"),
    ("configs", "name", ".leading_dot"),
    ("end_to_end", "bound", 0.3),
    ("end_to_end", "source", "program_span"),
])
def test_validate_refuses_bad_names_and_units(where, key, bad):
    bench = copy.deepcopy(BENCH)
    bench[where][0][key] = bad
    assert spec.validate(bench)


def test_validate_refuses_unknown_key():
    bench = copy.deepcopy(BENCH)
    bench["per_layer"][0]["why"] = "not a key of the format"
    assert spec.validate(bench)


def test_fixture_cell_added_as_files_is_found(tmp_path):
    root = fixture.make(tmp_path)
    bench = spec.load_benchmark(root)
    assert spec.validate(bench) == []
    for cell in (fixture.EMULATE_CELL, fixture.SERVE_CELL):
        c = harness.load_cell(root, cell)
        assert c.workload["config"] == "donn-tiny"
        names = [m["name"] for m in spec.per_layer_for(bench, cell)]
        assert names and all(
            callable(spec.load_module(root, "metrics", n).read)
            for n in names)
    # and the repository's own files are untouched by it
    assert fixture.EMULATE_CELL not in json.dumps(BENCH)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "perfbench/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_refuses_a_host_without_a_tpu():
    p = _run(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
              "--trace", "0"], REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    root = fixture.make(tmp_path)  # BENCHMARK.json and perfbench/ alone
    p = _run(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
              "--trace", "0"], root, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
