"""The training cell's kind, driven through whole runs on the CPU.

``fixture.make`` gives a copy of the benchmark; a tiny training cell is
added to it by files and entries alone, the way the chip cell was added.
The harness must find it by name, the program must come out correct, and
the control (the program's bfloat16 transfer planes), both planted faults
and a chunk whose update leaves the masks as they are must not.
"""
import io
import json
import pathlib
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(REPO), str(HERE)]

import fixture  # noqa: E402
from perfbench import harness, spec  # noqa: E402

SEED = 2 ** 31 + 8191
CHIP_CELL = "mnist5l-train-b512"
TRAIN_CELL = "tiny-train-b8"
TRAIN_METRICS = ("mfu.train", "step_roofline.train", "idle_share.train",
                 "compiles.train")
# The tiny cell's own limit, from its CPU readings over five seeds: program
# 3.0e-6 to 6.6e-6, control 1.1e-2 to 5.9e-2, an unchanged state 7.3e-2 to
# 0.27, the other faults 0.99 or more.  The chip cell's limit sits higher
# because the chip's packed hop carries about ten times the float32 error
# of the CPU's FFTs, at logits twenty times larger.
LIMIT = 1e-3


def _dump(path, obj):
    path.write_text(json.dumps(obj, indent=2) + "\n")


def add_train_cell(root: pathlib.Path) -> None:
    """A CPU-sized training cell on the fixture's configuration, added as
    files and entries."""
    pb = root / "perfbench"
    mix = json.loads((pb / "mixes" / "train_b512_s8.json").read_text())
    _dump(pb / "mixes" / "train_b8_s4.json",
          {**mix, "batch": 8, "steps_per_call": 4, "pool": 256})
    cell = json.loads((pb / "cells" / f"{CHIP_CELL}.json").read_text())
    _dump(pb / "cells" / f"{TRAIN_CELL}.json",
          {**cell, "limits": {"max_rel_err": LIMIT}, "trace_seconds": 0.5})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": TRAIN_CELL, "config": "donn-tiny", "traffic": "train_b8_s4",
         "chips": 1, "why": "fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CHIP_CELL in m.get("workloads", ()):
            m["workloads"].append(TRAIN_CELL)
    _dump(root / "BENCHMARK.json", bench)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = fixture.make(tmp_path_factory.mktemp("bench"))
    add_train_cell(root)
    return root


def run(root, variant, trace=False):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, TRAIN_CELL, SEED, 0.3, trace,
                           t_start=time.perf_counter(), require_chip=False,
                           variant=variant, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


def test_train_cell_added_as_files_is_found(root):
    bench = spec.load_benchmark(root)
    assert spec.validate(bench) == []
    c = harness.load_cell(root, TRAIN_CELL)
    assert c.mix["kind"] == "train"
    assert set(c.cell["limits"]) == {"max_rel_err"}
    assert callable(c.traffic.compare)
    assert callable(spec.load_module(root, "references",
                                     c.cell["reference"]).Reference)
    names = {m["name"] for m in spec.per_layer_for(bench, TRAIN_CELL)}
    assert names == set(TRAIN_METRICS)
    assert {m["name"] for m in spec.e2e_for(bench, TRAIN_CELL)} == \
        {"samples_per_s", "setup_s"}


def test_program_is_correct_and_counts_its_chunks(root):
    res, out, err = run(root, "program")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert json.loads(out.strip().splitlines()[-1]) == res
    assert err.strip().splitlines()[-1].startswith("[perfbench] check ")
    assert res["metrics"]["samples_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("variant", ["control", "fault:altered_answer",
                                     "fault:half_batch",
                                     "fault:unchanged_state"])
def test_control_and_faults_are_not_correct(root, variant):
    res, _, _ = run(root, variant)
    assert not res["correct"], res["checks"]


def test_checked_chunk_is_the_first_from_the_seeded_masks(root, monkeypatch):
    c = harness.load_cell(root, TRAIN_CELL)
    seen = {}
    window = c.traffic.window

    def spy(state, *args):
        res = window(state, *args)
        seen.update(res["check"], masks=harness.make_params(
            SEED, state["ctx"].cfg.depth, state["ctx"].cfg.n))
        return res

    monkeypatch.setattr(c.traffic, "window", spy)
    monkeypatch.setattr(harness, "load_cell", lambda *a: c)
    res, _, _ = run(root, "program")
    assert res["correct"], res["checks"]
    layers = seen["masks"]["phase"]
    np.testing.assert_array_equal(
        seen["phases"], np.stack([np.asarray(layers[f"layer_{i}"])
                                  for i in range(len(layers))]))
    assert seen["step"] == 0
    assert not seen["mu"].any() and not seen["nu"].any()
    assert len(seen["losses"]) == len(seen["xs"]) == c.cell["check_steps"]


def test_window_counts_chunks_steps_and_no_compiles(root, monkeypatch):
    c = harness.load_cell(root, TRAIN_CELL)
    seen = {}
    window = c.traffic.window

    def spy(state, *args):
        res = window(state, *args)
        seen.update(res)
        return res

    monkeypatch.setattr(c.traffic, "window", spy)
    monkeypatch.setattr(harness, "load_cell", lambda *a: c)
    res, _, _ = run(root, "program", trace=True)
    assert res["correct"], res["checks"]
    steps = seen["calls"] * c.mix["steps_per_call"]
    assert seen["counters"]["chunks"] == seen["calls"] >= 1
    assert seen["counters"]["steps"] == steps
    assert seen["samples"] == steps * c.mix["batch"]
    assert seen["counters"]["compiles"] == 0
    # a CPU trace holds no TPU plane: only the program counter reads
    assert res["metrics"] == {"compiles.train": {"value": 0.0,
                                                 "unit": "programs"}}
