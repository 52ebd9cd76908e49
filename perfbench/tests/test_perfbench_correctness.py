"""The correctness check, driven through whole runs on the CPU.

Each fixture cell runs the harness with its chip check skipped: the
program must come out correct, and the control (the program's own
bfloat16 path) and every planted fault must come out not correct, under
the limits of the chip cells the fixtures shrink.
"""
import io
import json
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import fixture  # noqa: E402
from perfbench import harness  # noqa: E402

SEED = 2 ** 31 + 4099


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixture.make(tmp_path_factory.mktemp("bench"))


def run(root, cell, variant, trace=False):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, cell, SEED, 0.3, trace,
                           t_start=time.perf_counter(), require_chip=False,
                           variant=variant, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("cell", [fixture.EMULATE_CELL, fixture.SERVE_CELL])
def test_program_is_correct(root, cell):
    res, out, err = run(root, cell, "program")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # the last stdout line is the result, "checks" its last key; the last
    # stderr line gives each checked number beside its limit
    assert json.loads(out.strip().splitlines()[-1]) == res
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("[perfbench] check ")
    for m in res["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("variant", ["control", "fault:altered_answer",
                                     "fault:half_batch"])
@pytest.mark.parametrize("cell", [fixture.EMULATE_CELL, fixture.SERVE_CELL])
def test_control_and_faults_are_not_correct(root, cell, variant):
    res, _, _ = run(root, cell, variant)
    assert not res["correct"], res["checks"]


def test_traced_run_on_cpu_reads_no_device(root):
    res, _, _ = run(root, fixture.EMULATE_CELL, "program", trace=True)
    assert res["correct"]
    # the CPU trace holds no TPU plane: readers return nothing, and no
    # share of a roofline or peak is reported as 0
    assert res["metrics"] == {}
    assert "breakdown" not in res


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_plant_changes_a_batch_where_it_is_produced(fault):
    import numpy as np

    out = np.arange(40, dtype=np.float32).reshape(4, 10)
    got = harness.plant(out, fault)
    assert harness.plant(out, None) is out
    assert not np.array_equal(got, out)
    assert np.array_equal(out, np.arange(40).reshape(4, 10))  # a copy
    assert harness.fault_of(f"fault:{fault}") == fault
    assert harness.fault_of("program") is None
    with pytest.raises(ValueError):
        harness.fault_of("fault:unknown")
