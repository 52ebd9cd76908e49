"""The join of device ops to the program's stages, the per-stage readers
and the stage split of a whole CPU run."""
import json
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import fixture  # noqa: E402
from perfbench import harness, spec, stages, trace  # noqa: E402

REPO = HERE.parents[1]
M = "jit_run"
# window 0..100 ns.  One call: a while loop [10, 80] whose body holds an
# fft [12, 30], a fusion [30, 50] and a copy no stage owns [50, 55]; an
# unscoped op the map does not know [85, 90]; a readout [90, 110] that the
# window clips at 100.
EVENTS = {
    "spans": [["window", 0, 100], ["cached_apply", 2, 90]],
    "devices": {
        "/device:TPU:0": [
            ["%while.5 = (s32[]) while(...)", 10, 70],
            ["%fft.3 = c64[2,8] fft(...)", 12, 18],
            ["%fusion.1 = c64[2,8] fusion(...)", 30, 20],
            ["%copy.2 = c64[2,8] copy(...)", 50, 5],
            ["%add.9 = f32[2] add(...)", 85, 5],
            ["%reduce.7 = f32[2] reduce(...)", 90, 20],
        ],
    },
    "hlo": {"/device:TPU:0": [(M, "while.5"), (M, "fft.3"), (M, "fusion.1"),
                              (M, "copy.2"), (M, "add.9"),
                              (M, "reduce.7")]},
    "program_spans": [["donn.dispatch", 0, 12], ["donn.compile", 2, 5],
                      ["donn.dispatch", 80, 6]],
}
# add.9 has a stage in another module only
MAP = {(M, "fft.3"): "fft", (M, "fusion.1"): "tf_mul",
       (M, "reduce.7"): "readout", ("other", "add.9"): "encode"}


def test_self_times_by_stage():
    got = stages.stage_seconds(EVENTS, MAP)
    # while: 70 - (18 + 20 + 5) = 27 of loop control, + copy 5, + add 5
    assert got == pytest.approx({"fft": 18e-9, "tf_mul": 20e-9,
                                 "readout": 10e-9, "unscoped": 37e-9})
    busy = trace.reduce(EVENTS)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_stage_seconds_average_over_devices():
    dev1 = [["%fft.3 = c64 fft()", 0, 40]]
    events = {"spans": [["window", 0, 100]],
              "devices": {"/device:TPU:0": dev1,
                          "/device:TPU:1": [["%fft.3 = c64 fft()", 0, 20]],
                          "/device:TPU:2": []},
              "hlo": {"/device:TPU:0": [(M, "fft.3")],
                      "/device:TPU:1": [(M, "fft.3")], "/device:TPU:2": []},
              "program_spans": []}
    got = stages.stage_seconds(events, MAP)
    assert got == pytest.approx({"fft": 30e-9})
    assert got["fft"] == pytest.approx(trace.reduce(events)["busy_s"])


def test_idle_gaps_go_to_the_innermost_program_span():
    got = stages.program_gaps(EVENTS)
    # gaps [0, 10]: dispatch 0-2, compile 2-7 (inner), dispatch 7-10;
    # [80, 85]: dispatch; the window ends busy
    assert got == pytest.approx({"donn.dispatch": 10e-9,
                                 "donn.compile": 5e-9})


def test_no_window_reads_nothing():
    events = {**EVENTS, "spans": []}
    assert stages.stage_seconds(events, MAP) is None
    assert stages.program_gaps(events) is None


def test_reduce_on_the_recorded_trace_is_unchanged():
    events = json.loads((HERE / "fixtures" / "cpu_emulate_events.json")
                        .read_text())
    want = json.loads((HERE / "fixtures" / "cpu_emulate_reduced.json")
                      .read_text())
    assert trace.reduce(events) == want


def _run(**kw):
    fields = dict(fixture.TINY)
    base = dict(fields=fields, setup_s=1.0, window_s=2.0, attempted=8,
                failed=0, samples=8, calls=2, frozen=False,
                latencies_ms=None, counters={},
                trace={"busy_s": 0.5, "window_s": 2.0, "idle_share": 0.75,
                       "stages": {"fft": 0.1, "ifft": 0.2, "tf_mul": 0.05,
                                  "modulate": 0.03, "readout": 0.02,
                                  "unscoped": 0.1}},
                peak={"ops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return harness.Run(**{**base, **kw})


def _fft_roofline():
    from perfbench import work

    ops = work.forward_work(fixture.TINY)["fft_ops_per_sample"] * 8
    return 100.0 * ops / 1e12 / 0.3


READERS = {
    "fft_ms_per_call.emulate": 150.0,
    "elementwise_ms_per_call.emulate": 40.0,
    "readout_ms_per_call.emulate": 10.0,
    "fft_roofline.emulate": _fft_roofline(),
    "unscoped_share.emulate": 20.0,
    "compiles.emulate": 0,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_readers(name):
    read = spec.load_module(REPO, "metrics", name).read
    assert read(_run(counters={"compiles": 0})) == pytest.approx(
        READERS[name])
    # nothing to read: no trace, a trace without stages, no counter
    assert read(_run(trace=None)) is None
    assert read(_run(trace={"busy_s": 0.5, "window_s": 2.0,
                            "idle_share": 0.75})) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixture.make(tmp_path_factory.mktemp("bench"))


def test_split_of_a_cpu_run(root):
    from perfbench import stage_split

    out = stage_split.split(root, fixture.EMULATE_CELL, 2 ** 31 + 17, 0.3,
                            t_start=time.perf_counter(), require_chip=False)
    assert out["samples_per_s"]["untraced"] > 0
    assert out["samples_per_s"]["traced"] > 0
    assert out["counters"] == {"compiles": 0, "cache_loads": 0}
    # the CPU trace holds no TPU plane: only the counter is read
    assert out["metrics"] == {"compiles.emulate": {"value": 0.0,
                                                   "unit": "compiles"}}
    assert "breakdown" not in out
    json.dumps(out)
