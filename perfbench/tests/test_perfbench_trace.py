"""The reduction from trace events to busy time, idle gaps and top ops."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from perfbench import trace  # noqa: E402

RECORDED = sorted((HERE / "fixtures").glob("*_events.json"))


def test_hand_made_trace():
    # window 0..100 ns; ops overlap on one device, another device idles
    events = {
        "spans": [["window", 0, 100], ["cached_apply", 5, 20],
                  ["logits_fetch", 60, 30], ["outside", 200, 50]],
        "devices": {
            "/device:TPU:0": [["fft", 10, 30], ["fusion.1", 30, 20],
                              ["fft", 70, 10], ["late", 95, 20]],
            "/device:TPU:1": [],
        },
    }
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(100e-9)
    # union: [10, 50] + [70, 80] + [95, 100] = 55 ns
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["idle_share"] == pytest.approx(0.45)
    # per-op time is clipped to the window; overlaps count for each op
    assert dict(r["device_ops"]) == pytest.approx(
        {"fft": 40e-9, "fusion.1": 20e-9, "late": 5e-9})
    assert [k for k, _ in r["device_ops"]][0] == "fft"
    # gaps: [0,10] cached_apply 5; [50,70] logits_fetch 10; [80,95]
    # logits_fetch 10 -> by span: cached_apply 10, logits_fetch 35
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"cached_apply": 10e-9, "logits_fetch": 35e-9})


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.reduce({"spans": [], "devices": {"d": [["x", 0, 5]]}}) \
        is None
    assert trace.reduce({"spans": [["window", 0, 10]],
                         "devices": {"/device:TPU:0": []}}) is None


def test_gap_without_span():
    events = {"spans": [["window", 0, 10]],
              "devices": {"/device:TPU:0": [["op", 2, 3]]}}
    assert dict(trace.reduce(events)["idle_gaps"]) == pytest.approx(
        {trace.NO_SPAN: 7e-9})


def _brute(events):
    """Busy time and per-span idle time on a 1 ns grid."""
    (lo, hi), = [(s, s + d) for n, s, d in events["spans"]
                 if n == "window"]
    lo, hi = int(lo), int(hi)
    dev = sorted(events["devices"])[0]
    busy = np.zeros(hi - lo, bool)
    for _, s, d in events["devices"][dev]:
        a, b = max(int(s), lo), min(int(s + d), hi)
        if b > a:
            busy[a - lo:b - lo] = True
    return busy.sum() * 1e-9, busy


@pytest.mark.skipif(not RECORDED, reason="no recorded trace kept")
@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_trace(path):
    events = json.loads(path.read_text())
    r = trace.reduce(events)
    busy, mask = _brute(events)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-6, abs=2e-9)
    assert r["idle_share"] == pytest.approx(1 - busy / r["window_s"],
                                            abs=1e-6)
    idle_total = sum(v for _, v in r["idle_gaps"])
    assert idle_total == pytest.approx(r["window_s"] - r["busy_s"],
                                       rel=1e-6, abs=2e-9)
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda kv: -kv[1])
    assert 0 < len(r["device_ops"]) <= 10
    names = {n for n, _, _ in events["spans"]} | {trace.NO_SPAN}
    assert {k for k, _ in r["idle_gaps"]} <= names
