"""Operation and byte counts from shapes, against hand counts."""
import json
import math
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import work  # noqa: E402


def fields(name):
    path = REPO / "perfbench" / "configs" / f"{name}.json"
    return json.loads(path.read_text())["fields"]


# 5 M log2 M per M-point transform, two transforms a hop, depth + 1 hops
@pytest.mark.parametrize("name,hops,side,fft_ops", [
    ("donn-mnist-5l", 6, 200, 36.7e6),
    ("donn-xl-500", 31, 500, 1.39e9),
])
def test_fft_ops_match_hand_counts(name, hops, side, fft_ops):
    w = work.forward_work(fields(name))
    m = side * side
    assert w["fft_ops_per_sample"] == pytest.approx(
        hops * 2 * 5 * m * math.log2(m))
    assert w["fft_ops_per_sample"] == pytest.approx(fft_ops, rel=2e-3)


@pytest.mark.parametrize("name", ["donn-mnist-5l", "donn-xl-500"])
def test_elementwise_passes_on_top(name):
    f = fields(name)
    m, d = f["n"] ** 2, f["depth"]
    w = work.forward_work(f)
    # TF multiply per hop, mask multiply per layer (6 per complex
    # product), intensity (3 a pixel), one add per detector pixel
    hand = (d + 1) * 6 * m + d * 6 * m + 3 * m \
        + f["num_classes"] * f["det_size"] ** 2
    assert w["elementwise_ops_per_sample"] == hand
    assert w["ops_per_sample"] == w["fft_ops_per_sample"] + hand
    assert w["ops_per_call"] == d * 2 * m
    assert work.forward_work(f, frozen=True)["ops_per_call"] == 0
    assert w["bytes_per_call"] == 8 * (d + 1) * m + 4 * d * m
    assert work.forward_work(f, frozen=True)["bytes_per_call"] == \
        8 * (d + 1) * m + 8 * d * m


@pytest.mark.parametrize("flag,value", [
    ("use_pallas", True), ("engine", "eager"), ("scan_unroll", 1),
    ("tf_dtype", "bfloat16"), ("remat", "layer"),
])
@pytest.mark.parametrize("name", ["donn-mnist-5l", "donn-xl-500"])
def test_counts_ignore_implementation_flags(name, flag, value):
    f = fields(name)
    assert work.forward_work(dict(f, **{flag: value})) == \
        work.forward_work(f)


def test_window_work_sums_samples_and_calls():
    f = fields("donn-xl-500")
    w = work.forward_work(f)
    ops, nbytes = work.window_work(f, samples=128, calls=2)
    assert ops == 128 * w["ops_per_sample"] + 2 * w["ops_per_call"]
    assert nbytes == 128 * w["bytes_per_sample"] + 2 * w["bytes_per_call"]
