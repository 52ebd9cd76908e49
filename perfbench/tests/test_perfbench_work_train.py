"""Training operation and byte counts from shapes, against hand counts."""
import json
import math
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import work, work_train  # noqa: E402


def fields(name="donn-mnist-5l"):
    path = REPO / "perfbench" / "configs" / f"{name}.json"
    return json.loads(path.read_text())["fields"]


def test_adjoint_transforms_match_hand_count():
    # the final hop and the 4 hops between the 5 layers, two 40,000-point
    # transforms each at 5 M log2 M
    w = work_train.train_work(fields())
    m = 200 * 200
    assert w["adjoint_fft_ops_per_sample"] == pytest.approx(
        5 * 2 * 5 * m * math.log2(m))
    assert w["adjoint_fft_ops_per_sample"] == pytest.approx(30.58e6,
                                                            rel=1e-3)


def test_per_sample_work_is_forward_adjoint_and_loss():
    f = fields()
    m, d, k, det = 200 * 200, 5, 10, 20
    w = work_train.train_work(f)
    # conjugate TF multiply per adjoint hop, readout adjoint per detector
    # pixel, modulation adjoint of the 4 inner layers, mask gradient and
    # its batch sum per layer
    hand = d * 6 * m + 2 * k * det * det + (d - 1) * 6 * m + d * 4 * m
    assert w["adjoint_elementwise_ops_per_sample"] == hand
    assert w["loss_ops_per_sample"] == 12 * k
    assert w["ops_per_sample"] == pytest.approx(
        work.forward_work(f)["ops_per_sample"]
        + w["adjoint_fft_ops_per_sample"] + hand + 12 * k)
    # a step costs under twice a forward: the adjoint skips the first hop
    ratio = w["ops_per_sample"] / work.forward_work(f)["ops_per_sample"]
    assert 1.8 < ratio < 2.0


def test_per_step_work_is_masks_and_adam():
    f = fields()
    m, d = 200 * 200, 5
    w = work_train.train_work(f)
    assert w["ops_per_step"] == d * 2 * m + 14 * d * m
    assert w["bytes_per_step"] == 8 * (d + 1) * m + 4 * d * m \
        + 24 * d * m + 8
    assert w["bytes_per_sample"] == 4 * (28 * 28 + 1)


@pytest.mark.parametrize("flag,value", [
    ("use_pallas", True), ("engine", "eager"), ("scan_unroll", 1),
    ("tf_dtype", "bfloat16"), ("remat", "layer"),
])
def test_counts_ignore_implementation_flags(flag, value):
    f = fields()
    assert work_train.train_work(dict(f, **{flag: value})) == \
        work_train.train_work(f)


def test_window_work_sums_samples_and_steps():
    f = fields()
    w = work_train.train_work(f)
    ops, nbytes = work_train.window_work(f, samples=4096, steps=8)
    assert ops == 4096 * w["ops_per_sample"] + 8 * w["ops_per_step"]
    assert nbytes == 4096 * w["bytes_per_sample"] + 8 * w["bytes_per_step"]
