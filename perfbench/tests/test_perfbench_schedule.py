"""The open-loop schedule is a pure function of the mix and the seed."""
import collections
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import spec  # noqa: E402

serve = spec.load_module(REPO, "traffic", "serve_poisson")
MIX = {"rate_hz": 1500.0, "pool": 4096, "replicas": 1, "gap_seed": 0}
BIG = 2 ** 31 + 977  # seeds may exceed 32 signed bits


def test_same_seed_same_schedule():
    a = serve.schedule(MIX, 10.0, BIG)
    b = serve.schedule(MIX, 10.0, BIG)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_seeds_share_gaps_in_another_order():
    due_a, idx_a = serve.schedule(MIX, 10.0, BIG)
    due_b, idx_b = serve.schedule(MIX, 10.0, 7)
    assert len(due_a) == len(due_b) == 15000
    assert not np.array_equal(due_a, due_b)
    assert not np.array_equal(idx_a, idx_b)
    # the same gaps in another order: all but the one that follows the
    # last arrival appear between arrivals in both (to cumsum rounding)
    gaps_a = collections.Counter(np.round(np.diff(due_a), 10))
    gaps_b = collections.Counter(np.round(np.diff(due_b), 10))
    assert sum(((gaps_a - gaps_b) + (gaps_b - gaps_a)).values()) <= 2 + \
        len(due_a) // 1000  # rounding may split a few ties


@pytest.mark.parametrize("seconds", [1.0, 10.0])
def test_schedule_fills_the_window(seconds):
    due, idx = serve.schedule(MIX, seconds, BIG)
    assert due[0] == 0.0
    assert np.all(np.diff(due) >= 0)
    assert due[-1] < seconds
    assert len(due) == round(MIX["rate_hz"] * seconds)
    assert idx.min() >= 0 and idx.max() < MIX["pool"]
    # the mean gap is the mix's rate
    assert np.mean(np.diff(due)) == pytest.approx(1 / MIX["rate_hz"],
                                                  rel=0.01)
