"""Whole-step share of the chip's peak: the algorithm's operations for the
window's training steps (``work_train.py``: forward, loss, adjoint, Adam)
over the window and the peak."""
from perfbench import work_train


def read(run):
    steps = run.counters.get("steps")
    if run.peak is None or not run.samples or not steps:
        return None
    ops, _ = work_train.window_work(run.fields, run.samples, steps)
    return 100.0 * ops / (run.window_s * run.peak["ops_per_s"])
