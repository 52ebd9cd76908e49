"""Whole-step share of the chip's peak: the algorithm's operations for
the window's forwards (``work.py``) over the window and the peak."""
from perfbench import work


def read(run):
    if run.peak is None or not run.samples:
        return None
    ops, _ = work.window_work(run.fields, run.samples, run.calls, run.frozen)
    return 100.0 * ops / (run.window_s * run.peak["ops_per_s"])
