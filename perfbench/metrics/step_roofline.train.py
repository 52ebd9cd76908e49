"""The compiled training chunk's share of its roofline: the least time the
chip could take for the window's steps (operations over peak or
compulsory bytes over bandwidth, whichever is longer, ``work_train.py``)
over the device's busy time."""
from perfbench import work_train


def read(run):
    steps = run.counters.get("steps")
    if run.peak is None or run.trace is None or not run.samples or not steps:
        return None
    ops, nbytes = work_train.window_work(run.fields, run.samples, steps)
    least = max(ops / run.peak["ops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / run.trace["busy_s"]
