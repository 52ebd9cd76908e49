"""The compiled forward's share of its roofline: the least time the chip
could take for the window's forwards (operations over peak or compulsory
bytes over bandwidth, whichever is longer) over the device's busy time."""
from perfbench import work


def read(run):
    if run.peak is None or run.trace is None or not run.samples:
        return None
    ops, nbytes = work.window_work(run.fields, run.samples, run.calls,
                                   run.frozen)
    least = max(ops / run.peak["ops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / run.trace["busy_s"]
