"""Device busy time in the traced window per router dispatch in it."""


def read(run):
    d = run.counters.get("dispatches")
    if run.trace is None or not d:
        return None
    return 1e3 * run.trace["busy_s"] / d
