"""Share of device busy time in the traced window that no stage of the
forward accounts for: loop control, copies, ops that lost their scope."""


def read(run):
    st = run.trace.get("stages") if run.trace else None
    if not st or not run.trace["busy_s"]:
        return None
    return 100.0 * st.get("unscoped", 0.0) / run.trace["busy_s"]
