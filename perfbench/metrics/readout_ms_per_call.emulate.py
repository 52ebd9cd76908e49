"""Device self time of the detector readout (stage ``readout``) per call
in the traced window."""


def read(run):
    st = run.trace.get("stages") if run.trace else None
    if not st or not run.calls:
        return None
    return 1e3 * st.get("readout", 0.0) / run.calls
