"""Device self time of the forward and inverse FFTs (stages ``fft`` and
``ifft``) per call in the traced window."""


def read(run):
    st = run.trace.get("stages") if run.trace else None
    if not st or not run.calls:
        return None
    return 1e3 * (st.get("fft", 0.0) + st.get("ifft", 0.0)) / run.calls
