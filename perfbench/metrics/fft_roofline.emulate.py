"""The FFTs' share of their roofline: the least time the chip could take
for the window's transforms (the algorithm's FFT operations, ``work.py``,
over the peak) over the device self time of stages ``fft`` and ``ifft``.
The count reads no implementation flag, so an FFT run as a DFT on the
matrix unit is judged on the same work."""
from perfbench import work


def read(run):
    st = run.trace.get("stages") if run.trace else None
    if run.peak is None or not st or not run.samples:
        return None
    fft_s = st.get("fft", 0.0) + st.get("ifft", 0.0)
    if fft_s <= 0:
        return None
    ops = work.forward_work(run.fields, run.frozen)["fft_ops_per_sample"]
    return 100.0 * ops * run.samples / run.peak["ops_per_s"] / fft_s
