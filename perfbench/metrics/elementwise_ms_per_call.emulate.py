"""Device self time of the elementwise stages (``tf_mul``, ``modulate``
and the fused Pallas hop ``fused_hop``) per call in the traced window."""


def read(run):
    st = run.trace.get("stages") if run.trace else None
    if not st or not run.calls:
        return None
    return 1e3 * sum(st.get(k, 0.0)
                     for k in ("tf_mul", "modulate", "fused_hop")) / run.calls
