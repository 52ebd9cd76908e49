"""The comparison that decides ``correct``."""
from __future__ import annotations

import numpy as np


def worst_rel_err(out, ref) -> float:
    """Worst over rows of max |out - ref| / max |ref| within the row.

    Each answer (one image's class intensities) is judged on its own
    scale, so a row whose light mostly missed the detector is held as
    tightly as a bright one.  A non-finite output reads as infinity.
    """
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise ValueError(f"output shape {out.shape} != reference "
                         f"{ref.shape}")
    if not np.all(np.isfinite(out)):
        return float("inf")
    scale = np.max(np.abs(ref), axis=-1)
    err = np.max(np.abs(out - ref), axis=-1) / np.maximum(scale, 1e-30)
    return float(np.max(err))
