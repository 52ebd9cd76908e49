"""Production mesh construction (multi-pod dry-run §1).

Defined as functions (not module constants) so importing never touches jax
device state.  Production target: TPU v5e, 256 chips/pod, 16x16 (data, model)
per pod; the multi-pod mesh adds a leading "pod" axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh as _compat_make_mesh

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _compat_make_mesh(shape, axes)


def make_mesh(shape, axes):
    return _compat_make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, have {n}")
    return _compat_make_mesh((data, model), ("data", "model"))
