"""Model-level DONN layers (LightRidge `lr.layers`, Table 2).

- ``DiffractiveLayer``: free-space propagation over z followed by trainable
  phase modulation.  ``codesign="none"`` corresponds to
  ``lr.layers.diffractlayer_raw``; any quantizing mode corresponds to the
  hardware-aware ``lr.layers.diffractlayer``.
- ``Detector``: pre-defined per-class readout regions; converts the field to
  intensity and pools each region (the paper's optical/photon detector + ADC).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codesign as cd
from repro.core import diffraction as df
from repro.nn import ParamSpec


class DiffractiveLayer:
    """One diffractive layer: propagate(z) then phase-modulate.

    The transfer function is precomputed at build time (static geometry); the
    trainable parameter is the (n, n) phase map.
    """

    def __init__(
        self,
        grid: df.Grid,
        z: float,
        wavelength: float,
        method: str = df.RS,
        band_limit: bool = True,
        pad: bool = False,
        device: Optional[cd.DeviceSpec] = None,
        codesign_mode: str = "none",
        gamma: float = 1.0,
    ):
        self.grid = grid
        self.z = z
        self.wavelength = wavelength
        self.method = method
        self.pad = pad
        self.device = device
        self.codesign_mode = codesign_mode
        self.gamma = gamma
        if method == df.FRAUNHOFER:
            self.h = None  # handled by df.fraunhofer at call time
        else:
            from repro.core.propagation import cached_transfer_function

            self.h = cached_transfer_function(
                grid, z, wavelength, method, band_limit, pad=pad
            )
        self._band_limit = band_limit
        self._h_dev = None  # device-side TF, uploaded once on first use

    def param_spec(self) -> ParamSpec:
        n = self.grid.n
        return ParamSpec(
            (n, n), jnp.float32, ("field_h", "field_w"), init="uniform_phase"
        )

    def propagate(self, u: jax.Array) -> jax.Array:
        if self.method == df.FRAUNHOFER:
            return df.fraunhofer(u, self.grid, self.z, self.wavelength)
        h_dev = self._h_dev
        if h_dev is None:
            h_dev = jnp.asarray(self.h)
            # cache only concrete arrays (a jit trace yields a Tracer here)
            if not isinstance(h_dev, jax.core.Tracer):
                self._h_dev = h_dev
        if self.pad:
            n = self.grid.n
            return df.crop_field(df.propagate_tf(df.pad_field(u, n), h_dev), n)
        return df.propagate_tf(u, h_dev)

    def modulate(
        self, phi: jax.Array, u: jax.Array, rng: Optional[jax.Array] = None
    ) -> jax.Array:
        phi_eff = cd.apply_codesign(phi, self.device, self.codesign_mode, rng)
        mod = self.gamma * jnp.exp(1j * phi_eff.astype(jnp.complex64))
        return u * mod

    def __call__(
        self, phi: jax.Array, u: jax.Array, rng: Optional[jax.Array] = None
    ) -> jax.Array:
        return self.modulate(phi, self.propagate(u), rng)


def detector_region_coords(
    n: int, num_classes: int, det_size: int, layout: str = "grid"
) -> list[tuple[int, int]]:
    """Top-left (y, x) corners of per-class detector regions.

    "grid": classes arranged in balanced rows centered on the plane (the
    3-4-3 style layout of Lin et al. for 10 classes generalized).
    "ring": regions on a circle (alternative layout for many classes).
    """
    coords: list[tuple[int, int]] = []
    if layout == "ring":
        r = 0.33 * n
        for c in range(num_classes):
            a = 2.0 * math.pi * c / num_classes
            y = int(n / 2 + r * math.sin(a)) - det_size // 2
            x = int(n / 2 + r * math.cos(a)) - det_size // 2
            coords.append((y, x))
        return coords
    rows = max(1, int(round(math.sqrt(num_classes))))
    base, extra = divmod(num_classes, rows)
    counts = [base + (1 if i < extra else 0) for i in range(rows)]
    # interleave so middle rows get the extras (3-4-3 for 10/3)
    counts.sort()
    mid = len(counts) // 2
    ordered = sorted(range(rows), key=lambda i: abs(i - mid))
    row_counts = [0] * rows
    for cnt, i in zip(sorted(counts, reverse=True), ordered):
        row_counts[i] = cnt
    lo, hi = 0.18 * n, 0.82 * n
    ys = np.linspace(lo, hi, rows + 1)
    ys = 0.5 * (ys[:-1] + ys[1:])
    for ri, cnt in enumerate(row_counts):
        xs = np.linspace(lo, hi, cnt + 1)
        xs = 0.5 * (xs[:-1] + xs[1:])
        for x in xs:
            coords.append((int(ys[ri]) - det_size // 2, int(x) - det_size // 2))
    return coords[:num_classes]


class Detector:
    """lr.layers.detector: per-class region intensity pooling."""

    def __init__(
        self,
        grid: df.Grid,
        num_classes: int,
        det_size: int,
        layout: str = "grid",
        x_loc=None,
        y_loc=None,
    ):
        n = grid.n
        self.grid = grid
        self.num_classes = num_classes
        self.det_size = det_size
        if x_loc is not None and y_loc is not None:
            coords = list(zip(list(y_loc), list(x_loc)))
        else:
            coords = detector_region_coords(n, num_classes, det_size, layout)
        self.coords = coords
        masks = np.zeros((num_classes, n, n), np.float32)
        for c, (y, x) in enumerate(coords):
            masks[c, y : y + det_size, x : x + det_size] = 1.0
        self.masks = masks

    def __call__(self, u: jax.Array) -> jax.Array:
        """Field (..., n, n) -> per-class intensities (..., C)."""
        return df.readout(u, jnp.asarray(self.masks))

    def intensity_image(self, u: jax.Array) -> jax.Array:
        return df.intensity(u)
