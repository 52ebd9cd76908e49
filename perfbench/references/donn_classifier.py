"""Plain float64 host reference of a single-channel DONN classifier.

Written from the physics (LightRidge, arXiv 2306.11268, section 3.1) and
not from the program: it imports nothing of ``repro`` (nor JAX) and takes
only the configuration's fields, the phase masks the benchmark made from
the seed, and the input images.

    u0      = image, nearest-upsampled by n // input_size, centred on n x n
              (amplitude encoding, zero phase, plane-wave source of
              amplitude 1)
    hop(u)  = ifft2(fft2(u) * H_z), H_z the band-limited angular-spectrum
              (Rayleigh-Sommerfeld) transfer function of gap z
    layer i : u = gamma * exp(j phi_i) * hop_i(u); with codesign "qat" the
              phase first snaps to the nearest of the device's ``levels``
              uniform states on [0, 2 pi)
    output  = |hop_final(u)|^2 summed over each class's det x det region;
              the regions sit in centred rows (3-4-3 for ten classes)
              between 18% and 82% of the plane

Everything runs on the host in float64 with scipy's FFTs, one thread per
core over chunks of images, so the reference shares neither the
accelerator's FFT lowering nor its float32 rounding with the program.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft

SUPPORTED = {"approximation": "rs", "pad": False, "channels": 1,
             "segmentation": False, "detector_layout": "grid",
             "layers": None, "response_gamma": 1.0}


def _check(cfg: dict) -> None:
    for k, v in SUPPORTED.items():
        if cfg.get(k, v) != v:
            raise NotImplementedError(f"reference covers {k}={v!r} only, "
                                      f"got {cfg.get(k)!r}")
    if cfg["codesign"] not in ("none", "qat"):
        raise NotImplementedError(f"codesign {cfg['codesign']!r}")


def gaps(cfg: dict) -> list:
    if cfg.get("distances") is not None:
        return [float(d) for d in cfg["distances"]]
    return [float(cfg["distance"])] * (cfg["depth"] + 1)


def transfer(n: int, dx: float, z: float, lam: float) -> np.ndarray:
    """Band-limited angular-spectrum transfer function, fftfreq order."""
    f = np.fft.fftfreq(n, d=dx)
    fx, fy = np.meshgrid(f, f, indexing="ij")
    k = 2.0 * math.pi / lam
    arg = 1.0 - (lam * fx) ** 2 - (lam * fy) ** 2
    h = np.where(arg >= 0.0,
                 np.exp(1j * k * z * np.sqrt(np.maximum(arg, 0.0))),
                 np.exp(-k * abs(z) * np.sqrt(np.maximum(-arg, 0.0))))
    # Matsushima & Shimobaba (2009) band limit for a window of n * dx
    f_lim = 1.0 / (lam * math.sqrt((2.0 * z / (n * dx)) ** 2 + 1.0))
    h = h * ((np.abs(fx) <= f_lim) & (np.abs(fy) <= f_lim))
    return h


def regions(n: int, classes: int, det: int) -> list:
    """Top-left corners of the class regions: centred rows, the middle
    rows taking the classes that do not divide evenly."""
    rows = max(1, round(math.sqrt(classes)))
    per_row = [classes // rows] * rows
    # rows nearest the middle receive the remainder, one class each
    middle_first = sorted(range(rows), key=lambda r: abs(r - rows // 2))
    for r in middle_first[: classes % rows]:
        per_row[r] += 1
    lo, hi = 0.18 * n, 0.82 * n

    def centres(k):
        edges = np.linspace(lo, hi, k + 1)
        return (edges[:-1] + edges[1:]) / 2

    out = []
    for r, y in enumerate(centres(rows)):
        for x in centres(per_row[r]):
            out.append((int(y) - det // 2, int(x) - det // 2))
    return out[:classes]


def encode(images: np.ndarray, n: int) -> np.ndarray:
    h = images.shape[-1]
    s = n // h
    up = np.repeat(np.repeat(images.astype(np.float32), s, axis=-2), s,
                   axis=-1)
    p = n - up.shape[-1]
    pads = [(0, 0)] * (images.ndim - 2) + [(p // 2, p - p // 2)] * 2
    return np.pad(up, pads)


class Reference:
    """The configuration's forward on the host, in float64."""

    CHUNK = 4  # images per task

    def __init__(self, cfg: dict):
        _check(cfg)
        self.cfg = cfg
        n, dx, lam = cfg["n"], cfg["pixel_size"], cfg["wavelength"]
        self.tfs = [transfer(n, dx, z, lam) for z in gaps(cfg)]
        self.coords = regions(n, cfg["num_classes"], cfg["det_size"])
        self.gamma = 1.0 if cfg["gamma"] is None else float(cfg["gamma"])

    def effective_phase(self, phi: np.ndarray) -> np.ndarray:
        """The phase the device shows: the masks as given, or with "qat"
        snapped to the nearest level (in the masks' own float32)."""
        phi = np.asarray(phi, np.float32)
        if self.cfg["codesign"] != "qat":
            return phi.astype(np.float64)
        levels = self.cfg["device_levels"]
        two_pi = np.float32(2.0 * math.pi)
        step = np.float32(two_pi / np.float32(levels))
        wrapped = np.mod(phi, two_pi)
        snapped = np.mod(np.round(wrapped / step), np.float32(levels))
        return snapped.astype(np.float64) * float(step)

    def _forward(self, mods, fields):
        u = fields.astype(np.complex128)
        for h, m in zip(self.tfs, mods):
            u = scipy.fft.ifft2(scipy.fft.fft2(u) * h) * m
        u = scipy.fft.ifft2(scipy.fft.fft2(u) * self.tfs[-1])
        inten = u.real ** 2 + u.imag ** 2
        d = self.cfg["det_size"]
        return np.stack([inten[:, y:y + d, x:x + d].sum(axis=(-2, -1))
                         for y, x in self.coords], axis=-1)

    def logits(self, phases, images) -> np.ndarray:
        """(N, h, w) images -> (N, classes) float64."""
        mods = self.gamma * np.exp(1j * self.effective_phase(phases))
        images = np.asarray(images, np.float32)
        n = self.cfg["n"]
        chunks = [images[lo:lo + self.CHUNK]
                  for lo in range(0, len(images), self.CHUNK)]
        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            out = list(pool.map(
                lambda c: self._forward(mods, encode(c, n)), chunks))
        return np.concatenate(out)
