"""Plain float64 host reference of training the single-channel DONN
classifier: the paper's loss, its mask gradients and Adam's update.

Written from the physics and the optimizer's published rule, not from the
program: like ``donn_classifier.py`` it imports nothing of ``repro`` (nor
JAX), and it takes from that module only the transfer function, the gaps,
the detector regions and the input encoding.  The forward is the one that
module describes:

    u_0     = encode(image)
    layer i : v_i = hop_i(u_i),  u_{i+1} = m_i v_i,  m_i = gamma exp(j q_i)
    w       = hop_final(u_L),  z_c = sum over region c of |w|^2
    loss    = mean over the batch of sum_c (softmax(z)_c - onehot_c)^2

with ``q_i`` the phase the device shows (codesign "qat": the mask snapped
to the nearest of ``device_levels`` states, in the masks' float32).

Gradients by the adjoint method.  For a real loss and a complex field
``a``, write ``g_a = dL/dRe(a) + j dL/dIm(a)``; then

    g_w     = 2 w dL/dI                      (I = |w|^2, dL/dI the class's
                                             dL/dz_c over its region)
    g_u     = hop^H(g_v) = ifft2(conj(H) fft2(g_v))   for each hop
    g_v_i   = conj(m_i) g_u_{i+1}
    dL/dq_i = sum over the batch of Im(conj(u_{i+1}) g_u_{i+1})

Quantization-aware training's straight-through estimator passes dL/dq
through the snap unchanged, so the mask gradient is the derivative at the
snapped phase.  ``adam`` replays Adam (Kingma & Ba, bias-corrected, with
decoupled weight decay as AdamW has it) on the masks in float64.

Everything runs on the host in float64 with scipy's FFTs, one thread per
core over chunks of images, and sums the chunks' gradients.
"""
from __future__ import annotations

import importlib.util
import math
import os
import pathlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft

_spec = importlib.util.spec_from_file_location(
    "perfbench_references_donn_classifier_base",
    pathlib.Path(__file__).with_name("donn_classifier.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
transfer, gaps, regions, encode = (_base.transfer, _base.gaps, _base.regions,
                                   _base.encode)

SUPPORTED = {"approximation": "rs", "pad": False, "channels": 1,
             "segmentation": False, "detector_layout": "grid",
             "layers": None, "response_gamma": 1.0, "layer_norm": False}


def _check(cfg: dict) -> None:
    for k, v in SUPPORTED.items():
        if cfg.get(k, v) != v:
            raise NotImplementedError(f"reference covers {k}={v!r} only, "
                                      f"got {cfg.get(k)!r}")
    if cfg["codesign"] not in ("none", "qat"):
        raise NotImplementedError(f"codesign {cfg['codesign']!r}")


def _hop(u, h):
    return scipy.fft.ifft2(scipy.fft.fft2(u) * h)


def adam(phases, mu, nu, step: int, grads, lr: float, b1: float = 0.9,
         b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0) -> tuple:
    """One bias-corrected Adam step from optimizer step ``step`` (0 for
    the first), with decoupled weight decay: (phases, mu, nu) after it,
    in float64."""
    g = np.asarray(grads, np.float64)
    mu = b1 * np.asarray(mu, np.float64) + (1.0 - b1) * g
    nu = b2 * np.asarray(nu, np.float64) + (1.0 - b2) * g * g
    t = step + 1
    mh = mu / (1.0 - b1 ** t)
    vh = nu / (1.0 - b2 ** t)
    phases = np.asarray(phases, np.float64)
    return (phases - lr * (mh / (np.sqrt(vh) + eps) + weight_decay * phases),
            mu, nu)


class Reference:
    """The configuration's loss and mask gradients on the host, in
    float64."""

    CHUNK = 4  # images per task

    def __init__(self, cfg: dict):
        _check(cfg)
        self.cfg = cfg
        n, dx, lam = cfg["n"], cfg["pixel_size"], cfg["wavelength"]
        self.tfs = [transfer(n, dx, z, lam) for z in gaps(cfg)]
        self.coords = regions(n, cfg["num_classes"], cfg["det_size"])
        self.gamma = 1.0 if cfg["gamma"] is None else float(cfg["gamma"])

    def effective_phase(self, phi) -> np.ndarray:
        """The phase the device shows: the masks as given, or with "qat"
        snapped to the nearest level, in the masks' own float32: the
        wrapped phase times levels / 2 pi, rounded half to even.

        A phase within a rounding of the midpoint between two levels
        snaps to either, and a single bright pixel's level moves every
        layer's gradient by up to 1e-2 (relative), so the scaling is
        written as a multiplication, the rounding XLA gives a division by
        a constant."""
        phi = np.asarray(phi, np.float32)
        if self.cfg["codesign"] != "qat":
            return phi.astype(np.float64)
        levels = np.float32(self.cfg["device_levels"])
        two_pi = np.float32(2.0 * math.pi)
        step = np.float32(two_pi / levels)
        wrapped = np.mod(phi, two_pi)
        snapped = np.mod(np.round(wrapped * np.float32(levels / two_pi)),
                         levels)
        return snapped.astype(np.float64) * float(step)

    def _chunk(self, mods, images, labels, batch: int):
        """(summed loss, summed mask gradients) of one chunk of images."""
        d = self.cfg["det_size"]
        k = self.cfg["num_classes"]
        u = encode(images, self.cfg["n"]).astype(np.complex128)
        outs = []
        for h, m in zip(self.tfs, mods):
            u = _hop(u, h) * m
            outs.append(u)
        w = _hop(u, self.tfs[-1])
        inten = w.real ** 2 + w.imag ** 2
        z = np.stack([inten[:, y:y + d, x:x + d].sum(axis=(-2, -1))
                      for y, x in self.coords], axis=-1)
        e = np.exp(z - z.max(axis=-1, keepdims=True))
        p = e / e.sum(axis=-1, keepdims=True)
        diff = p - np.eye(k)[labels]
        loss = float(np.sum(diff ** 2))
        gp = 2.0 * diff / batch
        gz = p * (gp - np.sum(gp * p, axis=-1, keepdims=True))
        gi = np.zeros(inten.shape)
        for c, (y, x) in enumerate(self.coords):
            gi[:, y:y + d, x:x + d] += gz[:, c, None, None]
        g = _hop(2.0 * w * gi, np.conj(self.tfs[-1]))
        grads = np.zeros((len(mods),) + inten.shape[1:])
        for i in range(len(mods) - 1, -1, -1):
            grads[i] = np.sum(np.imag(np.conj(outs[i]) * g), axis=0)
            if i:
                g = _hop(np.conj(mods[i]) * g, np.conj(self.tfs[i]))
        return loss, grads

    def loss_and_grads(self, phases, images, labels) -> tuple:
        """(loss, (L, n, n) gradients of the loss in the masks) of one
        batch, the loss a mean over its images."""
        return self.shown_loss_and_grads(self.effective_phase(phases),
                                         images, labels)

    def shown_loss_and_grads(self, shown, images, labels) -> tuple:
        """``loss_and_grads`` in the phases the device shows, taken as
        they are (float64, no snap)."""
        mods = self.gamma * np.exp(1j * np.asarray(shown, np.float64))
        images = np.asarray(images, np.float32)
        labels = np.asarray(labels)
        b = len(images)
        spans = [(lo, lo + self.CHUNK) for lo in range(0, b, self.CHUNK)]
        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            parts = list(pool.map(
                lambda s: self._chunk(mods, images[s[0]:s[1]],
                                      labels[s[0]:s[1]], b), spans))
        loss = sum(part[0] for part in parts) / b
        return loss, sum(part[1] for part in parts)

    def train(self, phases, mu, nu, step: int, xs, ys, opt: dict) -> tuple:
        """Adam steps from the state (phases, mu, nu, step) over the
        batches ``xs``/``ys`` (one a step), ``opt`` holding ``adam``'s
        hyperparameters: (the loss at each step, the gradients of the
        first)."""
        phases = np.asarray(phases, np.float64)
        losses, first = [], None
        for i, (xb, yb) in enumerate(zip(xs, ys)):
            loss, grads = self.loss_and_grads(phases, xb, yb)
            losses.append(loss)
            if first is None:
                first = grads
            if i + 1 < len(xs):
                phases, mu, nu = adam(phases, mu, nu, step + i, grads,
                                      **opt)
        return np.array(losses), first
