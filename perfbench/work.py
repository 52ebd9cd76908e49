"""Operations and compulsory bytes of a DONN forward, from shapes alone.

The counts are of the work the algorithm needs, so they are the same
whatever implements it (XLA's FFT, a DFT as matmuls, a fused kernel):

- one M-point complex 2-D transform costs 5 M log2 M operations (the
  radix-2 count); every hop is a forward and an inverse transform over
  the (padded) plane;
- elementwise passes per sample: the transfer-function multiply of every
  hop and the phase-mask multiply of every layer (6 operations per
  complex product), the intensity (3 per pixel) and the detector sums
  (one add per detector pixel);
- per call, independent of the batch: scaling cos/sin of the phase masks
  by gamma (2 per pixel per layer) when the masks are not frozen.  The
  cos and sin themselves are not counted.

Compulsory bytes per call: the input images and the output intensities,
and each hop's transfer function and each layer's mask read once.  The
field itself never has to leave the chip, so it is not counted.

Nothing here reads an implementation flag (``use_pallas``, ``engine``,
``scan_unroll``, ``tf_dtype``, ``remat``).
"""
from __future__ import annotations

import math


def fft_ops(points: int) -> float:
    return 5.0 * points * math.log2(points)


def forward_work(cfg: dict, frozen: bool = False) -> dict:
    """Per-sample and per-call operations and bytes of one forward.

    ``frozen`` is the serving form, whose masks were folded into
    (real, imag) float32 planes ahead of time.
    """
    n, depth = cfg["n"], cfg["depth"]
    if (cfg.get("approximation", "rs") == "fraunhofer"
            or cfg.get("channels", 1) != 1 or cfg.get("segmentation")):
        raise NotImplementedError("counts cover single-channel classifiers "
                                  "with angular-spectrum hops")
    side = 2 * n if cfg.get("pad") else n
    m, px = side * side, n * n
    hops = depth + 1
    classes, det, inp = cfg["num_classes"], cfg["det_size"], cfg["input_size"]
    fft = hops * 2 * fft_ops(m)
    elementwise = hops * 6 * m + depth * 6 * px + 3 * px + classes * det * det
    per_call_ops = 0.0 if frozen else depth * 2 * px
    mask_bytes = (8 if frozen else 4) * depth * px
    return {
        "fft_ops_per_sample": fft,
        "elementwise_ops_per_sample": float(elementwise),
        "ops_per_sample": fft + elementwise,
        "ops_per_call": per_call_ops,
        "bytes_per_sample": 4.0 * (inp * inp + classes),
        "bytes_per_call": 8.0 * hops * m + mask_bytes,
    }


def window_work(cfg: dict, samples: int, calls: int,
                frozen: bool = False) -> tuple:
    """(operations, compulsory bytes) of ``calls`` calls over ``samples``."""
    w = forward_work(cfg, frozen)
    ops = samples * w["ops_per_sample"] + calls * w["ops_per_call"]
    nbytes = samples * w["bytes_per_sample"] + calls * w["bytes_per_call"]
    return ops, nbytes
