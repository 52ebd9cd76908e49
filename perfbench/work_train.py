"""Operations and compulsory bytes of a DONN training step, from shapes.

A step is, for every sample of the batch, the forward (``work.
forward_work``), the loss and the adjoint pass back to the masks; then one
Adam update of the masks.  Beyond the forward, per sample:

- the adjoint hops: through the final hop and the depth - 1 hops between
  layers (the first hop needs none, the input taking no gradient), each a
  forward and an inverse transform (5 M log2 M apiece) and the conjugate
  transfer-function multiply (6 per complex product);
- the adjoint of the readout, 2 w dL/dI over the detector pixels (2 a
  pixel), and of the modulation of the depth - 1 layers the gradient
  passes back through (6 a pixel);
- the mask gradient of every layer, Im(conj(u) g) and its sum over the
  batch (4 a pixel);
- softmax and MSE, 6 operations a class forward (exp, sum, divide;
  subtract, square, add) and 6 backward.

Per step, independent of the batch: the forward's per-call work (the
masks scaled by gamma) and Adam, 14 operations a mask pixel (the two
moments 3 + 4, the bias corrections 2, square root, epsilon and divide 3,
step 2).

Compulsory bytes: per sample, the input image and its label; per step,
the forward's per-call bytes (each transfer function and mask read once)
and Adam's masks and moments read and written (24 a pixel), with the loss
and accuracy returned.

Nothing here reads an implementation flag (``use_pallas``, ``engine``,
``scan_unroll``, ``tf_dtype``, ``remat``).
"""
from __future__ import annotations

from perfbench import work

ADAM_OPS = 14  # per mask pixel and step
ADAM_BYTES = 24  # mask, mu, nu read and written, float32


def train_work(cfg: dict) -> dict:
    """Per-sample and per-step operations and bytes of a training step."""
    fw = work.forward_work(cfg)
    n, depth = cfg["n"], cfg["depth"]
    side = 2 * n if cfg.get("pad") else n
    m, px = side * side, n * n
    classes, det, inp = cfg["num_classes"], cfg["det_size"], cfg["input_size"]
    adjoint_fft = depth * 2 * work.fft_ops(m)
    adjoint_elementwise = (depth * 6 * m + 2 * classes * det * det
                           + (depth - 1) * 6 * px + depth * 4 * px)
    loss = 12 * classes
    per_sample = fw["ops_per_sample"] + adjoint_fft + adjoint_elementwise \
        + loss
    return {
        "adjoint_fft_ops_per_sample": adjoint_fft,
        "adjoint_elementwise_ops_per_sample": float(adjoint_elementwise),
        "loss_ops_per_sample": float(loss),
        "ops_per_sample": per_sample,
        "ops_per_step": fw["ops_per_call"] + ADAM_OPS * depth * px,
        "bytes_per_sample": 4.0 * (inp * inp + 1),
        "bytes_per_step": fw["bytes_per_call"] + ADAM_BYTES * depth * px
        + 8.0,
    }


def window_work(cfg: dict, samples: int, steps: int) -> tuple:
    """(operations, compulsory bytes) of ``steps`` steps over ``samples``."""
    w = train_work(cfg)
    ops = samples * w["ops_per_sample"] + steps * w["ops_per_step"]
    nbytes = samples * w["bytes_per_sample"] + steps * w["bytes_per_step"]
    return ops, nbytes
