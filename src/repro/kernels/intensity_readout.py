"""Pallas kernel: detector intensity readout (|U|^2 + region pooling).

Fuses the squared-magnitude and the per-class masked reduction — the
paper's detector/ADC interface — into one pass over the field, instead of
materializing the (B, H, W) intensity image in HBM and re-reading it for the
(C, H, W) mask contraction.

Grid: (B, nH, nW); the (H, W) tiles are reduction steps that accumulate into
the (1, C, 1) output block (TPU grids execute sequentially, so revisiting the
output block across reduction steps is well-defined).  The output is laid out
(B, C, 1) so that the block's last two dims equal the array's, as Mosaic
requires; a (1, C) block over a (B, C) array is refused for B > 1.  The
partial sums stay 2-D (C, 1) because Mosaic cannot relayout a 1-D vector.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _readout_kernel(ur_ref, ui_ref, m_ref, o_ref):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ur, ui = ur_ref[0], ui_ref[0]  # (bh, bw)
    inten = ur * ur + ui * ui
    rows = jnp.sum(m_ref[...] * inten[None], axis=2)  # (C, bh)
    o_ref[0] += jnp.sum(rows, axis=1, keepdims=True)  # (C, 1)


def intensity_readout_pallas(ur, ui, masks, *, bh: int, bw: int, interpret: bool):
    """ur/ui: (B, H, W), masks: (C, H, W) -> (B, C) pooled intensities."""
    B, H, W = ur.shape
    C = masks.shape[0]
    grid = (B, H // bh, W // bw)
    u_spec = pl.BlockSpec((1, bh, bw), lambda b, i, j: (b, i, j))
    m_spec = pl.BlockSpec((C, bh, bw), lambda b, i, j: (0, i, j))
    o_spec = pl.BlockSpec((1, C, 1), lambda b, i, j: (b, 0, 0))
    out = pl.pallas_call(
        _readout_kernel,
        grid=grid,
        in_specs=[u_spec, u_spec, m_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, 1), jnp.float32),
        interpret=interpret,
    )(ur, ui, masks)
    return out[:, :, 0]
