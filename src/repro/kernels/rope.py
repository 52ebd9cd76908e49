"""Pallas kernel: rotary position embedding (rotate-half convention).

RoPE is an elementwise complex rotation: x is viewed as (x1 + j x2) pairs
and rotated by exp(j theta_s,d):

    out1 = x1 cos - x2 sin,  out2 = x2 cos + x1 sin

Fusing the rotation avoids the concat/slice/mul/add chain XLA emits for the
unfused formulation.  Layout: (BN, S, D) with D the lane dim (head_dim, a
multiple of 2; padded to 128 lanes by the wrapper when needed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[0]  # (bs, D)
    c = cos_ref[...]  # (bs, D//2)
    s = sin_ref[...]
    d2 = x.shape[-1] // 2
    x1 = x[:, :d2]
    x2 = x[:, d2:]
    o_ref[0] = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_pallas(x, cos, sin, *, bs: int, interpret: bool):
    """x: (BN, S, D); cos/sin: (S, D//2)."""
    BN, S, D = x.shape
    grid = (BN, S // bs)
    x_spec = pl.BlockSpec((1, bs, D), lambda b, i: (b, i, 0))
    cs_spec = pl.BlockSpec((bs, D // 2), lambda b, i: (i, 0))
    return pl.pallas_call(
        _rope_kernel,
        grid=grid,
        in_specs=[x_spec, cs_spec, cs_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, cos, sin)
