"""Pallas TPU kernel: the folded hop's block mix in one pass.

A layer of the folded hop modulates the field's four folded blocks
(``diffraction.fold_field``) by mixing them: ``H(mod o H(x))``, with ``H``
the 4-point Hadamard transform over the blocks and ``mod`` the layer's
``diffraction.fold_modulation`` stack (``diffraction.modulate_folded``).
As jnp the TPU compiler runs that mix as five loop passes over the blocks
and the next dot reads it as four operands.  This kernel reads each
pixel's eight block planes (4 blocks x real/imaginary) and the layer's
eight modulation planes once, runs both butterflies and the complex
multiply in registers with the jnp mix's float32 adds and multiplies in
its order, and writes the eight output planes once, in the (4, 2, B, m, m)
layout the next forward dot reads.

Grid: (row tiles of m, batch), the batch innermost, so each row tile of
the modulation is fetched once per call.  A row tile is at most 128 rows,
a multiple of 8; m itself is ragged against the (8, 128) tile (250, 193,
...), so the last dimension is a full-extent block and the last row tile
may be partial (its rows past m are read undefined and never written).

The backward is the VJP of the jnp mix (``jax.custom_vjp``), so gradients
are exactly the jnp path's.  This module imports Pallas; the propagation
plan imports it only where it runs the kernel
(``propagation._mix_kernel_applies``).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import diffraction as df

_MAX_TILE_ROWS = 128
_STRIP = 8  # rows a step of the kernel's loop: one sublane tile
# float32 operations a pixel position: two butterflies of 8 adds over the
# (real, imaginary) pairs of 4 blocks, and 4 complex multiplies of 6
_FLOPS_PER_PIXEL = 2 * 2 * 8 + 4 * 6


def tile_rows(m: int) -> int:
    """Rows of a plane per grid step: m split into ceil(m / 128) tiles of
    equal height, rounded up to a multiple of 8."""
    tiles = -(-m // _MAX_TILE_ROWS)
    return -(-(-(-m // tiles)) // _STRIP) * _STRIP


def _butterflies(b: list) -> list:
    """``diffraction._hadamard`` on four blocks, each a [real, imag] pair
    of arrays, with the same adds in the same order."""
    def add(p, q):
        return [p[0] + q[0], p[1] + q[1]]

    def sub(p, q):
        return [p[0] - q[0], p[1] - q[1]]

    s, t = add(b[0], b[1]), sub(b[0], b[1])
    u, v = add(b[2], b[3]), sub(b[2], b[3])
    return [add(s, u), add(t, v), sub(s, u), sub(t, v)]


def _mix_kernel(x_ref, mod_ref, o_ref):
    """One row tile of one image: refs (4, 2, rows, m), in 8-row strips."""
    def strip(i, carry):
        rows = pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP)
        q = _butterflies([[x_ref[k, c, rows, :] for c in (0, 1)]
                          for k in range(4)])
        y = []
        for k in range(4):
            mr, mi = mod_ref[k, 0, rows, :], mod_ref[k, 1, rows, :]
            qr, qi = q[k]
            y.append([mr * qr - mi * qi, mr * qi + mi * qr])
        for k, block in enumerate(_butterflies(y)):
            for c in (0, 1):
                o_ref[k, c, rows, :] = block[c]
        return carry

    jax.lax.fori_loop(0, x_ref.shape[-2] // _STRIP, strip, 0)


def _interpret() -> bool:
    """Off a TPU the kernel runs in Pallas' interpreter."""
    return jax.default_backend() != "tpu"


def _mix(x: jax.Array, mod: jax.Array) -> jax.Array:
    """The kernel on blocks ``x`` (4, 2, B, m, m) and one layer's ``mod``
    (4, 2, m, m)."""
    b, h, w = x.shape[2:]
    rows = tile_rows(h)
    field = pl.BlockSpec((4, 2, pl.squeezed, rows, w),
                         lambda r, i: (0, 0, i, r, 0))
    plane = pl.BlockSpec((4, 2, rows, w), lambda r, i: (0, 0, r, 0))
    pixels = b * h * w
    return pl.pallas_call(
        _mix_kernel,
        grid=(pl.cdiv(h, rows), b),
        in_specs=[field, plane],
        out_specs=field,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=_FLOPS_PER_PIXEL * pixels,
            bytes_accessed=(2 * x.size + mod.size) * x.dtype.itemsize,
            transcendentals=0),
        name="fold_mix",
        interpret=_interpret(),
    )(x, mod)


@jax.custom_vjp
def modulate_folded(x: jax.Array, mod: jax.Array) -> jax.Array:
    """``diffraction.modulate_folded`` for float32 blocks ``x`` (4, 2, B,
    m, m) and one layer's ``mod`` (4, 2, m, m), as one pass."""
    return _mix(x, mod)


def _fwd(x, mod):
    return _mix(x, mod), (x, mod)


def _bwd(res, g):
    return jax.vjp(df.modulate_folded, *res)[1](g)


modulate_folded.defvjp(_fwd, _bwd)

