"""Pure-jnp oracle for the RoPE Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp


def rope_ref(x, cos, sin):
    """Rotate-half RoPE: x (B, S, D), cos/sin (S, D//2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
