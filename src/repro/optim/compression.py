"""Int8 error-feedback gradient compression for cross-pod reduction.

At 1000+ node scale the cross-pod (DCI) links are the scarcest bandwidth;
quantizing the cross-pod gradient exchange to int8 with per-block scales
cuts that traffic 4x.  Error feedback (Seide et al. '14, Karimireddy et
al. '19) accumulates the quantization residual locally and re-injects it
next step, preserving convergence (tests/test_compression.py).

``compressed_psum_mean`` is the collective used inside a shard_map'd pod
axis: all-gather the int8 payloads + f32 scales (4x fewer bytes than an
f32 ring all-reduce) and reduce locally.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

BLOCK = 2048


def _pad_to(x, m):
    n = x.size
    pad = (-n) % m
    return jnp.pad(x.reshape(-1), (0, pad)), n


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array, int]:
    """x (any shape) -> (int8 blocks (nb, BLOCK), scales (nb,), true size)."""
    flat, n = _pad_to(x.astype(jnp.float32), BLOCK)
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32), n


def dequantize_int8(q: jax.Array, scale: jax.Array, n: int, shape, dtype):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]
    return flat.reshape(shape).astype(dtype)


def ef_quantize(x: jax.Array, err: jax.Array):
    """Error-feedback quantize: returns (q, scale, n, new_err)."""
    comp = x.astype(jnp.float32) + err
    q, scale, n = quantize_int8(comp)
    deq = dequantize_int8(q, scale, n, x.shape, jnp.float32)
    return q, scale, n, comp - deq


def compressed_psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` with int8-compressed exchange.

    Must run inside shard_map with ``axis_name`` a manual axis.  Payload:
    int8 blocks + f32 scales (~ x.nbytes/4 + x.nbytes/(4*BLOCK)).
    """
    g = jax.lax.axis_size(axis_name)
    q, scale, n = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)  # (g, nb, BLOCK) int8
    ss = jax.lax.all_gather(scale, axis_name)  # (g, nb)
    total = jnp.sum(qs.astype(jnp.float32) * ss[..., None], axis=0)
    flat = total.reshape(-1)[:n]
    return (flat / g).reshape(x.shape).astype(x.dtype)


def tree_compressed_psum_mean(tree, axis_name: str):
    return jax.tree.map(lambda x: compressed_psum_mean(x, axis_name), tree)


def compression_ratio(x: jax.Array) -> float:
    """Achieved wire-bytes ratio vs f32 all-reduce (per hop)."""
    q, scale, n = quantize_int8(x)
    wire = q.size + scale.size * 4
    return (n * 4) / wire
