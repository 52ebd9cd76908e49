"""jit'd public wrappers around the LM stack's Pallas kernels.

Handles: padding to block multiples, block-size selection (128-lane /
8-sublane alignment), and backend dispatch (compiled Mosaic path on TPU,
interpret mode on CPU so the tests exercise the kernels; any other backend
is refused rather than silently interpreted).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rope as _rp
from repro.kernels import ref


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"{backend!r} backend is neither"
    )


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --------------------------------------------------------------------------
# apply_rope: unitary rotation; VJP rotates cotangent by -theta.
# --------------------------------------------------------------------------
def _rope_raw(x3, cos, sin):
    BN, S, D = x3.shape
    bs = min(_ceil_to(S, 8), 256)
    Sp = _ceil_to(S, bs)
    if Sp != S:
        x3 = jnp.pad(x3, [(0, 0), (0, Sp - S), (0, 0)])
        cos = jnp.pad(cos, [(0, Sp - S), (0, 0)])
        sin = jnp.pad(sin, [(0, Sp - S), (0, 0)])
    out = _rp.rope_pallas(x3, cos, sin, bs=bs, interpret=_interpret())
    return out[:, :S, :]


@jax.custom_vjp
def _rope(x3, cos, sin):
    return _rope_raw(x3, cos, sin)


def _rope_fwd(x3, cos, sin):
    return _rope_raw(x3, cos, sin), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    return _rope_raw(g, cos, -sin), jnp.zeros_like(cos), jnp.zeros_like(sin)


_rope.defvjp(_rope_fwd, _rope_bwd)


@jax.jit
def apply_rope(x, cos, sin):
    """x: (..., S, D) rotate-half RoPE with cos/sin (S, D//2)."""
    lead = x.shape[:-2]
    S, D = x.shape[-2:]
    out = _rope(x.reshape((-1, S, D)), cos, sin)
    return out.reshape(lead + (S, D))


# re-export the oracle for tests
rope_ref = ref.rope_ref


# --------------------------------------------------------------------------
# selective_scan: mamba-1 SSM forward (inference path; no custom VJP —
# training uses the chunked jnp scan in repro.models.ssm).
# --------------------------------------------------------------------------
@jax.jit
def selective_scan(dt, x, bs, cs, a):
    """dt/x (B, S, D); bs/cs (B, S, N); a (D, N) -> y (B, S, D) float32."""
    from repro.kernels import selective_scan as _ss

    B, S, D = x.shape
    bd = min(_ceil_to(D, 128), 512)
    Dp = _ceil_to(D, bd)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, Dp - D)]
        dt = jnp.pad(dt, pad)
        x = jnp.pad(x, pad)
        a = jnp.pad(a, [(0, Dp - D), (0, 0)])
    y = _ss.selective_scan_pallas(
        dt.astype(jnp.float32), x.astype(jnp.float32),
        bs.astype(jnp.float32), cs.astype(jnp.float32),
        a.astype(jnp.float32), bd=bd, interpret=_interpret(),
    )
    return y[..., :D]


def selective_scan_ref(dt, x, bs, cs, a):
    """Pure-jnp oracle (wraps the model's chunked scan, zero init)."""
    from repro.models.ssm import _selective_scan

    B, S, D = x.shape
    h0 = jnp.zeros((B, D, a.shape[-1]), jnp.float32)
    y, _ = _selective_scan(dt.astype(jnp.float32), bs.astype(jnp.float32),
                           cs.astype(jnp.float32), x.astype(jnp.float32),
                           a.astype(jnp.float32), h0, chunk=64)
    return y
