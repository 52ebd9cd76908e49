"""jit'd public wrappers around the Pallas kernels.

Handles: zero-padding to block multiples (zero is the identity element for
every kernel here), block-size selection (128-lane / 8-sublane alignment),
broadcasting, and backend dispatch (compiled Mosaic path on TPU,
interpret mode on CPU so the tests exercise the kernels; any other backend
is refused rather than silently interpreted).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import complex_mul as _cm
from repro.kernels import intensity_readout as _ir
from repro.kernels import rope as _rp
from repro.kernels import spectral_hop as _sh
from repro.kernels import ref


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"{backend!r} backend is neither (build the config with "
        f"use_pallas=False)"
    )


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_blocks(H: int, W: int, max_h: int = 64, max_w: int = 512):
    bw = min(_ceil_to(W, 128), max_w)
    bh = min(_ceil_to(H, 8), max_h)
    return bh, bw


def _pad2d(x, Hp, Wp):
    H, W = x.shape[-2], x.shape[-1]
    if H == Hp and W == Wp:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, Hp - H), (0, Wp - W)])


# --------------------------------------------------------------------------
# complex_mul: (B?, H, W) x (H, W) split-plane complex multiply.
# custom VJP: da = g * conj(b); db = sum_batch g * conj(a).
# --------------------------------------------------------------------------
def _complex_mul_raw(ar, ai, br, bi):
    B, H, W = ar.shape
    bh, bw = _pick_blocks(H, W)
    Hp, Wp = _ceil_to(H, bh), _ceil_to(W, bw)
    out_r, out_i = _cm.complex_mul_pallas(
        _pad2d(ar, Hp, Wp), _pad2d(ai, Hp, Wp),
        _pad2d(br, Hp, Wp), _pad2d(bi, Hp, Wp),
        bh=bh, bw=bw, interpret=_interpret(),
    )
    return out_r[..., :H, :W], out_i[..., :H, :W]


@jax.custom_vjp
def _complex_mul(ar, ai, br, bi):
    return _complex_mul_raw(ar, ai, br, bi)


def _complex_mul_fwd(ar, ai, br, bi):
    return _complex_mul_raw(ar, ai, br, bi), (ar, ai, br, bi)


def _complex_mul_bwd(res, g):
    ar, ai, br, bi = res
    gr, gi = g
    # d a = g * conj(b);  d b = sum_B g * conj(a)
    dar, dai = _complex_mul_raw(gr, gi, br, -bi)
    dbr = jnp.sum(gr * ar + gi * ai, axis=0)
    dbi = jnp.sum(gi * ar - gr * ai, axis=0)
    return dar, dai, dbr, dbi


_complex_mul.defvjp(_complex_mul_fwd, _complex_mul_bwd)


@jax.jit
def complex_mul(ar, ai, br, bi):
    """(B?, H, W) x (H, W) split-plane complex multiply via Pallas."""
    squeeze = ar.ndim == 2
    if squeeze:
        ar, ai = ar[None], ai[None]
    out_r, out_i = _complex_mul(ar, ai, br, bi)
    if squeeze:
        out_r, out_i = out_r[0], out_i[0]
    return out_r, out_i


# --------------------------------------------------------------------------
# phase_apply: gamma * u * exp(j phi).  VJP:
#   d u   = g * conj(gamma e^{j phi}) = rotation of g by -phi times gamma
#   d phi = sum_B ( gi * out_r - gr * out_i )   [since d out/d phi = j out]
# --------------------------------------------------------------------------
def _phase_apply_raw(ur, ui, phi, gamma):
    B, H, W = ur.shape
    bh, bw = _pick_blocks(H, W)
    Hp, Wp = _ceil_to(H, bh), _ceil_to(W, bw)
    out_r, out_i = _cm.phase_apply_pallas(
        _pad2d(ur, Hp, Wp), _pad2d(ui, Hp, Wp), _pad2d(phi, Hp, Wp),
        float(gamma), bh=bh, bw=bw, interpret=_interpret(),
    )
    return out_r[..., :H, :W], out_i[..., :H, :W]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _phase_apply(ur, ui, phi, gamma):
    return _phase_apply_raw(ur, ui, phi, gamma)


def _phase_apply_fwd(ur, ui, phi, gamma):
    out = _phase_apply_raw(ur, ui, phi, gamma)
    return out, (phi, out)


def _phase_apply_bwd(gamma, res, g):
    phi, (our, oui) = res
    gr, gi = g
    dur, dui = _phase_apply_raw(gr, gi, -phi, gamma)
    dphi = jnp.sum(gi * our - gr * oui, axis=0)
    return dur, dui, dphi


_phase_apply.defvjp(_phase_apply_fwd, _phase_apply_bwd)


@partial(jax.jit, static_argnames=("gamma",))
def phase_apply(ur, ui, phi, gamma: float = 1.0):
    """gamma * u * exp(j phi) on split planes (paper Eq. 9 hot spot)."""
    squeeze = ur.ndim == 2
    if squeeze:
        ur, ui = ur[None], ui[None]
    lead = ur.shape[:-2]
    H, W = ur.shape[-2:]
    out_r, out_i = _phase_apply(
        ur.reshape((-1, H, W)), ui.reshape((-1, H, W)), phi, float(gamma)
    )
    out_r = out_r.reshape(lead + (H, W))
    out_i = out_i.reshape(lead + (H, W))
    if squeeze:
        out_r, out_i = out_r[0], out_i[0]
    return out_r, out_i


# --------------------------------------------------------------------------
# phase_tf_apply: x * amp * exp(j theta) — the propagation engine's single
# fused elementwise op (cos/sin rotation + amplitude-weighted complex
# multiply in one VMEM pass).  Serves both scan-body call sites: the
# spectral TF multiply (theta=arg H, amp=|H|, constants) and the phase
# modulation (theta=phi, trainable; amp=gamma).  VJP:
#   d x     = g * amp * exp(-j theta)          (same kernel, rotated back)
#   d theta = sum_B (gi * out_r - gr * out_i)  (d out/d theta = j out)
#   d amp   = 0  (always static geometry: TF magnitudes, band-limit masks,
#                 gamma planes — mirrors the masks argument of readout)
# --------------------------------------------------------------------------
def _phase_tf_apply_raw(xr, xi, theta, amp, nb):
    PB, H, W = xr.shape
    bh, bw = _pick_blocks(H, W)
    Hp, Wp = _ceil_to(H, bh), _ceil_to(W, bw)
    out_r, out_i = _cm.phase_tf_apply_pallas(
        _pad2d(xr, Hp, Wp), _pad2d(xi, Hp, Wp),
        _pad2d(theta, Hp, Wp), _pad2d(amp, Hp, Wp),
        nb=nb, bh=bh, bw=bw, interpret=_interpret(),
    )
    return out_r[..., :H, :W], out_i[..., :H, :W]


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _phase_tf_apply(xr, xi, theta, amp, nb):
    return _phase_tf_apply_raw(xr, xi, theta, amp, nb)


def _phase_tf_apply_fwd(xr, xi, theta, amp, nb):
    out = _phase_tf_apply_raw(xr, xi, theta, amp, nb)
    return out, (theta, amp, out)


def _phase_tf_apply_bwd(nb, res, g):
    theta, amp, (our, oui) = res
    gr, gi = g
    dxr, dxi = _phase_tf_apply_raw(gr, gi, -theta, amp, nb)
    P, H, W = theta.shape
    cot = (gi * our - gr * oui).reshape((P, nb, H, W))
    dtheta = jnp.sum(cot, axis=1)
    return dxr, dxi, dtheta, jnp.zeros_like(amp)


_phase_tf_apply.defvjp(_phase_tf_apply_fwd, _phase_tf_apply_bwd)


@jax.jit
def phase_tf_apply(xr, xi, theta, amp):
    """x * amp * exp(j theta) on split planes via the fused Pallas kernel.

    x: (..., H, W); theta/amp: (H, W) shared by every field, or a plane
    stack (*P, H, W) with x: (..., *P, H, W) so plane p modulates the
    fields in slot p.  The plane axes may be any number of leading dims —
    (C, H, W) is the multi-channel DONN layout (one phase plane per
    optical channel), (K, H, W) / (K, C, H, W) are the batched
    multi-candidate layouts (one TF/phase plane per DSE candidate [and
    channel]); they all flatten to one plane-major axis internally.
    """
    pdims = theta.ndim - 2
    H, W = theta.shape[-2:]
    if pdims > 0:
        pshape = theta.shape[:-2]
        if xr.shape[xr.ndim - 2 - pdims: xr.ndim - 2] != pshape:
            raise ValueError(
                f"plane axes {pshape} of theta/amp must match the "
                f"corresponding axes of x {xr.shape}"
            )
        squeeze = xr.ndim == pdims + 2
        if squeeze:
            xr, xi = xr[None], xi[None]
        P = math.prod(pshape)
        lead = xr.shape[: xr.ndim - pdims - 2]
        # (..., *P, H, W) -> (P, B, H, W) -> (P*B, H, W): plane-major slabs
        xr3 = jnp.moveaxis(xr.reshape((-1, P, H, W)), 1, 0)
        xi3 = jnp.moveaxis(xi.reshape((-1, P, H, W)), 1, 0)
        B = xr3.shape[1]
        out_r, out_i = _phase_tf_apply(
            xr3.reshape((P * B, H, W)), xi3.reshape((P * B, H, W)),
            theta.reshape((P, H, W)), amp.reshape((P, H, W)), B,
        )
        out_r = jnp.moveaxis(out_r.reshape((P, B, H, W)), 0, 1)
        out_i = jnp.moveaxis(out_i.reshape((P, B, H, W)), 0, 1)
        out_r = out_r.reshape(lead + pshape + (H, W))
        out_i = out_i.reshape(lead + pshape + (H, W))
    else:
        squeeze = xr.ndim == 2
        if squeeze:
            xr, xi = xr[None], xi[None]
        lead = xr.shape[:-2]
        flat_r = xr.reshape((-1, H, W))
        out_r, out_i = _phase_tf_apply(
            flat_r, xi.reshape((-1, H, W)), theta[None], amp[None],
            flat_r.shape[0],
        )
        out_r = out_r.reshape(lead + (H, W))
        out_i = out_i.reshape(lead + (H, W))
    if squeeze:
        out_r, out_i = out_r[0], out_i[0]
    return out_r, out_i


# --------------------------------------------------------------------------
# fused_spectral_hop: one full propagation hop + modulation,
#   out = M . ifft2(Hc . fft2(x)),   Hc = amp_h e^{j th_h}, M = amp_m e^{j th_m}
# as fft2 -> conj-kernel(-th_h) -> fft2 -> conj-kernel(+th_m, 1/(H*W)) via
# ifft2(y) = conj(fft2(conj(y)))/(H*W).  Everything between/after the two
# forward FFTs is a single fused VMEM pass (see kernels/spectral_hop.py).
# VJP (the hop is C-linear in x; adjoint convention matches phase_tf_apply,
# d x = A^H g):
#   d x    = ifft2( conj(Hc) . fft2( conj(M) . g ) )   [reuses phase_tf kernel]
#   d th_m = sum_nb (gi * out_r - gr * out_i)          [d out/d th_m = j out]
#   d th_h = d amp_h = d amp_m = 0   (TF/band-limit/gamma: static geometry)
# --------------------------------------------------------------------------
def _conj_ps_raw(xr, xi, theta, amp, nb, sign, scale):
    PB, H, W = xr.shape
    bh, bw = _pick_blocks(H, W)
    Hp, Wp = _ceil_to(H, bh), _ceil_to(W, bw)
    out_r, out_i = _sh.conj_phase_scale_pallas(
        _pad2d(xr, Hp, Wp), _pad2d(xi, Hp, Wp),
        _pad2d(theta, Hp, Wp), _pad2d(amp, Hp, Wp),
        sign=sign, scale=scale, nb=nb, bh=bh, bw=bw, interpret=_interpret(),
    )
    return out_r[..., :H, :W], out_i[..., :H, :W]


def _fused_hop_raw(xr, xi, th_h, amp_h, th_m, amp_m, nb):
    H, W = xr.shape[-2:]
    s = jnp.fft.fft2(jax.lax.complex(xr, xi))
    tr, ti = _conj_ps_raw(s.real, s.imag, th_h, amp_h, nb, -1.0, 1.0)
    w = jnp.fft.fft2(jax.lax.complex(tr, ti))
    return _conj_ps_raw(w.real, w.imag, th_m, amp_m, nb, 1.0, 1.0 / (H * W))


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_hop(xr, xi, th_h, amp_h, th_m, amp_m, nb):
    return _fused_hop_raw(xr, xi, th_h, amp_h, th_m, amp_m, nb)


def _fused_hop_fwd(xr, xi, th_h, amp_h, th_m, amp_m, nb):
    out = _fused_hop_raw(xr, xi, th_h, amp_h, th_m, amp_m, nb)
    return out, (th_h, amp_h, th_m, amp_m, out)


def _fused_hop_bwd(nb, res, g):
    th_h, amp_h, th_m, amp_m, (our, oui) = res
    gr, gi = g
    # conj(M) . g, back through the spectral hop, conj(Hc) ., inverse FFT
    vr, vi = _phase_tf_apply_raw(gr, gi, -th_m, amp_m, nb)
    v = jnp.fft.fft2(jax.lax.complex(vr, vi))
    wr, wi = _phase_tf_apply_raw(v.real, v.imag, -th_h, amp_h, nb)
    dx = jnp.fft.ifft2(jax.lax.complex(wr, wi))
    P, H, W = th_m.shape
    cot = (gi * our - gr * oui).reshape((P, nb, H, W))
    dth_m = jnp.sum(cot, axis=1)
    return (dx.real, dx.imag, jnp.zeros_like(th_h), jnp.zeros_like(amp_h),
            dth_m, jnp.zeros_like(amp_m))


_fused_hop.defvjp(_fused_hop_fwd, _fused_hop_bwd)


@jax.jit
def fused_spectral_hop(xr, xi, theta_h, amp_h, theta_m, amp_m):
    """One hop + modulation, M . ifft2(Hc . fft2(x)), on split planes.

    x: (..., H, W); the four planes share one shape — (H, W) applied to
    every field, or a plane stack (*P, H, W) with x: (..., *P, H, W) so
    plane p transforms the fields in slot p (same stack-axis contract as
    ``phase_tf_apply``: (C, H, W) multi-channel, (K, ..., H, W) batched
    DSE candidates).  theta_h/amp_h are the transfer-function phase and
    magnitude (band-limit folded into amp); theta_m/amp_m the modulation
    phase and amplitude (gamma / codesign folded into amp_m).
    """
    # the TF and modulation planes may have different stack shapes (e.g.
    # multi-channel: TF (H, W) shared, phases (C, H, W)) — broadcast to one
    planes = (theta_h, amp_h, theta_m, amp_m)
    bshape = jnp.broadcast_shapes(*(p.shape for p in planes))
    planes = tuple(jnp.broadcast_to(p, bshape) for p in planes)
    pdims = len(bshape) - 2
    H, W = bshape[-2:]
    if pdims > 0:
        pshape = bshape[:-2]
        if xr.shape[xr.ndim - 2 - pdims: xr.ndim - 2] != pshape:
            raise ValueError(
                f"plane axes {pshape} of the TF/modulation planes must "
                f"match the corresponding axes of x {xr.shape}"
            )
        squeeze = xr.ndim == pdims + 2
        if squeeze:
            xr, xi = xr[None], xi[None]
        P = math.prod(pshape)
        lead = xr.shape[: xr.ndim - pdims - 2]
        xr3 = jnp.moveaxis(xr.reshape((-1, P, H, W)), 1, 0)
        xi3 = jnp.moveaxis(xi.reshape((-1, P, H, W)), 1, 0)
        B = xr3.shape[1]
        out_r, out_i = _fused_hop(
            xr3.reshape((P * B, H, W)), xi3.reshape((P * B, H, W)),
            *(p.reshape((P, H, W)) for p in planes), B,
        )
        out_r = jnp.moveaxis(out_r.reshape((P, B, H, W)), 0, 1)
        out_i = jnp.moveaxis(out_i.reshape((P, B, H, W)), 0, 1)
        out_r = out_r.reshape(lead + pshape + (H, W))
        out_i = out_i.reshape(lead + pshape + (H, W))
    else:
        squeeze = xr.ndim == 2
        if squeeze:
            xr, xi = xr[None], xi[None]
        lead = xr.shape[:-2]
        flat_r = xr.reshape((-1, H, W))
        out_r, out_i = _fused_hop(
            flat_r, xi.reshape((-1, H, W)),
            *(p[None] for p in planes), flat_r.shape[0],
        )
        out_r = out_r.reshape(lead + (H, W))
        out_i = out_i.reshape(lead + (H, W))
    if squeeze:
        out_r, out_i = out_r[0], out_i[0]
    return out_r, out_i


# --------------------------------------------------------------------------
# intensity_readout: out[b,c] = sum_hw masks[c] * (ur^2 + ui^2).
# VJP (masks are non-trainable detector geometry):
#   d ur = 2 ur * (g @ masks),  d ui = 2 ui * (g @ masks)
# --------------------------------------------------------------------------
def _readout_raw(ur, ui, masks):
    B, H, W = ur.shape
    bh, bw = _pick_blocks(H, W, max_h=32, max_w=256)
    Hp, Wp = _ceil_to(H, bh), _ceil_to(W, bw)
    return _ir.intensity_readout_pallas(
        _pad2d(ur, Hp, Wp), _pad2d(ui, Hp, Wp),
        _pad2d(masks.astype(ur.dtype), Hp, Wp),
        bh=bh, bw=bw, interpret=_interpret(),
    )


@jax.custom_vjp
def _readout(ur, ui, masks):
    return _readout_raw(ur, ui, masks)


def _readout_fwd(ur, ui, masks):
    return _readout_raw(ur, ui, masks), (ur, ui, masks)


def _readout_bwd(res, g):
    ur, ui, masks = res
    w = jnp.einsum("bc,chw->bhw", g, masks)
    return 2.0 * ur * w, 2.0 * ui * w, jnp.zeros_like(masks)


_readout.defvjp(_readout_fwd, _readout_bwd)


@jax.jit
def intensity_readout(ur, ui, masks):
    """(B?, H, W) field planes + (C, H, W) masks -> (B?, C) intensities."""
    squeeze = ur.ndim == 2
    if squeeze:
        ur, ui = ur[None], ui[None]
    lead = ur.shape[:-2]
    H, W = ur.shape[-2:]
    out = _readout(ur.reshape((-1, H, W)), ui.reshape((-1, H, W)), masks)
    out = out.reshape(lead + (masks.shape[0],))
    if squeeze:
        out = out[0]
    return out


@jax.jit
def channel_intensity_readout(ur, ui, masks):
    """(..., C, H, W) multi-channel fields + (K, H, W) masks -> (..., K).

    The multi-channel DONN detector accumulation: per-channel fused
    intensity readout (one Pallas pass per plane slab) followed by the
    incoherent channel sum.  Shared by ``MultiChannelDONN`` (plan and
    eager paths), ``emulate_batch`` and the deployment inference engine so
    every batched path accumulates through the same fused kernel.

    Coverage audit (ISSUE-5): with this helper in place every scan-plan /
    batched detector accumulation routes through ``intensity_readout``
    under ``use_pallas`` — ``Detector.__call__`` (classify, DSE ``cls``
    family), this channel sum (RGB plan + eager + ``multi`` family).  The
    remaining jnp einsum readouts are the documented non-Pallas fallbacks
    and the spatially-sharded step (``donn_steps.make_donn_spatial_loss``),
    which gates ``use_pallas`` off because its planes are row shards.
    """
    per_ch = intensity_readout(ur, ui, masks)  # (..., C, K)
    return jnp.sum(per_ch, axis=-2)


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


# re-export oracles for tests/benchmarks
complex_mul_ref = ref.complex_mul_ref
phase_apply_ref = ref.phase_apply_ref
phase_tf_apply_ref = ref.phase_tf_apply_ref
fused_spectral_hop_ref = ref.fused_spectral_hop_ref
intensity_readout_ref = ref.intensity_readout_ref
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
