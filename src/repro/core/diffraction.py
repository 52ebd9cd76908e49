"""FFT-based scalar-diffraction physics kernels (LightRidge §3.1).

Implements the three approximations of the paper as *transfer functions* over
a uniform sampling grid, plus the propagation primitive

    U_out = iFFT2( FFT2(U_in) * H(fx, fy; z, lambda) )

- Rayleigh-Sommerfeld (exact angular-spectrum solution, Eq. 1): valid in both
  near and far field; highest fidelity.
- Fresnel (parabolic wavefronts, Eq. 3): near-field approximation.
- Fraunhofer (planar wavefronts, Eq. 4): far field; implemented as a single
  scaled FFT (its output grid is rescaled by lambda*z/(N*dx^2)).

All transfer functions are precomputed with numpy at model-build time (they
depend only on static geometry) and embedded as constants, so jit'd forward
passes contain only FFT2 / complex-multiply / iFFT2 — the three operators the
paper identifies as the DONN hot spots (Fig. 9).  Because every transfer
function here is even in each frequency axis, the same hop also runs as
real-DFT matmuls (``real_dft_matrices``), which the propagation plan uses
on a TPU.

Optional band-limiting (Matsushima & Shimobaba 2009) suppresses aliasing of
the angular spectrum for long propagation distances; optional 2x zero-padding
turns the circular convolution into a linear one.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import lru_get, lru_put

RS = "rs"
FRESNEL = "fresnel"
FRAUNHOFER = "fraunhofer"
METHODS = (RS, FRESNEL, FRAUNHOFER)


@dataclasses.dataclass(frozen=True)
class Grid:
    """Uniform square sampling grid for an optical field."""

    n: int  # samples per side (system size / resolution)
    pixel_size: float  # diffraction unit size [m]

    @property
    def extent(self) -> float:
        return self.n * self.pixel_size

    def freqs(self, pad: bool = False) -> np.ndarray:
        n = 2 * self.n if pad else self.n
        return np.fft.fftfreq(n, d=self.pixel_size)

    def coords(self) -> np.ndarray:
        # centered spatial coordinates of sample centers
        return (np.arange(self.n) - (self.n - 1) / 2.0) * self.pixel_size


def fresnel_tf_centered(
    grid: Grid, z: float, wavelength: float, pad: bool = False
) -> np.ndarray:
    """Fresnel transfer function over *centered* (fftshift-ordered) freqs.

    The textbook spelling: H lives on the centered frequency grid, so a hop
    using it must bracket the spectral multiply with an fftshift/ifftshift
    pair — ``ifft2(ifftshift(H_c * fftshift(fft2(u))))``.  The propagation
    engine never pays those two shifts per layer: ``transfer_function``
    pre-folds the pair into the cached plane at build time
    (``ifftshift(H_c)`` is stored, which is exactly H over natural fftfreq
    ordering), so the runtime hop is a bare ``ifft2(fft2(u) * H)``.
    Parity between the two spellings is pinned by
    tests/test_diffraction.py::test_fresnel_prefolded_shift_pair.
    """
    f = np.fft.fftshift(grid.freqs(pad=pad))
    fx, fy = np.meshgrid(f, f, indexing="ij")
    k = 2.0 * math.pi / wavelength
    return (
        np.exp(1j * k * z)
        * np.exp(-1j * math.pi * wavelength * z * (fx**2 + fy**2))
    ).astype(np.complex64)


def transfer_function(
    grid: Grid,
    z: float,
    wavelength: float,
    method: str = RS,
    band_limit: bool = True,
    pad: bool = False,
) -> np.ndarray:
    """Free-space transfer function H(fx, fy) on the (possibly padded) grid.

    Returned as a numpy complex64 array (static geometry => build-time
    const).  Planes are stored *pre-shifted* — natural ``fftfreq`` ordering
    — so the runtime hop is shift-free; see ``fresnel_tf_centered`` for the
    centered spelling the fold starts from.
    """
    if method not in (RS, FRESNEL):
        raise ValueError(f"transfer_function supports rs|fresnel, got {method}")
    f = grid.freqs(pad=pad)
    fx, fy = np.meshgrid(f, f, indexing="ij")
    k = 2.0 * math.pi / wavelength
    if method == RS:
        # exact angular spectrum: H = exp(j k z sqrt(1 - (l fx)^2 - (l fy)^2))
        arg = 1.0 - (wavelength * fx) ** 2 - (wavelength * fy) ** 2
        prop = arg >= 0.0
        kz = k * np.sqrt(np.maximum(arg, 0.0))
        kappa = k * np.sqrt(np.maximum(-arg, 0.0))
        h = np.where(prop, np.exp(1j * kz * z), np.exp(-kappa * abs(z)))
    else:
        # centered Fresnel plane with the fftshift/ifftshift pair folded in
        # at build time: each cached fresnel hop drops two shifts per layer
        # (the shift is a permutation, so the fold is bit-exact)
        h = np.fft.ifftshift(fresnel_tf_centered(grid, z, wavelength, pad))
    if band_limit:
        # Matsushima & Shimobaba band-limited angular spectrum
        n = 2 * grid.n if pad else grid.n
        s = n * grid.pixel_size
        f_limit = 1.0 / (wavelength * math.sqrt((2.0 * z / s) ** 2 + 1.0))
        h = h * ((np.abs(fx) <= f_limit) & (np.abs(fy) <= f_limit))
    return h.astype(np.complex64)


def propagate_tf(u: jax.Array, h: jax.Array) -> jax.Array:
    """Angular-spectrum propagation of field(s) u (..., N, N) by TF h."""
    spec = jnp.fft.fft2(u)
    out = jnp.fft.ifft2(spec * h)
    return out


# --------------------------------------------------------------------------
# Packed real-DFT hop: the angular-spectrum hop as matmuls
# --------------------------------------------------------------------------
# Every transfer function above is even in each frequency axis (a function
# of fx^2 and fy^2, band limit included), so the hop ifft2(H . fft2(u)) is a
# symmetric convolution (Martucci 1994) and runs on real transforms.  Row k
# of the real DFT ``G`` is cos(2 pi k n / N) for k <= N//2 and
# -sin(2 pi k n / N) above: for a real signal it holds the spectrum's real
# part C[k] at k <= N//2 and its sine part S[N-k] at k > N//2, each at the
# index whose H value it meets (H[k] = H[N-k]).  The inverse is
# ``Gi = G^T diag(w) / N`` (w = 1 at k = 0 and k = N/2, 2 elsewhere: each
# packed row stands for the pair k, N-k), and the hop of a field u is
#
#     Gi . (H o (G . u . G^T)) . Gi^T
#
# with H the natural-order plane, unchanged.  G is real, so the complex
# field goes through as its real and imaginary parts: 2 real N^3 matmuls
# per stage, half of a dense complex DFT.
_REAL_DFT_CACHE: dict = {}
_REAL_DFT_CACHE_MAX = 16

# The hop's matmuls run at full float32 (a TPU's default is one bf16 pass,
# a different result), like the readouts' READOUT_PRECISION.
HOP_PRECISION = jax.lax.Precision.HIGHEST


def real_dft_matrices(n: int) -> tuple:
    """``(G, Gi)``, the length-n real DFT and its inverse (float32 numpy,
    built in float64, cached per n)."""
    hit = lru_get(_REAL_DFT_CACHE, n)
    if hit is not None:
        return hit
    k = np.arange(n)
    # (k * j) mod n keeps the angles in [0, 2 pi): exact integer reduction
    ang = 2.0 * math.pi * (np.outer(k, k) % n) / n
    g = np.where(k[:, None] <= n // 2, np.cos(ang), -np.sin(ang))
    w = np.full(n, 2.0)
    w[0] = 1.0
    if n % 2 == 0:
        w[n // 2] = 1.0
    pair = (g.astype(np.float32), (g.T * (w / n)).astype(np.float32))
    lru_put(_REAL_DFT_CACHE, n, pair, _REAL_DFT_CACHE_MAX)
    return pair


def real_dft_2d(s: jax.Array, m) -> jax.Array:
    """``m . s . m^T`` over the last two axes of a real stack ``s``."""
    m = jnp.asarray(m)
    t = jnp.einsum("...ij,kj->...ik", s, m, precision=HOP_PRECISION)
    return jnp.einsum("ki,...ij->...kj", m, t, precision=HOP_PRECISION)


def is_even_plane(h: np.ndarray) -> bool:
    """Whether plane(s) (..., N, N) satisfy H[k] = H[(N - k) % N] exactly
    along each of the last two axes (what the packed hop needs)."""
    for axis in (-2, -1):
        if not np.array_equal(h, np.roll(np.flip(h, axis), 1, axis)):
            return False
    return True


def propagate(
    u: jax.Array,
    grid: Grid,
    z: float,
    wavelength: float,
    method: str = RS,
    band_limit: bool = True,
    pad: bool = False,
) -> jax.Array:
    """One-shot propagation (builds H; prefer precomputing H in layers)."""
    if method == FRAUNHOFER:
        return fraunhofer(u, grid, z, wavelength)
    if pad:
        return _propagate_padded(u, grid, z, wavelength, method, band_limit)
    h = jnp.asarray(transfer_function(grid, z, wavelength, method, band_limit))
    return propagate_tf(u, h)


def pad_field(u: jax.Array, n: int) -> jax.Array:
    """Center-embed an (..., n, n) field into the 2x zero-padded grid."""
    widths = [(0, 0)] * (u.ndim - 2) + [
        (n // 2, n - n // 2), (n // 2, n - n // 2)
    ]
    return jnp.pad(u, widths)


def crop_field(u: jax.Array, n: int) -> jax.Array:
    """Inverse of ``pad_field``: recover the central (..., n, n) window."""
    lo = n // 2
    return u[..., lo : lo + n, lo : lo + n]


def _propagate_padded(u, grid, z, wavelength, method, band_limit):
    n = grid.n
    h = jnp.asarray(
        transfer_function(grid, z, wavelength, method, band_limit, pad=True)
    )
    return crop_field(propagate_tf(pad_field(u, n), h), n)


def fraunhofer_quad(grid: Grid, z: float, wavelength: float) -> np.ndarray:
    """Far-field output-plane factor of Eq. 4 (quadratic phase + scaling).

    Shared by the eager path (``fraunhofer``) and the propagation-plan
    cache so the two can never diverge.
    """
    n = grid.n
    k = 2.0 * math.pi / wavelength
    x = np.fft.fftshift(np.fft.fftfreq(n, d=grid.pixel_size)) * wavelength * z
    xx, yy = np.meshgrid(x, x, indexing="ij")
    quad = np.exp(1j * k * z) * np.exp(1j * k / (2.0 * z) * (xx**2 + yy**2))
    scale = grid.pixel_size**2 / (1j * wavelength * z)
    return (quad * scale).astype(np.complex64)


def fraunhofer(
    u: jax.Array, grid: Grid, z: float, wavelength: float
) -> jax.Array:
    """Far-field (Fraunhofer) propagation, Eq. 4.

    Output samples live on the rescaled far-field grid with spacing
    lambda*z/(N*dx); the quadratic output phase and 1/(j lambda z) scaling are
    applied so intensities are physical.
    """
    spec = jnp.fft.fftshift(jnp.fft.fft2(u), axes=(-2, -1))
    return spec * jnp.asarray(fraunhofer_quad(grid, z, wavelength))


# bounded LRU, same shared discipline as the propagation TF/plan caches
_RESAMPLE_CACHE: dict = {}
_RESAMPLE_CACHE_MAX = 256


def resample_matrix(grid_in: Grid, grid_out: Grid) -> np.ndarray:
    """Bilinear field-resampling operator between two plane grids.

    Returns the (n_out, n_in) separable 1-D interpolation matrix ``A`` such
    that ``u_out = A @ u_in @ A.T`` resamples a field over *physical*
    coordinates (both grids are centered; samples falling outside the input
    aperture read zero).  For equal pixel sizes *and* matching sample
    alignment (n_in and n_out of the same parity, so the centered grids
    coincide) the matrix degenerates to an exact centered crop / zero-pad
    (0/1 entries) and aperture-only stitches are lossless; an odd<->even
    stitch at equal pitch interpolates half-sample-shifted values instead.
    Static geometry => numpy constant (cached process-wide LRU, embedded
    into jit programs like the TF planes).
    """
    key = (grid_in.n, float(grid_in.pixel_size),
           grid_out.n, float(grid_out.pixel_size))
    hit = lru_get(_RESAMPLE_CACHE, key)
    if hit is not None:
        return hit
    # output sample positions in input index space
    t = (grid_out.coords() / grid_in.pixel_size) + (grid_in.n - 1) / 2.0
    i0 = np.floor(t).astype(np.int64)
    w = (t - i0).astype(np.float64)
    A = np.zeros((grid_out.n, grid_in.n), np.float64)
    rows = np.arange(grid_out.n)
    for idx, wt in ((i0, 1.0 - w), (i0 + 1, w)):
        ok = (idx >= 0) & (idx < grid_in.n)
        A[rows[ok], idx[ok]] += wt[ok]
    A = A.astype(np.float32)
    lru_put(_RESAMPLE_CACHE, key, A, _RESAMPLE_CACHE_MAX)
    return A


def _is_exact_crop_pad(grid_in: Grid, grid_out: Grid) -> bool:
    """True when the stitch degenerates to a centered crop / zero-pad:
    equal pitch and same parity, so the centered sample grids coincide."""
    return (float(grid_in.pixel_size) == float(grid_out.pixel_size)
            and (grid_in.n - grid_out.n) % 2 == 0)


def resample_field(u: jax.Array, grid_in: Grid, grid_out: Grid) -> jax.Array:
    """Resample field(s) (..., n_in, n_in) onto ``grid_out`` (bilinear).

    Two fast paths keep boundary stitches off the matmul unit where
    possible: exact crop/pad stitches (equal pitch, matching parity) are
    pure slicing, and genuinely bilinear stitches of complex fields run as
    split real/imag float32 contractions — half the real FLOPs of the
    complex-promoted einsum (a float32 operator against a complex64 field
    upcasts the operator and multiplies zeros otherwise).
    """
    if grid_in == grid_out:
        return u
    from repro.core.propagation import stage

    with stage("stitch"):
        return _resample(u, grid_in, grid_out)


def _resample(u: jax.Array, grid_in: Grid, grid_out: Grid) -> jax.Array:
    if _is_exact_crop_pad(grid_in, grid_out):
        # centered grids coincide: output[o] = input[o + (n_in - n_out)/2]
        # (zero outside the input aperture) — pure slicing / padding,
        # bit-identical to the degenerate 0/1 resample matrix
        n_in, n_out = grid_in.n, grid_out.n
        if n_in >= n_out:
            off = (n_in - n_out) // 2
            return u[..., off:off + n_out, off:off + n_out]
        lo = (n_out - n_in) // 2
        hi = n_out - n_in - lo
        return jnp.pad(u, [(0, 0)] * (u.ndim - 2) + [(lo, hi), (lo, hi)])
    A = jnp.asarray(resample_matrix(grid_in, grid_out))
    if jnp.iscomplexobj(u):
        re = jnp.einsum("oi,...ij,pj->...op", A, u.real, A)
        im = jnp.einsum("oi,...ij,pj->...op", A, u.imag, A)
        return jax.lax.complex(re, im)
    return jnp.einsum("oi,...ij,pj->...op", A, u, A)


def fresnel_number(grid: Grid, z: float, wavelength: float) -> float:
    """Fresnel number a^2/(lambda z) with a = half-aperture (regime check)."""
    a = grid.extent / 2.0
    return a * a / (wavelength * z)


def phase_to_field(phi: jax.Array) -> jax.Array:
    """exp(j phi) as complex64 from a real phase array."""
    return jnp.exp(1j * phi.astype(jnp.complex64))


def intensity(u: jax.Array) -> jax.Array:
    """|U|^2 — detector-plane light intensity."""
    return (u.real**2 + u.imag**2).astype(jnp.float32)


def readout(u: jax.Array, masks, channel_axis: bool = False) -> jax.Array:
    """Detector readout: |U|^2 pooled over (K, n, n) region masks.

    ``u`` is (..., n, n), or (..., C, n, n) with ``channel_axis``, whose
    channels add incoherently on the shared detector; returns (..., K).
    """
    from repro.core.propagation import stage

    with stage("readout"):
        fields = "...dhw" if channel_axis else "...hw"
        return jnp.einsum(f"{fields},chw->...c", intensity(u), masks,
                          precision=READOUT_PRECISION)


# Precision of the jnp detector readouts (|U|^2 contracted with the 0/1
# per-class region masks).  At default precision a TPU runs that f32
# contraction as a single bf16 pass, which on a v5e moved the 200^2
# classifier's logits by 2e-4 and its loss by 9e-4 (relative) against the
# float32 reference, while the Pallas readout, which sums in f32, agreed to
# 2e-7.  The contraction is a few MFLOP beside the FFTs, so it stays f32.
READOUT_PRECISION = jax.lax.Precision.HIGHEST
