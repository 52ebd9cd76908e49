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
# with H the natural-order plane.  G is real, so the complex field goes
# through as its real and imaginary parts.
#
# The hop runs folded, on half of G's multiply-adds.  Pair each sample
# with its mirror, x_j and x_{N-1-j} (j < m = ceil(N/2)): shifted by half a
# sample, the spectrum of the even fold a_j = x_j + x_{N-1-j} is a cosine
# transform C[k, j] = cos(pi k (2j + 1) / N) (k < m) and that of the odd
# fold b_j = x_j - x_{N-1-j} a sine transform S[k, j] = sin(pi k (2j + 1)
# / N) (1 <= k <= m): exp(-i pi k / N) X[k] = (C a)[k] - i (S b)[k]
# (a DCT-II / DST-II split; Martucci 1994).  Since H is even the shift
# cancels through the hop, and the cosine and sine coefficients at k meet
# H[k].  In 2-D a field becomes four (m, m) folded blocks, even or odd
# along rows and along columns (``fold_field``); the hop is four matmul
# stages each contracting m instead of N, with each block times its
# quadrant of H (rows and columns starting at 0 for a cosine block, 1 for
# a sine block), and the inverse stages return the folds.  A modulation,
# elementwise on the field, mixes the four blocks of a pixel: a Hadamard
# transform of the blocks gives the field's mirrored quadrants, where the
# product is elementwise, and another returns the folds
# (``modulate_folded``).  So a plan folds once per forward and carries the
# blocks through its scan.  The fold and unfold at the ends are matmuls
# with 0/+-1 matrices (``fold_matrices``): a TPU runs a reversal as a pass
# of its own, and did not compile it exactly at every size.
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


def folded_dft_matrices(n: int) -> tuple:
    """``(F, Fi)``, each a (2, m, m) float32 stack (m = (n + 1) // 2) of the
    cosine and sine blocks of the half-sample folded length-n real DFT and
    of its inverse (built in float64, cached per n).

    ``F[0]`` (rows k = 0 .. m-1) takes the even fold ``x_j + x_{n-1-j}``,
    ``F[1]`` (rows k = 1 .. m) the odd fold ``x_j - x_{n-1-j}``; for odd n
    the middle sample pairs with itself, so ``F[0]`` halves its column and
    ``F[1]``'s last row (k = m) is zero."""
    key = ("folded", n)
    hit = lru_get(_REAL_DFT_CACHE, key)
    if hit is not None:
        return hit
    m = (n + 1) // 2
    j = np.arange(m)
    k = np.arange(m + 1)
    # pi k (2j + 1) / n, its integer part reduced mod 2n exactly
    ang = math.pi * (np.outer(k, 2 * j + 1) % (2 * n)) / n
    cos, sin = np.cos(ang[:m]), np.sin(ang[1:])
    half = np.ones(m)
    wc = np.full(m, 2.0)  # each coefficient k stands for k and n - k ...
    wc[0] = 1.0
    ws = np.full(m, 2.0)
    if n % 2:
        half[-1] = 0.5  # the middle sample pairs with itself
        sin[-1] = 0.0  # k = m is no coefficient
        sin[:, -1] = 0.0  # sin(pi k): the middle of an odd fold is 0
    else:
        ws[-1] = 1.0  # ... but k = n/2 for itself
    fwd = np.stack([cos * half, sin])
    # 2 (cos^T, sin^T) diag(w) / n returns the folds, not x itself
    inv = np.stack([cos.T * (2.0 * wc / n), sin.T * (2.0 * ws / n)])
    pair = (fwd.astype(np.float32), inv.astype(np.float32))
    lru_put(_REAL_DFT_CACHE, key, pair, _REAL_DFT_CACHE_MAX)
    return pair


# The folded hop's four blocks, by (row block, column block): 0 is the
# cosine (even) block of an axis, 1 the sine (odd) block.
BLOCKS = ((0, 0), (0, 1), (1, 0), (1, 1))


def fold_matrices(n: int) -> tuple:
    """``(P, Q)``: the even and odd half-sample folds of a length-n axis,
    ``P`` (2, m, n) with ``(P x)_j = x_j +- x_{n-1-j}`` (for odd n the
    middle sample pairs with itself), and their inverse ``Q`` (2, n, m),
    ``x = Q[0] a + Q[1] b``.  Entries 0, +-1/2, +-1 and 2: exact in every
    matmul precision pass, so at HIGHEST the folds are plain sums."""
    m = (n + 1) // 2
    j = np.arange(m)
    p = np.zeros((2, m, n), np.float32)
    q = np.zeros((2, n, m), np.float32)
    for s, sign in enumerate((1.0, -1.0)):
        np.add.at(p[s], (j, j), 1.0)
        np.add.at(p[s], (j, n - 1 - j), sign)
        q[s, j, j] = 0.5
        t = j[: n - m]
        q[s, n - 1 - t, t] = 0.5 * sign
    return p, q


def _fold_planes(v: jax.Array) -> jax.Array:
    """Real plane(s) (..., n, n) -> their four folded blocks (4, ..., m,
    m) in ``BLOCKS`` order, as plain matmuls with ``fold_matrices``: the
    mirrored reads run in the matmuls, which the TPU compiles exactly at
    every size, and not as reversals."""
    p = fold_matrices(v.shape[-1])[0]
    rows = [jnp.einsum("jc,...ci->...ji", p[r], v, precision=HOP_PRECISION)
            for r in (0, 1)]
    return jnp.stack([
        jnp.einsum("...ji,li->...jl", rows[r], p[t], precision=HOP_PRECISION)
        for r, t in BLOCKS])


def fold_field(u: jax.Array) -> jax.Array:
    """Complex field(s) (..., n, n) -> the folded hop's four real blocks in
    ``BLOCKS`` order, each its (real, imaginary) parts: (4, 2, ..., m, m).
    """
    return _fold_planes(jnp.stack([u.real, u.imag]))


def unfold_field(x: jax.Array, n: int) -> jax.Array:
    """Inverse of ``fold_field``."""
    q = fold_matrices(n)[1]
    rows = [sum(jnp.einsum("...jl,cl->...jc", x[2 * r + t], q[t],
                           precision=HOP_PRECISION) for t in (0, 1))
            for r in (0, 1)]
    v = sum(jnp.einsum("ij,...jc->...ic", q[r], rows[r],
                       precision=HOP_PRECISION) for r in (0, 1))
    return jax.lax.complex(v[0], v[1])


def _hadamard(x: jax.Array) -> jax.Array:
    """The 4-point Walsh-Hadamard transform of the blocks on the leading
    axis (k = 2 r + t): block k of the result sums block k' = 2 r' + t'
    with the sign (-1)^(r r' + t t').  It takes a field's four folds to
    four times its mirrored quadrants (rows, and columns, of the second
    half reversed) and back.  Two butterflies of adds, one pass over the
    blocks; as a 4 x 4 dot the v5e laid the block axis out second-minor
    and took six times as long."""
    s, t = x[0] + x[1], x[0] - x[1]
    u, v = x[2] + x[3], x[2] - x[3]
    return jnp.stack([s + u, t + v, s - u, t - v])


def fold_modulation(mod: jax.Array) -> jax.Array:
    """Complex modulation plane(s) (L, ..., n, n) -> (L, 4, 2, ..., m, m):
    for each leading L the plane's mirrored quadrants, each its (real,
    imaginary) parts, over 4 (what ``modulate_folded`` multiplies)."""
    v = _fold_planes(jnp.stack([mod.real, mod.imag], axis=1))
    return jnp.moveaxis(_hadamard(v), 0, 1) / 16.0


def modulate_folded(x: jax.Array, mod: jax.Array) -> jax.Array:
    """The elementwise product of a field with a modulation plane, on the
    field's folded blocks ``x`` (``fold_field``) and one layer's
    ``fold_modulation`` stack ``mod`` (4, 2, ..., m, m).  The product is
    elementwise on the mirrored quadrants, so the blocks go there and back
    by ``_hadamard`` (on a TPU v5e this ran the cell faster than mixing the
    four folds directly, which reads each of them for every block it
    writes)."""
    mod = mod.reshape(mod.shape[:2] + (1,) * (x.ndim - mod.ndim)
                      + mod.shape[2:])
    q = _hadamard(x)
    re = mod[:, 0] * q[:, 0] - mod[:, 1] * q[:, 1]
    im = mod[:, 0] * q[:, 1] + mod[:, 1] * q[:, 0]
    return _hadamard(jnp.stack([re, im], axis=1))


def folded_dft_2d(x: jax.Array, mats, inverse: bool = False) -> jax.Array:
    """One folded transform of the four blocks ``x`` (4, 2, ..., m, m):
    block (r, t) becomes ``mats[r] . x . mats[t]^T``, each side one dot
    batched over the blocks (``folded_dft_matrices``' forward or inverse
    stack).  The column side runs first forward and last ``inverse``, so
    the dots next to a layer's modulation read and write the blocks in
    their plain layout."""
    mats = np.asarray(mats)
    rows = jnp.asarray(mats[[r for r, _ in BLOCKS]])
    cols = jnp.asarray(mats[[t for _, t in BLOCKS]])
    sides = [
        lambda v: jnp.einsum("k...ij,klj->k...il", v, cols,
                             precision=HOP_PRECISION),
        lambda v: jnp.einsum("kli,k...ij->k...lj", rows, v,
                             precision=HOP_PRECISION),
    ]
    for side in sides[::-1] if inverse else sides:
        x = side(x)
    return x


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
