"""Physics-level tests of the scalar-diffraction kernels (paper §3.1)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DONNConfig, build_model
from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.core.layers import DiffractiveLayer
from repro.core.models import channel_readout

WL = 532e-9
PX = 36e-6


def _rand_field(n, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(
        r.normal(size=(n, n)) + 1j * r.normal(size=(n, n)), jnp.complex64
    )


class TestEnergyConservation:
    def test_rs_unitary_without_band_limit(self):
        g = df.Grid(64, PX)
        u = _rand_field(64)
        v = df.propagate(u, g, 0.01, WL, df.RS, band_limit=False)
        np.testing.assert_allclose(
            float(jnp.sum(df.intensity(u))), float(jnp.sum(df.intensity(v))),
            rtol=1e-4,
        )

    def test_fresnel_unitary(self):
        g = df.Grid(64, PX)
        u = _rand_field(64, 1)
        v = df.propagate(u, g, 0.05, WL, df.FRESNEL, band_limit=False)
        np.testing.assert_allclose(
            float(jnp.sum(df.intensity(u))), float(jnp.sum(df.intensity(v))),
            rtol=1e-4,
        )

    def test_band_limit_only_removes_energy(self):
        g = df.Grid(64, PX)
        u = _rand_field(64, 2)
        v = df.propagate(u, g, 0.3, WL, df.RS, band_limit=True)
        assert float(jnp.sum(df.intensity(v))) <= float(
            jnp.sum(df.intensity(u))
        ) * (1 + 1e-5)


class TestComposition:
    @pytest.mark.parametrize("method", [df.RS, df.FRESNEL])
    def test_two_hops_equal_one(self, method):
        g = df.Grid(48, PX)
        u = _rand_field(48, 3)
        z1, z2 = 0.013, 0.021
        v2 = df.propagate(
            df.propagate(u, g, z1, WL, method, band_limit=False),
            g, z2, WL, method, band_limit=False,
        )
        v1 = df.propagate(u, g, z1 + z2, WL, method, band_limit=False)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                                   rtol=2e-3, atol=2e-3)

    def test_forward_backward_identity(self):
        g = df.Grid(48, PX)
        u = _rand_field(48, 4)
        v = df.propagate(
            df.propagate(u, g, 0.02, WL, df.RS, band_limit=False),
            g, -0.02, WL, df.RS, band_limit=False,
        )
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=2e-3, atol=2e-3)


class TestGaussianBeamAnalytic:
    def test_waist_expansion_matches_theory(self):
        """w(z) = w0 sqrt(1 + (z/zR)^2) for a Gaussian beam."""
        n, px = 256, 8e-6
        g = df.Grid(n, px)
        w0 = 120e-6
        c = g.coords()
        xx, yy = np.meshgrid(c, c, indexing="ij")
        u0 = jnp.asarray(np.exp(-(xx**2 + yy**2) / w0**2), jnp.complex64)
        zr = math.pi * w0**2 / WL
        z = 1.5 * zr
        uz = df.propagate(u0, g, z, WL, df.RS, band_limit=False)
        inten = np.asarray(df.intensity(uz))
        # I ~ exp(-2 r^2/w^2) => <x^2> = w^2/4 => w = 2 sqrt(<x^2>)
        tot = inten.sum()
        x2 = (inten * xx**2).sum() / tot
        w_meas = 2.0 * math.sqrt(x2)
        w_theory = w0 * math.sqrt(1 + (z / zr) ** 2)
        assert abs(w_meas - w_theory) / w_theory < 0.05

    def test_fresnel_matches_rs_in_paraxial_regime(self):
        n, px = 128, 16e-6
        g = df.Grid(n, px)
        w0 = 200e-6
        c = g.coords()
        xx, yy = np.meshgrid(c, c, indexing="ij")
        u0 = jnp.asarray(np.exp(-(xx**2 + yy**2) / w0**2), jnp.complex64)
        z = 0.05
        i_rs = np.asarray(df.intensity(df.propagate(u0, g, z, WL, df.RS)))
        i_fr = np.asarray(df.intensity(df.propagate(u0, g, z, WL, df.FRESNEL)))
        corr = np.corrcoef(i_rs.ravel(), i_fr.ravel())[0, 1]
        assert corr > 0.999


class TestLinearity:
    @settings(max_examples=10, deadline=None)
    @given(a=st.floats(-2, 2), b=st.floats(-2, 2))
    def test_superposition(self, a, b):
        g = df.Grid(32, PX)
        u1, u2 = _rand_field(32, 5), _rand_field(32, 6)
        p = lambda u: df.propagate(u, g, 0.02, WL, df.RS)
        lhs = np.asarray(p(a * u1 + b * u2))
        rhs = np.asarray(a * p(u1) + b * p(u2))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-3)


class TestFraunhofer:
    def test_far_field_of_slit_is_sinc(self):
        n, px = 256, 10e-6
        g = df.Grid(n, px)
        slit_w = 20  # pixels
        u = np.zeros((n, n), np.complex64)
        u[:, n // 2 - slit_w // 2 : n // 2 + slit_w // 2] = 1.0
        z = 2.0  # far field
        far = df.fraunhofer(jnp.asarray(u), g, z, WL)
        inten = np.asarray(df.intensity(far))
        row = inten[n // 2]
        # central maximum at center; first zeros at x = lambda z / slit width
        assert row.argmax() == n // 2
        fx = np.fft.fftshift(np.fft.fftfreq(n, d=px))
        x = fx * WL * z
        zero_x = WL * z / (slit_w * px)
        iz = int(np.argmin(np.abs(x - zero_x)))
        assert row[iz] < 0.01 * row[n // 2]


class TestGradients:
    def test_phase_gradients_flow(self):
        g = df.Grid(32, PX)
        u = _rand_field(32, 7)
        h = jnp.asarray(df.transfer_function(g, 0.02, WL, df.RS))

        def f(phi):
            v = df.propagate_tf(u * jnp.exp(1j * phi.astype(jnp.complex64)), h)
            return jnp.sum(df.intensity(v)[:8, :8])

        grad = jax.grad(f)(jnp.zeros((32, 32), jnp.float32))
        assert bool(jnp.all(jnp.isfinite(grad))) and float(
            jnp.max(jnp.abs(grad))
        ) > 0


class TestFresnelShiftPrefold:
    """The cached Fresnel TF pre-folds the fftshift/ifftshift pair.

    The textbook centered-plane hop spends two shifts per layer:
    ``ifft2(ifftshift(H_c * fftshift(fft2(u))))``.  The TF cache stores
    ``ifftshift(H_c)`` instead, so the runtime hop is shift-free — these
    tests pin both the value fold and the hop parity.
    """

    def test_cached_plane_is_preshifted_centered_plane(self):
        g = df.Grid(64, PX)
        hc = df.fresnel_tf_centered(g, 0.05, WL)
        h = df.transfer_function(g, 0.05, WL, df.FRESNEL, band_limit=False)
        # the shift is a pure permutation: the fold is bit-exact
        np.testing.assert_array_equal(np.fft.ifftshift(hc), h)

    def test_fresnel_prefolded_shift_pair(self):
        g = df.Grid(64, PX)
        u = _rand_field(64, 11)
        z = 0.05
        hc = df.fresnel_tf_centered(g, z, WL)
        # the unshifted (explicit shift-pair, centered-plane) reference hop
        spec = np.fft.fftshift(np.fft.fft2(np.asarray(u)))
        ref = np.fft.ifft2(np.fft.ifftshift(spec * hc))
        got = np.asarray(
            df.propagate(u, g, z, WL, df.FRESNEL, band_limit=False)
        )
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_padded_plane_preshifted_too(self):
        g = df.Grid(32, PX)
        hc = df.fresnel_tf_centered(g, 0.02, WL, pad=True)
        h = df.transfer_function(g, 0.02, WL, df.FRESNEL, band_limit=False,
                                 pad=True)
        np.testing.assert_array_equal(np.fft.ifftshift(hc), h)


# --------------------------------------------------------------------------
# Elementwise stages of the forward against numpy oracles: the detector
# readout, the phase modulation (plan and eager layer), and the modulation
# of a field's folded blocks (the folded hop's ``modulate`` stage).
# --------------------------------------------------------------------------
SHAPES = [(1, 8, 128), (2, 64, 128), (3, 200, 200), (1, 37, 111), (2, 17, 513)]


def _planes(shape, seed):
    r = np.random.default_rng(seed)
    return r.normal(size=shape), r.normal(size=shape)


def _cfield(shape, seed):
    ur, ui = _planes(shape, seed)
    return ur + 1j * ui


def _assert_close(got, want, rtol):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max())


def _readout_ref(u, masks):
    """|u|^2 pooled per detector region, float64: (B, H, W) x (C, H, W)."""
    return np.einsum("bhw,chw->bc", np.abs(u) ** 2, masks)


class TestReadout:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("classes", [3, 10])
    def test_matches_oracle(self, shape, classes):
        B, H, W = shape
        u = _cfield(shape, 1)
        masks = (np.random.default_rng(3).random((classes, H, W)) < 0.1
                 ).astype(np.float32)
        got = df.readout(jnp.asarray(u, jnp.complex64), jnp.asarray(masks))
        assert got.shape == (B, classes)
        _assert_close(got, _readout_ref(u, masks), 1e-5)

    def test_partition_sums_to_total(self):
        """Masks that partition the plane => per-class sums add to total."""
        u = jnp.asarray(_cfield((2, 32, 128), 4), jnp.complex64)
        labels = np.random.default_rng(6).integers(0, 4, (32, 128))
        masks = jnp.asarray(np.stack([labels == c for c in range(4)]),
                            jnp.float32)
        out = df.readout(u, masks)
        np.testing.assert_allclose(jnp.sum(out, -1),
                                   jnp.sum(df.intensity(u), axis=(1, 2)),
                                   rtol=1e-5)

    def test_gradients(self):
        ur, ui = (jnp.asarray(p, jnp.float32) for p in _planes((2, 16, 128), 7))
        masks = jnp.asarray(
            np.random.default_rng(8).random((3, 16, 128)) < 0.5, jnp.float32)
        g = jax.grad(lambda a, b: jnp.sum(
            df.readout(jax.lax.complex(a, b), masks)), argnums=(0, 1))(ur, ui)
        # d/du sum_c <masks_c, |u|^2> = 2 u sum_c masks_c
        w = jnp.sum(masks, axis=0)
        np.testing.assert_allclose(g[0], 2 * ur * w, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g[1], 2 * ui * w, rtol=1e-5, atol=1e-5)


class TestChannelReadout:
    """The multi-channel detector accumulation (``models.channel_readout``):
    per-channel intensities summed incoherently over the shared detector."""

    def test_matches_oracle(self):
        r = np.random.default_rng(0)
        u = _cfield((2, 3, 40, 40), 1)
        masks = (r.random((5, 40, 40)) > 0.7).astype(np.float32)
        got = channel_readout(jnp.asarray(u, jnp.complex64), masks)
        want = np.einsum("bdhw,chw->bc", np.abs(u) ** 2, masks)
        _assert_close(got, want, 1e-5)

    def test_single_sample(self):
        u = _cfield((3, 16, 16), 2)
        masks = np.ones((2, 16, 16), np.float32)
        got = channel_readout(jnp.asarray(u, jnp.complex64), masks)
        _assert_close(got, np.full((2,), np.sum(np.abs(u) ** 2)), 1e-5)

    def test_gradients_flow_through_channel_sum(self):
        ur, ui = (jnp.asarray(p, jnp.float32) for p in _planes((1, 2, 16, 16),
                                                               3))
        masks = jnp.ones((1, 16, 16), jnp.float32)
        da, db = jax.grad(lambda a, b: jnp.sum(channel_readout(
            jax.lax.complex(a, b), masks)), argnums=(0, 1))(ur, ui)
        np.testing.assert_allclose(da, 2 * ur, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(db, 2 * ui, rtol=1e-5, atol=1e-5)

    def test_eager_multichannel_model_reads_out_its_fields(self):
        """The eager RGB model's logits are the oracle readout of its
        per-channel output fields."""
        cfg = DONNConfig(name="mc-eager", n=24, depth=2, distance=0.05,
                         det_size=4, channels=3, engine="eager")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        x = np.random.default_rng(3).random((2, 3, 24, 24), np.float32)
        cm = m.channel_model
        fields = jax.vmap(
            lambda ph, xc: cm.fields({"phase": ph}, xc, None)[-1],
            in_axes=(0, -3), out_axes=-3)(params["phase"], jnp.asarray(x))
        want = np.einsum("bdhw,chw->bc", np.abs(np.asarray(fields)) ** 2,
                         cm.detector.masks)
        _assert_close(m.apply(params, x), want, 1e-5)


def _modulation_sites(gamma):
    """The plan's and the eager layer's phase modulation, (phi, u) -> u'."""
    plan = pp.PropagationPlan(df.Grid(8, PX), [0.05], WL, gamma=gamma)
    layer = DiffractiveLayer(df.Grid(8, PX), 0.05, WL, gamma=gamma)
    return {"plan": lambda phi, u: plan._modulate(u, phi),
            "eager": lambda phi, u: layer.modulate(phi, u)}


class TestModulation:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_oracle(self, shape):
        B, H, W = shape
        u = _cfield(shape, 1)
        phi = np.random.default_rng(3).uniform(0, 6.28, (H, W))
        want = 1.3 * u * np.exp(1j * phi)
        for name, fn in _modulation_sites(1.3).items():
            got = fn(jnp.asarray(phi, jnp.float32),
                     jnp.asarray(u, jnp.complex64))
            _assert_close(got, want, 1e-5)

    def test_unitary_when_gamma_one(self):
        u = jnp.asarray(_cfield((2, 32, 128), 4), jnp.complex64)
        phi = jnp.asarray(_planes((32, 128), 6)[0], jnp.float32)
        for name, fn in _modulation_sites(1.0).items():
            np.testing.assert_allclose(df.intensity(fn(phi, u)),
                                       df.intensity(u), rtol=1e-5, atol=1e-5)

    def test_gradients_match_reference(self):
        ur, ui = (jnp.asarray(p, jnp.float32) for p in _planes((2, 24, 96), 7))
        phi = jnp.asarray(_planes((24, 96), 9)[0], jnp.float32)

        def split_ref(p):
            # gamma * u * exp(j phi) on split planes
            c, s = 1.1 * jnp.cos(p), 1.1 * jnp.sin(p)
            return ur * c - ui * s, ur * s + ui * c

        def loss(a, b):
            return jnp.sum(jnp.sin(a) + b * b)

        want = jax.grad(lambda p: loss(*split_ref(p)))(phi)
        for name, fn in _modulation_sites(1.1).items():
            got = jax.grad(lambda p: loss(
                *(lambda v: (v.real, v.imag))(fn(p, jax.lax.complex(ur, ui)))
            ))(phi)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# the folded blocks need square planes: SHAPES' batch and row counts, an
# odd N among them
FOLD_SHAPES = [(b, h, h) for b, h, _ in SHAPES]


def _folded_product(u, mod):
    """unfold(modulate_folded(fold(u), fold_modulation(mod)))."""
    n = u.shape[-1]
    x = df.modulate_folded(df.fold_field(u), df.fold_modulation(mod[None])[0])
    return df.unfold_field(x, n)


class TestFoldedModulation:
    """``diffraction.modulate_folded`` (the folded hop's ``modulate``
    stage) against unfold -> multiply -> fold."""

    @pytest.mark.parametrize("shape", FOLD_SHAPES)
    @pytest.mark.parametrize("storage", ["float32", "bfloat16"])
    def test_matches_oracle(self, shape, storage):
        B, n, _ = shape
        u = _cfield(shape, 1)
        phi = np.random.default_rng(3).uniform(0, 6.28, (n, n))
        # a frozen modulation plane pair in its storage dtype, read in f32
        mr, mi = (jnp.asarray(p, jnp.float32).astype(storage).astype(
            jnp.float32) for p in (1.2 * np.cos(phi), 1.2 * np.sin(phi)))
        got = _folded_product(jnp.asarray(u, jnp.complex64),
                              jax.lax.complex(mr, mi))
        want = u * (np.asarray(mr, np.float64) + 1j * np.asarray(mi))
        _assert_close(got, want, 1e-5)

    def test_2d_input(self):
        u = _cfield((16, 16), 5)
        m = _cfield((16, 16), 6)
        got = _folded_product(jnp.asarray(u, jnp.complex64),
                              jnp.asarray(m, jnp.complex64))
        assert got.shape == (16, 16)
        _assert_close(got, u * m, 1e-5)

    def test_unit_modulus_keeps_energy(self):
        """A phase-only plane moves no energy between pixels."""
        u = jnp.asarray(_cfield((2, 33, 33), 7), jnp.complex64)
        phi = np.random.default_rng(8).uniform(0, 6.28, (33, 33))
        got = _folded_product(u, jnp.asarray(np.exp(1j * phi), jnp.complex64))
        np.testing.assert_allclose(df.intensity(got), df.intensity(u),
                                   rtol=1e-5, atol=1e-5)
