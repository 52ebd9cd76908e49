"""Propagation-plan engine: scan path vs eager loop, TF cache, TF multiply."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DONNConfig, build_model
from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.core.models import clear_emulation_caches
from repro.data import synth_digits, synth_rgb_scenes, synth_seg

TINY = dict(name="t", n=64, depth=3, distance=0.05, det_size=8)


def _pair(cfg_kw):
    cfg = DONNConfig(**cfg_kw)
    return build_model(cfg), build_model(
        dataclasses.replace(cfg, engine="eager")
    )


def _force_folded(monkeypatch):
    """The scan's hops as the packed hop, folded (a TPU rule on a CPU)."""
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    monkeypatch.setattr(pp, "_folded_hop_applies", lambda n: True)
    clear_emulation_caches()


FOLDED = {}  # the default config, its scan forced to the folded hop


class TestScanMatchesEager:
    @pytest.mark.parametrize(
        "extra",
        [
            {},
            {"approximation": "fresnel"},
            {"pad": True},
            # Fraunhofer needs the far field: at TINY's default z=0.05 the
            # Fresnel number is ~50 (physics validator flags it); z=2.5
            # puts every hop at F <= 1 where the single-FFT pattern holds
            {"approximation": "fraunhofer", "band_limit": False,
             "distance": 2.5},
            FOLDED,
            {"codesign": "qat", "device_levels": 64},
            {"distances": (0.04, 0.05, 0.06, 0.08)},
        ],
        ids=["rs", "fresnel", "padded", "fraunhofer", "folded", "qat",
             "heterogeneous"],
    )
    def test_classify_forward(self, monkeypatch, extra):
        if extra is FOLDED:
            _force_folded(monkeypatch)
        m_scan, m_eager = _pair({**TINY, **extra})
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(4, seed=0)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )

    def test_classify_gradients_match(self):
        m_scan, m_eager = _pair(TINY)
        p = m_scan.init(jax.random.PRNGKey(1))
        xs, _ = synth_digits(4, seed=1)
        x = jnp.asarray(xs)
        g1 = jax.grad(lambda p: jnp.sum(m_scan.apply(p, x) ** 2))(p)
        g2 = jax.grad(lambda p: jnp.sum(m_eager.apply(p, x) ** 2))(p)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_segmentation_with_skip(self):
        m_scan, m_eager = _pair(
            {**TINY, "segmentation": True, "skip_from": 0, "layer_norm": True}
        )
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_seg(4, seed=0)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x, train=True), m_eager.apply(p, x, train=True),
            rtol=1e-5, atol=1e-5,
        )

    def test_jit_apply(self):
        m_scan, m_eager = _pair(TINY)
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(4, seed=2)
        x = jnp.asarray(xs)
        got = jax.jit(lambda p, x: m_scan.apply(p, x))(p, x)
        np.testing.assert_allclose(got, m_eager.apply(p, x), rtol=1e-5,
                                   atol=1e-5)


class TestForwardSlicing:
    """forward(start/stop) composition — beyond the segmentation skip path."""

    def _plan_and_inputs(self, extra=None, seed=0):
        cfg = DONNConfig(**{**TINY, **(extra or {})})
        plan = pp.plan_from_config(cfg, 1.0)
        r = np.random.default_rng(seed)
        phis = jnp.asarray(
            r.uniform(0, 2 * np.pi, (cfg.depth, cfg.n, cfg.n)), jnp.float32
        )
        u = jnp.asarray(
            r.normal(size=(2, cfg.n, cfg.n))
            + 1j * r.normal(size=(2, cfg.n, cfg.n)),
            jnp.complex64,
        )
        return cfg, plan, phis, u

    @pytest.mark.parametrize("cut", [1, 2])
    def test_slices_compose_to_full_forward(self, cut):
        _, plan, phis, u = self._plan_and_inputs()
        full = plan.forward(phis, u)
        head = plan.forward(phis, u, stop=cut)
        tail = plan.forward(phis, head, start=cut)
        np.testing.assert_allclose(tail, full, rtol=1e-5, atol=1e-6)

    def test_slices_compose_with_codesign_rngs(self):
        cfg, plan, phis, u = self._plan_and_inputs(
            {"codesign": "gumbel", "device_levels": 16}, seed=1
        )
        rngs = jax.random.split(jax.random.PRNGKey(3), cfg.depth)
        full = plan.forward(phis, u, rngs)
        head = plan.forward(phis, u, rngs, stop=1)
        tail = plan.forward(phis, head, rngs, start=1)
        # codesign quantizes the full stack, so layer-i rng alignment is
        # independent of the slice boundaries
        np.testing.assert_allclose(tail, full, rtol=1e-5, atol=1e-6)

    def test_empty_slice_is_identity(self):
        _, plan, phis, u = self._plan_and_inputs()
        np.testing.assert_array_equal(plan.forward(phis, u, start=2, stop=2), u)

    def test_external_tfs_match_baked_constants(self):
        _, plan, phis, u = self._plan_and_inputs(seed=2)
        tfs = plan._tf_pair()
        np.testing.assert_allclose(
            plan.apply(phis, u, tfs=tfs), plan.apply(phis, u),
            rtol=1e-6, atol=1e-6,
        )


class TestApplyBatch:
    def test_matches_stacked_sequential(self):
        cfg = DONNConfig(**TINY)
        plan = pp.plan_from_config(cfg, 1.0)
        r = np.random.default_rng(0)
        K = 3
        phis = jnp.asarray(
            r.uniform(0, 2 * np.pi, (K, cfg.depth, cfg.n, cfg.n)), jnp.float32
        )
        u = jnp.asarray(
            r.normal(size=(2, cfg.n, cfg.n))
            + 1j * r.normal(size=(2, cfg.n, cfg.n)),
            jnp.complex64,
        )
        got = plan.apply_batch(phis, u)
        for k in range(K):
            np.testing.assert_allclose(
                got[k], plan.apply(phis[k], u), rtol=1e-5, atol=1e-6
            )

    def test_per_candidate_inputs_and_rng(self):
        cfg = DONNConfig(**{**TINY, "codesign": "gumbel", "device_levels": 8})
        plan = pp.plan_from_config(cfg, 1.0)
        r = np.random.default_rng(1)
        K = 2
        phis = jnp.asarray(
            r.uniform(0, 2 * np.pi, (K, cfg.depth, cfg.n, cfg.n)), jnp.float32
        )
        u = jnp.asarray(
            r.normal(size=(K, 2, cfg.n, cfg.n))
            + 1j * r.normal(size=(K, 2, cfg.n, cfg.n)),
            jnp.complex64,
        )
        rng = jax.random.PRNGKey(5)
        got = plan.apply_batch(phis, u, rng=rng, per_candidate_inputs=True)
        rngs = jax.random.split(rng, K)
        for k in range(K):
            np.testing.assert_allclose(
                got[k], plan.apply(phis[k], u[k], rngs[k]),
                rtol=1e-5, atol=1e-6,
            )


class TestScanUnroll:
    @pytest.mark.parametrize("unroll", [1, 2, 3])
    def test_unroll_matches_eager(self, unroll):
        m_scan, m_eager = _pair({**TINY, "scan_unroll": unroll})
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(4, seed=0)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )

    def test_default_heuristic(self):
        assert pp.default_scan_unroll(3) == 3
        assert pp.default_scan_unroll(8) == 8
        assert pp.default_scan_unroll(16) == 8
        assert pp.default_scan_unroll(64) == 8

    def test_invalid_unroll_rejected(self):
        with pytest.raises(ValueError):
            DONNConfig(**{**TINY, "scan_unroll": 0})


class TestTFDtype:
    def test_bf16_storage_agrees_loosely(self):
        """bf16 TF planes, f32 accumulation: documented looser tolerance."""
        m_bf16, m_eager = _pair({**TINY, "tf_dtype": "bfloat16"})
        p = m_bf16.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(4, seed=0)
        x = jnp.asarray(xs)
        got = m_bf16.apply(p, x)
        want = m_eager.apply(p, x)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
        assert got.dtype == jnp.float32  # accumulation stays f32
        # the bf16 storage must actually engage: outputs differ from the
        # f32 scan path beyond float32 roundoff
        f32 = build_model(DONNConfig(**{**TINY, "tf_dtype": "float32"}))
        assert not np.allclose(got, f32.apply(p, x), rtol=1e-6, atol=1e-6)

    def test_invalid_tf_dtype_rejected(self):
        with pytest.raises(ValueError):
            DONNConfig(**{**TINY, "tf_dtype": "float16"})


class TestTFCache:
    def test_repeated_geometry_hits(self):
        pp.clear_tf_cache()
        cfg = DONNConfig(**TINY)
        build_model(cfg)
        s0 = pp.tf_cache_stats()
        assert s0["misses"] > 0
        build_model(cfg)  # identical geometry: everything served from cache
        s1 = pp.tf_cache_stats()
        assert s1["misses"] == s0["misses"]
        assert s1["hits"] > s0["hits"]

    def test_distinct_geometry_misses(self):
        pp.clear_tf_cache()
        g = df.Grid(32, 36e-6)
        pp.transfer_planes(g, 0.05, 532e-9)
        before = pp.tf_cache_stats()["misses"]
        pp.transfer_planes(g, 0.06, 532e-9)  # different z
        pp.transfer_planes(g, 0.05, 633e-9)  # different wavelength
        assert pp.tf_cache_stats()["misses"] == before + 2

    def test_cached_planes_match_direct_computation(self):
        g = df.Grid(32, 36e-6)
        h = df.transfer_function(g, 0.05, 532e-9, df.RS, True)
        planes = pp.transfer_planes(g, 0.05, 532e-9, df.RS, True)
        np.testing.assert_array_equal(planes["hr"], h.real)
        np.testing.assert_array_equal(planes["hi"], h.imag)
        assert set(planes) == {"hr", "hi"}

    def test_lru_refresh_on_hit(self, monkeypatch):
        """A hit must refresh recency: alternating sweeps keep hot entries."""
        pp.clear_tf_cache()
        monkeypatch.setattr(pp, "_TF_CACHE_MAX", 3)
        g = df.Grid(8, 36e-6)
        zs = [0.01, 0.02, 0.03]
        for z in zs:
            pp.transfer_planes(g, z, 532e-9)
        pp.transfer_planes(g, zs[0], 532e-9)  # hit: refresh z=0.01
        pp.transfer_planes(g, 0.04, 532e-9)  # evicts z=0.02 (now oldest)
        keys = {k[2] for k in pp._TF_CACHE}
        assert 0.01 in keys and 0.02 not in keys
        assert 0.03 in keys and 0.04 in keys

    def test_eviction_bounds_size(self, monkeypatch):
        pp.clear_tf_cache()
        monkeypatch.setattr(pp, "_TF_CACHE_MAX", 4)
        g = df.Grid(8, 36e-6)
        for i in range(10):
            pp.transfer_planes(g, 0.01 + 0.001 * i, 532e-9)
        assert len(pp._TF_CACHE) <= 4
        assert pp.tf_cache_stats()["misses"] == 10


class TestPlanCache:
    def test_repeated_config_hits(self):
        pp.clear_plan_cache()
        cfg = DONNConfig(**TINY)
        p1 = pp.plan_from_config(cfg, 1.0)
        s0 = pp.plan_cache_stats()
        p2 = pp.plan_from_config(DONNConfig(**TINY), 1.0)
        s1 = pp.plan_cache_stats()
        assert p1 is p2
        assert s1["hits"] == s0["hits"] + 1
        assert s1["misses"] == s0["misses"]

    def test_geometry_change_misses(self):
        pp.clear_plan_cache()
        cfg = DONNConfig(**TINY)
        pp.plan_from_config(cfg, 1.0)
        pp.plan_from_config(dataclasses.replace(cfg, distance=0.06), 1.0)
        pp.plan_from_config(dataclasses.replace(cfg, scan_unroll=2), 1.0)
        pp.plan_from_config(cfg, 0.9)  # gamma is part of the key
        assert pp.plan_cache_stats()["misses"] == 4

    def test_eviction_lru(self, monkeypatch):
        pp.clear_plan_cache()
        monkeypatch.setattr(pp, "_PLAN_CACHE_MAX", 2)
        cfg = DONNConfig(**TINY)
        a = pp.plan_from_config(cfg, 1.0)
        pp.plan_from_config(dataclasses.replace(cfg, distance=0.06), 1.0)
        assert pp.plan_from_config(cfg, 1.0) is a  # hit refreshes recency
        pp.plan_from_config(dataclasses.replace(cfg, distance=0.07), 1.0)
        # the refreshed entry survived; the middle one was evicted
        assert pp.plan_from_config(cfg, 1.0) is a
        assert len(pp._PLAN_CACHE) <= 2

    def test_clear_resets_stats_and_executables(self):
        pp.clear_plan_cache()
        cfg = DONNConfig(**TINY)
        pp.plan_from_config(cfg, 1.0)
        pp.clear_plan_cache()
        s = pp.plan_cache_stats()
        assert s == {"hits": 0, "misses": 0, "size": 0,
                     "exec_hits": 0, "exec_misses": 0, "exec_size": 0}


class TestMultiChannelBatched:
    def test_batched_matches_per_channel_reference(self):
        cfg = DONNConfig(**{**TINY, "channels": 3, "num_classes": 6})
        m_scan, m_eager = _pair(cfg.__dict__)
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_rgb_scenes(4, seed=0)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )

    def test_batched_gradients_match(self):
        cfg = DONNConfig(**{**TINY, "channels": 3, "num_classes": 6})
        m_scan, m_eager = _pair(cfg.__dict__)
        p = m_scan.init(jax.random.PRNGKey(2))
        xs, _ = synth_rgb_scenes(4, seed=1)
        x = jnp.asarray(xs)
        g1 = jax.grad(lambda p: jnp.sum(m_scan.apply(p, x) ** 2))(p)
        g2 = jax.grad(lambda p: jnp.sum(m_eager.apply(p, x) ** 2))(p)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_batched_folded_forward(self, monkeypatch):
        _force_folded(monkeypatch)
        m_scan, m_eager = _pair({**TINY, "channels": 3, "num_classes": 6})
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_rgb_scenes(4, seed=2)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )


class TestSpectralMul:
    """The plan's TF multiply (the ``tf_mul`` stage) against the polar
    oracle ``x * amp * exp(j theta)``, on cartesian ``(hr, hi)`` planes."""

    def _rand(self, shape, seed):
        return np.random.default_rng(seed).normal(size=shape)

    def _case(self, xshape, pshape, seed):
        x = self._rand(xshape, seed) + 1j * self._rand(xshape, seed + 1)
        th = self._rand(pshape, seed + 2)
        am = np.abs(self._rand(pshape, seed + 3))
        return x, th, am, x * am * np.exp(1j * th)

    def _plan(self):
        return pp.PropagationPlan(df.Grid(8, 36e-6), [0.05], 532e-9)

    def _mul(self, x, th, am):
        pair = tuple(jnp.asarray(am * f(th), jnp.float32)
                     for f in (np.cos, np.sin))
        return self._plan()._spectral_mul(jnp.asarray(x, jnp.complex64), pair)

    def _close(self, got, want):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())

    @pytest.mark.parametrize("shape", [(1, 8, 128), (3, 37, 111), (2, 64, 64)])
    def test_forward_matches_ref(self, shape):
        x, th, am, want = self._case(shape, shape[-2:], 1)
        self._close(self._mul(x, th, am), want)

    def test_per_plane_forward(self):
        x, th, am, want = self._case((4, 3, 16, 64), (3, 16, 64), 5)
        self._close(self._mul(x, th, am), want)

    def test_multi_axis_plane_broadcast(self):
        """(K, C, H, W) plane stacks against (B, K, C, H, W) fields."""
        x, th, am, want = self._case((4, 2, 3, 16, 64), (2, 3, 16, 64), 20)
        self._close(self._mul(x, th, am), want)
        # leading batch axis absent: (K, C, H, W) fields take them too
        self._close(self._mul(x[0], th, am), want[0])

    def test_mismatched_plane_axes_raise(self):
        x = self._rand((4, 2, 16, 64), 24) + 0j
        th, am = self._rand((3, 16, 64), 25), np.ones((3, 16, 64))
        with pytest.raises((TypeError, ValueError), match="broadcast"):
            self._mul(x, th, am)

    def test_gradients_match_ref(self):
        B, H, W = 2, 33, 65
        x, th, am, _ = self._case((B, H, W), (H, W), 9)
        args = tuple(jnp.asarray(p, jnp.float32)
                     for p in (x.real, x.imag, am * np.cos(th),
                               am * np.sin(th)))
        plan = self._plan()

        def loss(a, b):
            return jnp.sum(a ** 2 + 2.0 * b)

        def via_plan(xr, xi, hr, hi):
            out = plan._spectral_mul(jax.lax.complex(xr, xi), (hr, hi))
            return loss(out.real, out.imag)

        def split_ref(xr, xi, hr, hi):
            return loss(xr * hr - xi * hi, xr * hi + xi * hr)

        g1 = jax.grad(via_plan, argnums=(0, 1, 2, 3))(*args)
        g2 = jax.grad(split_ref, argnums=(0, 1, 2, 3))(*args)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_unit_amp_matches_modulation(self):
        """A unit-modulus TF multiply is a phase modulation at gamma 1."""
        x, th, _, _ = self._case((2, 16, 128), (16, 128), 13)
        got = self._mul(x, th, np.ones_like(th))
        mod = self._plan()._modulate(jnp.asarray(x, jnp.complex64),
                                     jnp.asarray(th, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(mod),
                                   rtol=1e-5, atol=1e-5)
