"""Heterogeneous per-layer architectures: LayerSpec config, segmented scan
plans, mixed-precision codesign, ragged-depth batched DSE, and the
plan/static cache-key guard."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.dsl as lr
from repro.core import (
    DONNConfig,
    LayerSpec,
    PropagationPlan,
    SegmentedPlan,
    build_model,
    emulate_batch,
)
from repro.core import codesign as cd
from repro.core import diffraction as df
from repro.core import models as mmod
from repro.core import propagation as pp
from repro.data import synth_digits, synth_rgb_scenes, synth_seg

BASE = dict(n=48, depth=3, distance=0.05, det_size=6)

# 2 distinct precisions (256-level SLM front, 4-level printed back) and
# 2 distinct plane sizes — the acceptance-criteria architecture
MIXED = (
    LayerSpec(distance=0.04, size=48, device_levels=256, codesign="qat"),
    LayerSpec(distance=0.05, size=48, device_levels=256, codesign="qat"),
    LayerSpec(distance=0.05, size=32, pixel_size=54e-6, device_levels=4,
              codesign="qat"),
)

# same shape of mix, sized for the 64x64 rgb/segmentation synth data
MIXED64 = (
    LayerSpec(distance=0.04, size=64, device_levels=256, codesign="qat"),
    LayerSpec(distance=0.05, size=64, device_levels=256, codesign="qat"),
    LayerSpec(distance=0.05, size=48, pixel_size=48e-6, device_levels=4,
              codesign="qat"),
)


def _pair(cfg_kw):
    cfg = DONNConfig(**cfg_kw)
    return build_model(cfg), build_model(
        dataclasses.replace(cfg, engine="eager")
    )


def _digits(k=4, seed=0):
    xs, _ = synth_digits(k, seed=seed)
    return jnp.asarray(xs)


class TestConfigValidation:
    def test_bad_distances_length_fails_at_construction(self):
        with pytest.raises(ValueError, match="distances"):
            DONNConfig(**{**BASE, "distances": (0.05, 0.05)})

    def test_layers_length_mismatch_names_field(self):
        with pytest.raises(ValueError, match="layers"):
            DONNConfig(**{**BASE, "layers": (LayerSpec(),)})

    def test_layers_and_distances_mutually_exclusive(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            DONNConfig(**{**BASE, "layers": (LayerSpec(),) * 3,
                          "distances": (0.05,) * 4})

    def test_layer_spec_validates_enums(self):
        with pytest.raises(ValueError, match="approximation"):
            LayerSpec(approximation="angular")
        with pytest.raises(ValueError, match="codesign"):
            LayerSpec(codesign="quantize")

    def test_gap_distances_with_layers(self):
        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        assert cfg.gap_distances() == (0.04, 0.05, 0.05, 0.05)


class TestCanonicalization:
    def test_uniform_layers_fold_to_scalar_form(self):
        cfg = DONNConfig(**{**BASE,
                            "layers": (LayerSpec(distance=0.05),) * 3})
        canon = cfg.canonical()
        assert canon.layers is None
        assert canon.gap_distances() == cfg.gap_distances()

    def test_uniform_layers_hit_identical_plan_cache_entry(self):
        pp.clear_plan_cache()
        scalar = DONNConfig(**BASE)
        spelled = DONNConfig(**{**BASE,
                                "layers": (LayerSpec(distance=0.05),) * 3})
        assert (pp.plan_cache_key(scalar, 1.0)
                == pp.plan_cache_key(spelled, 1.0))
        assert pp.plan_from_config(scalar, 1.0) is pp.plan_from_config(
            spelled, 1.0
        )
        assert isinstance(pp.plan_from_config(spelled, 1.0), PropagationPlan)

    def test_uniform_layers_fold_onto_common_values_not_scalars(self):
        """Layers equal to *each other* fold even when the inheritance
        scalars differ — e.g. an all-4-level-qat stack spelled per layer
        is the same architecture as the scalar qat config."""
        scalar = DONNConfig(**BASE, codesign="qat", device_levels=4)
        spelled = DONNConfig(
            **BASE,
            layers=tuple(
                LayerSpec(distance=0.05, codesign="qat", device_levels=4)
                for _ in range(3)
            ),
        )
        canon = spelled.canonical()
        assert canon.layers is None
        assert canon.codesign == "qat" and canon.device_levels == 4
        assert (pp.plan_cache_key(spelled, 1.0)
                == pp.plan_cache_key(scalar, 1.0))
        # and emulate_batch accepts it as a uniform candidate
        params = build_model(scalar).init(jax.random.PRNGKey(0))
        out = emulate_batch([spelled, scalar], params, _digits())
        np.testing.assert_allclose(out[0], out[1], rtol=1e-6, atol=1e-6)

    def test_layers_off_detector_grid_stay_segmented(self):
        # all layers equal each other but live on a smaller plane than the
        # detector grid: not expressible as a scalar config
        cfg = DONNConfig(**{**BASE,
                            "layers": (LayerSpec(distance=0.05, size=32),) * 3})
        assert cfg.canonical().layers is not None

    def test_heterogeneous_config_gets_segmented_plan(self):
        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        plan = pp.plan_from_config(cfg, 1.0)
        assert isinstance(plan, SegmentedPlan)
        assert plan.segment_slices == ((0, 2), (2, 3))

    def test_inherited_none_fields_resolve_from_scalars(self):
        cfg = DONNConfig(**{**BASE, "codesign": "qat", "device_levels": 16,
                            "layers": (LayerSpec(distance=0.04),
                                       LayerSpec(distance=0.05,
                                                 device_levels=4),
                                       LayerSpec(distance=0.05))})
        r = cfg.resolved_layers()
        assert [l.device_levels for l in r] == [16, 4, 16]
        assert all(l.size == cfg.n and l.codesign == "qat" for l in r)


class TestHeterogeneousForward:
    @pytest.mark.parametrize(
        "layers",
        [
            MIXED,
            # mixed approximation methods, uniform grid
            (LayerSpec(distance=0.04, approximation="rs"),
             LayerSpec(distance=0.05, approximation="fresnel"),
             LayerSpec(distance=0.05, approximation="rs")),
            # mixed pixel size only (same n: pure resampling stitch)
            (LayerSpec(distance=0.04),
             LayerSpec(distance=0.05, pixel_size=54e-6),
             LayerSpec(distance=0.05, pixel_size=54e-6)),
        ],
        ids=["mixed_size_precision", "mixed_method", "mixed_pitch"],
    )
    def test_classify_scan_matches_eager(self, layers):
        m_scan, m_eager = _pair({**BASE, "layers": layers})
        p = m_scan.init(jax.random.PRNGKey(0))
        x = _digits()
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )

    def test_gradients_match(self):
        m_scan, m_eager = _pair({**BASE, "layers": MIXED})
        p = m_scan.init(jax.random.PRNGKey(1))
        x = _digits(seed=1)
        g1 = jax.grad(lambda p: jnp.sum(m_scan.apply(p, x) ** 2))(p)
        g2 = jax.grad(lambda p: jnp.sum(m_eager.apply(p, x) ** 2))(p)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_ragged_param_shapes(self):
        m, _ = _pair({**BASE, "layers": MIXED})
        p = m.init(jax.random.PRNGKey(0))
        shapes = [p["phase"][f"layer_{i}"].shape for i in range(3)]
        assert shapes == [(48, 48), (48, 48), (32, 32)]
        phis = m.stacked_phases(p)
        assert isinstance(phis, tuple) and len(phis) == 2
        assert phis[0].shape == (2, 48, 48) and phis[1].shape == (1, 32, 32)

    def test_rng_codesign_alignment(self):
        layers = (
            LayerSpec(distance=0.04, device_levels=16, codesign="gumbel"),
            LayerSpec(distance=0.05, size=32, pixel_size=54e-6,
                      device_levels=8, codesign="gumbel"),
            LayerSpec(distance=0.05, size=32, pixel_size=54e-6,
                      device_levels=8, codesign="gumbel"),
        )
        m_scan, m_eager = _pair({**BASE, "layers": layers})
        p = m_scan.init(jax.random.PRNGKey(0))
        x = _digits(seed=2)
        rng = jax.random.PRNGKey(7)
        np.testing.assert_allclose(
            m_scan.apply(p, x, rng), m_eager.apply(p, x, rng),
            rtol=1e-5, atol=1e-5,
        )

    def test_multichannel_heterogeneous(self):
        cfg_kw = {**BASE, "n": 64, "channels": 3, "num_classes": 6,
                  "layers": MIXED64}
        m_scan, m_eager = _pair(cfg_kw)
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_rgb_scenes(4, seed=0)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(
            m_scan.apply(p, x), m_eager.apply(p, x), rtol=1e-5, atol=1e-5
        )

    def test_segmentation_skip_heterogeneous(self):
        cfg_kw = {**BASE, "n": 64, "segmentation": True, "skip_from": 0,
                  "layer_norm": True, "layers": MIXED64}
        m_scan, m_eager = _pair(cfg_kw)
        p = m_scan.init(jax.random.PRNGKey(0))
        xs, _ = synth_seg(4, seed=0)
        x = jnp.asarray(xs)
        got = m_scan.apply(p, x, train=True)
        assert got.shape == (4, 64, 64)  # detector/system grid
        np.testing.assert_allclose(
            got, m_eager.apply(p, x, train=True), rtol=1e-5, atol=1e-4
        )

    def test_jit_apply(self):
        m_scan, m_eager = _pair({**BASE, "layers": MIXED})
        p = m_scan.init(jax.random.PRNGKey(0))
        x = _digits(seed=3)
        got = jax.jit(lambda p, x: m_scan.apply(p, x))(p, x)
        np.testing.assert_allclose(got, m_eager.apply(p, x), rtol=1e-5,
                                   atol=1e-5)

    def test_train_step(self):
        """A heterogeneous model trains a step through the runtime path."""
        from repro.nn import init_params
        from repro.optim import AdamW
        from repro.runtime.donn_steps import (
            donn_state_specs, make_donn_train_step,
        )

        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        state = init_params(donn_state_specs(cfg), jax.random.PRNGKey(0))
        step = jax.jit(make_donn_train_step(cfg, AdamW(lr=0.05)))
        xs, ys = synth_digits(8, seed=0)
        batch = {"images": jnp.asarray(xs), "labels": jnp.asarray(ys)}
        new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        moved = [
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(new_state["params"]),
                            jax.tree.leaves(state["params"]))
        ]
        assert all(m > 0 for m in moved)


class TestSegmentedSlicing:
    def _plan_and_inputs(self, seed=0):
        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        plan = pp.plan_from_config(cfg, 1.0)
        r = np.random.default_rng(seed)
        phases = [
            jnp.asarray(r.uniform(0, 2 * np.pi, (s.size, s.size)),
                        jnp.float32)
            for s in cfg.resolved_layers()
        ]
        u = jnp.asarray(
            r.normal(size=(2, 48, 48)) + 1j * r.normal(size=(2, 48, 48)),
            jnp.complex64,
        )
        return plan, plan.stack_phases(phases), u

    @pytest.mark.parametrize("cut", [1, 2])  # mid-segment and boundary
    def test_slices_compose_to_full_forward(self, cut):
        plan, phis, u = self._plan_and_inputs()
        full = plan.forward(phis, u)
        head = plan.forward(phis, u, stop=cut)
        tail = plan.forward(phis, head, start=cut)
        np.testing.assert_allclose(tail, full, rtol=1e-5, atol=1e-6)

    def test_full_apply_shape_on_detector_grid(self):
        plan, phis, u = self._plan_and_inputs(seed=1)
        out = plan.apply(phis, u)
        assert out.shape == (2, 48, 48)  # resampled back to detector grid


class TestResampling:
    def test_equal_grids_identity(self):
        g = df.Grid(32, 36e-6)
        u = jnp.ones((32, 32), jnp.complex64)
        assert df.resample_field(u, g, g) is u

    def test_equal_pitch_is_exact_crop_pad(self):
        g_in, g_out = df.Grid(32, 36e-6), df.Grid(48, 36e-6)
        r = np.random.default_rng(0)
        u = jnp.asarray(r.normal(size=(32, 32)), jnp.float32)
        up = df.resample_field(u, g_in, g_out)
        back = df.resample_field(up, g_out, g_in)
        np.testing.assert_allclose(back, u, atol=1e-6)  # pad then crop
        A = df.resample_matrix(g_in, g_out)
        assert set(np.unique(A)) <= {0.0, 1.0}

    def test_rows_are_partition_of_unity_inside_aperture(self):
        A = df.resample_matrix(df.Grid(48, 36e-6), df.Grid(32, 54e-6))
        sums = A.sum(axis=1)
        interior = sums[2:-2]
        np.testing.assert_allclose(interior, 1.0, atol=1e-6)

    def test_matrix_cache_is_bounded_lru(self, monkeypatch):
        df._RESAMPLE_CACHE.clear()
        monkeypatch.setattr(df, "_RESAMPLE_CACHE_MAX", 3)
        grids = [df.Grid(8 + i, 36e-6) for i in range(5)]
        out = df.Grid(16, 36e-6)
        for g in grids[:3]:
            df.resample_matrix(g, out)
        a = df.resample_matrix(grids[0], out)  # hit: refresh recency
        df.resample_matrix(grids[3], out)  # evicts grids[1] (oldest)
        assert len(df._RESAMPLE_CACHE) <= 3
        assert df.resample_matrix(grids[0], out) is a  # survived eviction


class TestMixedDepthEmulateBatch:
    def _cfgs(self, depths=(2, 3, 5), **extra):
        return [
            DONNConfig(name=f"d{d}", n=48, det_size=6, depth=d,
                       distance=0.05, **extra)
            for d in depths
        ]

    def test_matches_sequential_per_candidate(self):
        cfgs = self._cfgs()
        plist = [build_model(c).init(jax.random.PRNGKey(i))
                 for i, c in enumerate(cfgs)]
        x = _digits()
        seq = [build_model(c).apply(p, x) for c, p in zip(cfgs, plist)]
        bat = emulate_batch(cfgs, plist, x)
        assert bat.shape == (len(cfgs),) + seq[0].shape
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_qat_codesign_mixed_depth(self):
        cfgs = self._cfgs(codesign="qat", device_levels=16)
        plist = [build_model(c).init(jax.random.PRNGKey(i))
                 for i, c in enumerate(cfgs)]
        x = _digits(seed=1)
        seq = [build_model(c).apply(p, x) for c, p in zip(cfgs, plist)]
        bat = emulate_batch(cfgs, plist, x)
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_mixed_depth_and_geometry(self):
        cfgs = [
            DONNConfig(name="a", n=48, det_size=6, depth=2, distance=0.04,
                       wavelength=532e-9),
            DONNConfig(name="b", n=48, det_size=6, depth=4, distance=0.06,
                       wavelength=633e-9, pixel_size=30e-6),
        ]
        plist = [build_model(c).init(jax.random.PRNGKey(i))
                 for i, c in enumerate(cfgs)]
        x = _digits(seed=2)
        bat = emulate_batch(cfgs, plist, x)
        for i, (c, p) in enumerate(zip(cfgs, plist)):
            np.testing.assert_allclose(
                bat[i], build_model(c).apply(p, x), rtol=1e-5, atol=1e-5
            )

    def test_executable_reused_across_mixed_depth_sets(self):
        mmod.clear_emulation_caches()
        cfgs = self._cfgs()
        plist = [build_model(c).init(jax.random.PRNGKey(i))
                 for i, c in enumerate(cfgs)]
        x = _digits(seed=4)
        emulate_batch(cfgs, plist, x)
        s0 = pp.plan_cache_stats()
        # same depth *profile*, different distances: same padded program
        cfgs2 = [dataclasses.replace(c, distance=0.045) for c in cfgs]
        emulate_batch(cfgs2, plist, x)
        s1 = pp.plan_cache_stats()
        assert s1["exec_misses"] == s0["exec_misses"]
        assert s1["exec_hits"] == s0["exec_hits"] + 1

    def test_skip_from_ignored_without_segmentation(self):
        # DONN classifiers ignore skip_from; the batched path must too
        cfgs = [
            dataclasses.replace(c, skip_from=5)
            for c in self._cfgs(depths=(2, 3))
        ]
        plist = [build_model(c).init(jax.random.PRNGKey(i))
                 for i, c in enumerate(cfgs)]
        x = _digits(seed=6)
        bat = emulate_batch(cfgs, plist, x)
        for i, (c, p) in enumerate(zip(cfgs, plist)):
            np.testing.assert_allclose(
                bat[i], build_model(c).apply(p, x), rtol=1e-5, atol=1e-5
            )

    def test_heterogeneous_layer_configs_rejected(self):
        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="per-candidate-uniform"):
            emulate_batch([cfg], [params], _digits())

    def test_dse_explore_with_depth_candidates(self):
        from repro.core.dse import LightRidgeDSE

        rng = np.random.default_rng(0)
        pts, accs = [], []
        for lam in (500e-9, 600e-9):
            for d in (20e-6, 36e-6):
                for D in (0.05, 0.1):
                    for depth in (2, 4):
                        pts.append((lam, d, D, depth))
                        accs.append(0.5 + 0.05 * depth
                                    + rng.uniform(0, 0.01))
        dse = LightRidgeDSE(n_estimators=40)
        dse.fit(pts, accs)
        seen = {}

        def emulate_batch_fn(points):
            seen["pts"] = points
            return [0.9] * len(points)

        res = dse.explore(
            550e-9,
            [(20e-6, 0.05, 2), (36e-6, 0.1, 4), (20e-6, 0.1, 4)],
            top_k=2, emulate_batch=emulate_batch_fn,
        )
        assert len(seen["pts"]) == 2 and len(seen["pts"][0]) == 4
        assert "depth" in res.best_point

    def test_mixed_tuple_arity_rejected(self):
        from repro.core.dse import LightRidgeDSE

        dse = LightRidgeDSE(n_estimators=10)
        with pytest.raises(ValueError, match="3- and 4-tuple"):
            dse.fit([(500e-9, 20e-6, 0.05), (500e-9, 20e-6, 0.05, 2)],
                    [0.5, 0.6])


class TestSpecRoundTrip:
    @pytest.mark.parametrize(
        "cfg",
        [
            DONNConfig(name="u", **BASE, codesign="qat", device_levels=64),
            DONNConfig(name="h", **{**BASE, "layers": MIXED}),
            DONNConfig(name="s", **{**BASE, "segmentation": True,
                                    "skip_from": 0, "layer_norm": True}),
            DONNConfig(name="d", **BASE,
                       distances=None, scan_unroll=2, tf_dtype="bfloat16",
                       engine="eager", channels=3, num_classes=6),
            # uniform layers living off the detector grid: still needs the
            # layers form on the from_spec side (scalar can't express it)
            DONNConfig(name="og", **{**BASE,
                                     "layers": (LayerSpec(distance=0.05,
                                                          size=32),) * 3}),
        ],
        ids=["uniform_qat", "heterogeneous", "segmentation", "runtime_knobs",
             "uniform_off_detector_grid"],
    )
    def test_roundtrip_preserves_architecture(self, cfg):
        spec = lr.to_spec(cfg)
        json.loads(json.dumps(spec))  # JSON-able
        _, cfg2 = lr.from_spec(spec)
        assert cfg2.resolved_layers() == cfg.resolved_layers()
        assert cfg2.gap_distances() == cfg.gap_distances()
        assert mmod.config_static_key(cfg2) == mmod.config_static_key(cfg)
        assert pp.plan_cache_key(cfg2, 1.0) == pp.plan_cache_key(cfg, 1.0)

    def test_roundtrip_preserves_laser_profile(self):
        from repro.core import Laser

        cfg = DONNConfig(name="l", **BASE)
        src = Laser(wavelength=532e-9, profile="gaussian", waist=1e-3,
                    power=2.0)
        spec = lr.to_spec(cfg, src)
        json.loads(json.dumps(spec))
        model, _ = lr.from_spec(spec)
        ref = build_model(cfg, src)
        p = ref.init(jax.random.PRNGKey(0))
        x = _digits(seed=6)
        np.testing.assert_allclose(model.apply(p, x), ref.apply(p, x),
                                   rtol=1e-6, atol=1e-6)

    def test_roundtrip_preserves_detector_grid(self):
        """The detector grid (cfg.n/pixel_size) is carried explicitly, not
        inferred from the first layer: a stack whose planes are smaller
        than the detector round-trips to the same outputs."""
        cfg = DONNConfig(
            name="dg", n=64, depth=2, distance=0.05, det_size=8,
            layers=(LayerSpec(distance=0.05, size=48),
                    LayerSpec(distance=0.05, size=32, pixel_size=54e-6)),
        )
        _, cfg2 = lr.from_spec(lr.to_spec(cfg))
        assert (cfg2.n, cfg2.pixel_size) == (cfg.n, cfg.pixel_size)
        assert mmod.config_static_key(cfg2) == mmod.config_static_key(cfg)
        m1, m2 = build_model(cfg), build_model(cfg2)
        p = m1.init(jax.random.PRNGKey(0))
        x = _digits(seed=5)
        np.testing.assert_allclose(m1.apply(p, x), m2.apply(p, x),
                                   rtol=1e-6, atol=1e-6)


# one alternate value per DONNConfig field; None marks cosmetic fields that
# legitimately stay out of the numerics keys.  Adding a config field without
# extending this table fails the guard below — the stale-cache tripwire.
_GUARD_BASE = dict(n=48, depth=3, distance=0.05, det_size=6)
_FIELD_ALTERNATES = {
    "name": None,  # cosmetic: never reaches the compiled program
    "n": 32,
    "pixel_size": 40e-6,
    "wavelength": 633e-9,
    "distance": 0.07,
    "distances": (0.04, 0.05, 0.06, 0.07),
    "depth": 4,
    "approximation": "fresnel",
    "band_limit": False,
    "pad": True,
    "num_classes": 6,
    "det_size": 8,
    "detector_layout": "ring",
    "gamma": 0.9,
    "codesign": "qat",
    "device_levels": 64,
    "response_gamma": 1.2,
    "channels": 3,
    "segmentation": True,
    "skip_from": 1,
    "layer_norm": True,
    "layers": (LayerSpec(distance=0.05, size=32),) * 3,
    "use_pallas": None,  # retired: True is refused, so it keys nothing
    "engine": "eager",
    "input_size": 14,
    "scan_unroll": 2,
    "tf_dtype": "bfloat16",
    "remat": "layer",
}

# fields whose change must also re-key the *plan* (propagation numerics);
# the rest only affect the model/executable level (config_static_key)
_PLAN_FIELDS = (
    "n", "pixel_size", "wavelength", "distance", "distances", "depth",
    "approximation", "band_limit", "pad", "codesign", "device_levels",
    "response_gamma", "layers", "scan_unroll", "tf_dtype", "remat",
)


class TestCacheKeyGuard:
    def test_every_config_field_has_a_guard_entry(self):
        fields = {f.name for f in dataclasses.fields(DONNConfig)}
        missing = fields - set(_FIELD_ALTERNATES)
        assert not missing, (
            f"new DONNConfig field(s) {sorted(missing)} lack cache-key "
            "guard coverage: add an alternate value to _FIELD_ALTERNATES "
            "and make sure config_static_key/plan_cache_key see the field"
        )
        stale = set(_FIELD_ALTERNATES) - fields
        assert not stale, f"guard table has stale entries: {sorted(stale)}"

    @pytest.mark.parametrize("field", sorted(_FIELD_ALTERNATES))
    def test_field_reaches_config_static_key(self, field):
        alt = _FIELD_ALTERNATES[field]
        base = DONNConfig(**_GUARD_BASE)
        if alt is None:  # cosmetic: must NOT re-key (shared executables)
            assert (mmod.config_static_key(dataclasses.replace(base,
                                                               name="other"))
                    == mmod.config_static_key(base))
            return
        changed = dataclasses.replace(base, **{field: alt})
        assert mmod.config_static_key(changed) != mmod.config_static_key(
            base
        ), f"{field} does not reach config_static_key: stale-cache hazard"

    @pytest.mark.parametrize("field", _PLAN_FIELDS)
    def test_plan_affecting_field_reaches_plan_cache_key(self, field):
        base = DONNConfig(**_GUARD_BASE)
        if field in ("device_levels", "response_gamma"):
            # device knobs only reach the propagation numerics when a
            # codesign mode consumes them
            base = dataclasses.replace(base, codesign="qat")
        changed = dataclasses.replace(base,
                                      **{field: _FIELD_ALTERNATES[field]})
        assert pp.plan_cache_key(changed, 1.0) != pp.plan_cache_key(
            base, 1.0
        ), f"{field} does not reach plan_cache_key: stale-plan hazard"

    def test_gamma_argument_rekeys_plan(self):
        base = DONNConfig(**_GUARD_BASE)
        assert pp.plan_cache_key(base, 1.0) != pp.plan_cache_key(base, 0.9)


class TestPerLayerDevices:
    def test_presets(self):
        assert cd.slm().levels == 256
        assert cd.printed_mask().levels == 4
        assert cd.device_for_layer("none", 256) is None
        dev = cd.device_for_layer("qat", 4, 1.2)
        assert dev.levels == 4 and dev.response_gamma == 1.2

    def test_mixed_devices_quantize_to_their_own_levels(self):
        """Front layers quantize to 256 SLM levels, back layer to 4."""
        cfg = DONNConfig(**{**BASE, "layers": MIXED})
        m = build_model(cfg)
        devs = [l.device for l in m.layers]
        assert [d.levels for d in devs] == [256, 256, 4]
        phi = jnp.asarray(
            np.random.default_rng(0).uniform(0, 2 * np.pi, (16, 16)),
            jnp.float32,
        )
        q4 = cd.quantize_qat(phi, devs[2])
        assert len(np.unique(np.asarray(q4))) <= 4
