"""End-to-end DONN behaviour: training works, advanced archs, DSL, codesign."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.dsl as lr
from repro.core import DONNConfig, build_model
from repro.core import codesign as cd
from repro.core.baselines import LightPipesLikeEngine
from repro.core.diffraction import Grid
from repro.core.regularization import calibrate_gamma
from repro.core.train_utils import evaluate_classifier, train_classifier
from repro.data import batch_iterator, synth_digits, synth_rgb_scenes, synth_seg

TINY = dict(n=64, depth=2, distance=0.05, det_size=8)


class TestDONNTraining:
    def test_training_improves_accuracy(self):
        cfg = DONNConfig(name="t", **TINY)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        xs, ys = synth_digits(512, seed=0)
        it = batch_iterator(xs, ys, 64, seed=1)
        acc0 = evaluate_classifier(model, params, batch_iterator(xs, ys, 64), 4)
        res = train_classifier(model, params, it, steps=60, lr=0.3)
        acc1 = evaluate_classifier(model, res.params,
                                   batch_iterator(xs, ys, 64), 4)
        assert acc1 > acc0 + 0.15, f"{acc0} -> {acc1}"

    def test_gamma_calibration_hits_target_scale(self):
        """gamma rebalances detector-logit scale (inverse softmax temp)."""
        xs, _ = synth_digits(8, seed=3)
        base = build_model(DONNConfig(name="b", n=64, depth=5, distance=0.05,
                                      det_size=8))
        p = base.init(jax.random.PRNGKey(0))
        g = calibrate_gamma(base, p, jnp.asarray(xs), target_logit=2.0)
        reg = build_model(DONNConfig(name="r", n=64, depth=5, distance=0.05,
                                     det_size=8, gamma=g))
        m = float(jnp.mean(reg.apply(p, jnp.asarray(xs))))
        assert abs(m - 2.0) < 0.2

    def test_gamma_regularization_improves_shallow_accuracy(self):
        """Paper Fig 7: the D=1 DONN gains large accuracy from gamma."""
        xs, ys = synth_digits(512, seed=0)
        cfg = DONNConfig(name="g1", n=64, depth=1, distance=0.05, det_size=8)
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        g = calibrate_gamma(m, p, jnp.asarray(xs[:16]))
        m2 = build_model(dataclasses.replace(cfg, gamma=g))
        accs = {}
        for name, mm in (("base", m), ("gamma", m2)):
            res = train_classifier(mm, p, batch_iterator(xs, ys, 64, seed=1),
                                   steps=50, lr=0.5)
            accs[name] = evaluate_classifier(
                mm, res.params, batch_iterator(xs, ys, 64, seed=2), 4)
        assert accs["gamma"] > accs["base"] + 0.15, accs

    def test_prop_view_intermediate_fields(self):
        cfg = DONNConfig(name="t", **TINY)
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(2, seed=4)
        views = m.prop_view(p, jnp.asarray(xs))
        assert len(views) == cfg.depth + 2  # encode + per-layer + detector
        assert all(v.shape[-2:] == (64, 64) for v in views)


class TestAdvancedArchitectures:
    def test_multichannel_rgb_forward_and_train(self):
        cfg = DONNConfig(name="rgb", n=64, depth=2, distance=0.05, det_size=8,
                         channels=3, num_classes=6)
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        xs, ys = synth_rgb_scenes(96, seed=0)
        g = calibrate_gamma(m, p, jnp.asarray(xs[:8]))
        m = build_model(dataclasses.replace(cfg, gamma=g))
        it = batch_iterator(xs, ys, 16, seed=1)
        res = train_classifier(m, p, it, steps=30, lr=0.3, num_classes=6)
        assert res.losses[-1] < 0.5 * res.losses[0]

    def test_segmentation_with_skip(self):
        cfg = DONNConfig(name="seg", n=64, depth=3, distance=0.05,
                         segmentation=True, skip_from=0, layer_norm=True)
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        xs, ms = synth_seg(8, seed=0)
        out = m.apply(p, jnp.asarray(xs), train=True)
        assert out.shape == (8, 64, 64)
        assert bool(jnp.all(jnp.isfinite(out)))
        # skip connection adds a second optical path
        assert m.skip_hop is not None

    def test_segmentation_trains(self):
        from repro.core.train_utils import bce_segmentation_loss
        from repro.optim import AdamW

        cfg = DONNConfig(name="seg", n=64, depth=2, distance=0.05,
                         segmentation=True, skip_from=0, layer_norm=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        xs, msk = synth_seg(64, seed=1)
        opt = AdamW(lr=0.05)
        state = opt.init(params)

        @jax.jit
        def step(params, state, i, xb, mb):
            def loss(p):
                return bce_segmentation_loss(m.apply(p, xb, train=True), mb)
            l, g = jax.value_and_grad(loss)(params)
            params, state = opt.update(g, state, params, i)
            return params, state, l

        losses = []
        for i in range(25):
            s = (i * 16) % 48
            params, state, l = step(params, state, jnp.asarray(i),
                                    jnp.asarray(xs[s:s+16]),
                                    jnp.asarray(msk[s:s+16]))
            losses.append(float(l))
        assert losses[-1] < losses[0]


class TestDSL:
    def test_sequential_builds_paper_system(self):
        src = lr.laser(wavelength=532e-9)
        layers = [lr.layers.diffractlayer(distance=0.05, pixel_size=36e-6,
                                          size=64, precision=256)
                  for _ in range(3)]
        det = lr.layers.detector(num_classes=10, det_size=8, distance=0.05)
        model, cfg = lr.models.sequential(layers, det, laser=src)
        assert cfg.depth == 3 and cfg.codesign == "qat"
        p = model.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(2, seed=0)
        assert model.apply(p, jnp.asarray(xs)).shape == (2, 10)

    def test_from_spec_json_roundtrip(self):
        spec = {
            "name": "donn-json",
            "laser": {"wavelength": 532e-9},
            "layers": [{"distance": 0.05, "pixel_size": 36e-6, "size": 64}] * 2,
            "detector": {"num_classes": 10, "det_size": 8, "distance": 0.05},
        }
        model, cfg = lr.from_spec(spec)
        assert cfg.name == "donn-json" and cfg.depth == 2

    def test_heterogeneous_distances(self):
        layers = [lr.layers.diffractlayer_raw(distance=d, size=64)
                  for d in (0.04, 0.06)]
        det = lr.layers.detector(det_size=8, distance=0.08)
        model, cfg = lr.models.sequential(layers, det)
        assert cfg.gap_distances() == (0.04, 0.06, 0.08)


class TestCodesign:
    def test_qat_quantizes_to_device_levels(self):
        dev = cd.DeviceSpec(levels=16)
        phi = jnp.asarray(np.random.default_rng(0).uniform(0, 6.28, (32, 32)),
                          jnp.float32)
        q = cd.quantize_qat(phi, dev)
        levels = dev.level_phases()
        d = np.abs(np.asarray(q)[..., None] - levels)
        assert float(d.min(-1).max()) < 1e-5

    def test_qat_straight_through_gradient(self):
        dev = cd.DeviceSpec(levels=16)
        phi = jnp.asarray([1.0, 2.0, 3.0])
        g = jax.grad(lambda p: jnp.sum(cd.quantize_qat(p, dev) ** 2))(phi)
        assert bool(jnp.all(jnp.abs(g) > 0))  # STE passes gradients

    def test_gumbel_hard_matches_ptq_at_low_tau(self):
        dev = cd.DeviceSpec(levels=8)
        phi = jnp.asarray(np.random.default_rng(1).uniform(0, 6.28, (16,)),
                          jnp.float32)
        hard = cd.quantize_gumbel(phi, dev, rng=None, tau=0.01, hard=True)
        _, ptq = cd.weight_fab(phi, dev)
        np.testing.assert_allclose(hard, ptq, atol=1e-5)

    def test_nonlinear_response_curve(self):
        dev = cd.DeviceSpec(levels=256, response_gamma=1.2)
        lv = dev.level_phases()
        assert np.all(np.diff(lv) >= 0) and lv[-1] <= 2 * np.pi + 1e-6
        mid = lv[128] / lv[-1]
        assert mid < 0.5  # gamma>1 bends the curve below linear

    def test_weight_fab_export(self):
        dev = cd.DeviceSpec(levels=256)
        phi = jnp.asarray(np.random.default_rng(2).uniform(0, 6.28, (8, 8)),
                          jnp.float32)
        img = cd.to_slm(phi, dev)
        assert img.dtype == np.uint8 and img.shape == (8, 8)
        thick = cd.to_3d_render(phi, 532e-9)
        assert thick.max() <= 532e-9 / 0.52 + 1e-9

    def test_quantized_model_trains(self):
        cfg = DONNConfig(name="q", **TINY, codesign="qat", device_levels=64)
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        xs, ys = synth_digits(256, seed=5)
        res = train_classifier(m, p, batch_iterator(xs, ys, 32), steps=30,
                               lr=0.3)
        assert res.losses[-1] < res.losses[0]


class TestBaselineEngine:
    def test_lightpipes_like_matches_physics(self):
        """The deliberately-slow baseline must still be *correct*."""
        g = Grid(48, 36e-6)
        eng = LightPipesLikeEngine(g, 532e-9)
        r = np.random.default_rng(0)
        u = (r.normal(size=(2, 48, 48)) + 1j * r.normal(size=(2, 48, 48)))
        from repro.core.diffraction import propagate

        ours = np.asarray(propagate(jnp.asarray(u, jnp.complex64), g, 0.02,
                                    532e-9, "rs", band_limit=False))
        theirs = eng.propagate_batch(u, 0.02)
        np.testing.assert_allclose(ours, theirs.astype(np.complex64),
                                   rtol=5e-3, atol=5e-3)
