"""Compile-once emulation runtime: batched multi-candidate emulation,
model/plan/executable caches (the DSE verification hot path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DONNConfig,
    build_model,
    cached_apply,
    cached_model,
    clear_plan_cache,
    emulate_batch,
    plan_cache_stats,
)
from repro.core import models as mmod
from repro.core import propagation as pp
from repro.data import synth_digits, synth_rgb_scenes, synth_seg

BASE = dict(n=48, depth=3, det_size=6)
GEOS = [(36e-6, 532e-9, 0.30), (30e-6, 432e-9, 0.25), (40e-6, 632e-9, 0.35)]


def _cls_cfgs(**extra):
    return [
        DONNConfig(name=f"c{i}", pixel_size=ps, wavelength=wl, distance=D,
                   **{**BASE, **extra})
        for i, (ps, wl, D) in enumerate(GEOS)
    ]


def _digits(k=4, seed=0):
    xs, _ = synth_digits(k, seed=seed)
    return jnp.asarray(xs)


class TestEmulateBatch:
    def test_classify_matches_sequential(self):
        cfgs = _cls_cfgs()
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        x = _digits()
        seq = [build_model(c).apply(params, x) for c in cfgs]
        bat = emulate_batch(cfgs, params, x)
        assert bat.shape == (len(cfgs),) + seq[0].shape
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_per_candidate_params(self):
        cfgs = _cls_cfgs()
        m0 = build_model(cfgs[0])
        plist = [m0.init(jax.random.PRNGKey(k)) for k in range(len(cfgs))]
        x = _digits(seed=1)
        seq = [build_model(c).apply(p, x) for c, p in zip(cfgs, plist)]
        bat = emulate_batch(cfgs, plist, x)
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_rng_split_matches_sequential(self):
        cfgs = _cls_cfgs(codesign="gumbel", device_levels=16)
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        x = _digits(seed=2)
        rng = jax.random.PRNGKey(7)
        rngs = jax.random.split(rng, len(cfgs))
        seq = [build_model(c).apply(params, x, r) for c, r in zip(cfgs, rngs)]
        bat = emulate_batch(cfgs, params, x, rng=rng)
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_multichannel_matches_sequential(self):
        cfgs = [
            DONNConfig(name=f"m{i}", n=64, depth=3, det_size=6, channels=3,
                       num_classes=6, pixel_size=ps, distance=D)
            for i, (ps, D) in enumerate([(36e-6, 0.05), (30e-6, 0.04)])
        ]
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        xs, _ = synth_rgb_scenes(4, seed=0)
        x = jnp.asarray(xs)
        seq = [build_model(c).apply(params, x) for c in cfgs]
        bat = emulate_batch(cfgs, params, x)
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_segmentation_skip_train_matches_sequential(self):
        cfgs = [
            DONNConfig(name=f"s{i}", n=64, depth=3, segmentation=True,
                       skip_from=0, layer_norm=True, pixel_size=ps,
                       distance=D)
            for i, (ps, D) in enumerate([(36e-6, 0.05), (32e-6, 0.045)])
        ]
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(1))
        xs, _ = synth_seg(4, seed=0)
        x = jnp.asarray(xs)
        seq = [build_model(c).apply(params, x, train=True) for c in cfgs]
        bat = emulate_batch(cfgs, params, x, train=True)
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-4)

    def test_folded_matches_sequential(self, monkeypatch):
        # per-candidate TF planes as traced inputs through the folded hop
        monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
        monkeypatch.setattr(pp, "_folded_hop_applies", lambda n: True)
        mmod.clear_emulation_caches()
        cfgs = _cls_cfgs()
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        x = _digits(seed=3)
        seq = [build_model(c).apply(params, x) for c in cfgs]
        bat = emulate_batch(cfgs, params, x)
        mmod.clear_emulation_caches()
        for i, want in enumerate(seq):
            np.testing.assert_allclose(bat[i], want, rtol=1e-5, atol=1e-5)

    def test_statics_mismatch_raises(self):
        cfgs = _cls_cfgs()
        bad = dataclasses.replace(cfgs[1], num_classes=6)
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="statics"):
            emulate_batch([cfgs[0], bad], params, _digits())

    def test_mixed_depth_needs_per_candidate_params(self):
        # depth is a *geometry* axis now (depth-padded + masked stacks),
        # but a single shared params pytree cannot cover two depths
        cfgs = _cls_cfgs()
        deeper = dataclasses.replace(cfgs[1], depth=4)
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="per-candidate params"):
            emulate_batch([cfgs[0], deeper], params, _digits())

    def test_empty_and_param_count_checks(self):
        cfgs = _cls_cfgs()
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            emulate_batch([], params, _digits())
        with pytest.raises(ValueError):
            emulate_batch(cfgs, [params], _digits())

    def test_executable_reused_across_calls(self):
        clear_plan_cache()
        cfgs = _cls_cfgs()
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        x = _digits(seed=4)
        emulate_batch(cfgs, params, x)
        s0 = plan_cache_stats()
        emulate_batch(cfgs, params, x)
        s1 = plan_cache_stats()
        # second call: all plans and the compiled executable are hits
        assert s1["exec_misses"] == s0["exec_misses"]
        assert s1["exec_hits"] == s0["exec_hits"] + 1
        assert s1["misses"] == s0["misses"]

    def test_batched_inputs_memoized(self):
        mmod.clear_emulation_caches()
        cfgs = _cls_cfgs()
        params = build_model(cfgs[0]).init(jax.random.PRNGKey(0))
        x = _digits(seed=7)
        emulate_batch(cfgs, params, x)
        misses = mmod._BATCH_INPUT_STATS["misses"]
        emulate_batch(cfgs, params, x)  # warm: stacked inputs come from memo
        assert mmod._BATCH_INPUT_STATS["misses"] == misses
        assert mmod._BATCH_INPUT_STATS["hits"] >= 1
        emulate_batch(cfgs[:2], params, x)  # new candidate set: one rebuild
        assert mmod._BATCH_INPUT_STATS["misses"] == misses + 1


class TestCachedApply:
    def test_matches_model_apply(self):
        cfg = DONNConfig(name="ca", **BASE)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        x = _digits(seed=5)
        fn = cached_apply(cfg)
        np.testing.assert_allclose(
            fn(params, x), model.apply(params, x), rtol=1e-6, atol=1e-6
        )

    def test_compiles_once_per_shape(self):
        clear_plan_cache()
        cfg = DONNConfig(name="ca2", **BASE)
        params = cached_model(cfg).init(jax.random.PRNGKey(0))
        fn = cached_apply(cfg)
        fn(params, _digits(4, seed=0))
        s0 = plan_cache_stats()
        fn(params, _digits(4, seed=1))  # same shape: executable reused
        s1 = plan_cache_stats()
        assert s1["exec_misses"] == s0["exec_misses"]
        assert s1["exec_hits"] == s0["exec_hits"] + 1
        fn(params, _digits(8, seed=0))  # new shape: one more compile
        assert plan_cache_stats()["exec_misses"] == s0["exec_misses"] + 1

    def test_rng_variant(self):
        cfg = DONNConfig(name="ca3", codesign="qat", device_levels=32, **BASE)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        x = _digits(seed=6)
        rng = jax.random.PRNGKey(3)
        fn = cached_apply(cfg)
        np.testing.assert_allclose(
            fn(params, x, rng), model.apply(params, x, rng),
            rtol=1e-6, atol=1e-6,
        )


class TestCachedModel:
    def test_same_config_shares_instance(self):
        cfg = DONNConfig(name="cm", **BASE)
        assert cached_model(cfg) is cached_model(DONNConfig(name="cm", **BASE))

    def test_name_is_cosmetic(self):
        # a DSE sweep naming candidates uniquely still compiles once
        a = cached_model(DONNConfig(name="x1", **BASE))
        b = cached_model(DONNConfig(name="x2", **BASE))
        assert a is b

    def test_distinct_config_distinct_instance(self):
        a = cached_model(DONNConfig(name="cm2", **BASE))
        b = cached_model(DONNConfig(name="cm2", distance=0.31, **BASE))
        assert a is not b

    def test_explicit_laser_bypasses_cache(self):
        from repro.core import Laser

        cfg = DONNConfig(name="cm3", **BASE)
        a = cached_model(cfg, laser=Laser(wavelength=cfg.wavelength))
        assert a is not cached_model(cfg, laser=Laser(wavelength=cfg.wavelength))


class TestPlanSharing:
    def test_models_share_cached_plan(self):
        clear_plan_cache()
        cfg = DONNConfig(name="ps", **BASE)
        p1 = build_model(cfg).plan
        p2 = build_model(cfg).plan
        assert p1 is p2
        assert plan_cache_stats()["hits"] >= 1

    def test_config_statics_key_normalizes_distances(self):
        cfg_list = DONNConfig(name="k", distances=[0.1, 0.1, 0.1, 0.1], **BASE)
        cfg_tup = DONNConfig(name="k", distances=(0.1, 0.1, 0.1, 0.1), **BASE)
        assert (mmod.config_static_key(cfg_list)
                == mmod.config_static_key(cfg_tup))
        hash(mmod.config_static_key(cfg_list))  # must be hashable
