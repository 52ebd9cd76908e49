"""Training-throughput engine: chunked drivers, remat, prefetch, caching.

The contract under test (ISSUE 4): the donated multi-step scanned drivers
are *numerically identical* to the seed-style per-step loop (same rng
chain, same optimizer trajectory), ``DONNConfig.remat`` changes memory
behavior but not values, the device prefetcher preserves stream order,
and training programs stop re-tracing across model rebuilds.
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DONNConfig, LayerSpec, build_model
from repro.core import propagation as pp
from repro.core.train_utils import (
    make_train_chunk, make_train_step, optimizer_cache_key, train_classifier,
)
from repro.data import batch_iterator, synth_digits
from repro.data.pipeline import device_prefetch, stack_batches
from repro.optim import AdamW

TINY = dict(n=48, depth=3, distance=0.05, det_size=6)


def _params_close(a, b, rtol=1e-5, atol=1e-7):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


class TestChunkedClassifier:
    def _run(self, cfg, steps, steps_per_call, needs_rng=False, **kw):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        xs, ys = synth_digits(256, seed=0)
        res = train_classifier(
            model, params, batch_iterator(xs, ys, 8, seed=1), steps=steps,
            lr=0.3, needs_rng=needs_rng, rng=jax.random.PRNGKey(3),
            steps_per_call=steps_per_call, **kw,
        )
        return res

    def test_chunked_matches_per_step(self):
        cfg = DONNConfig(name="tc", **TINY)
        ref = self._run(cfg, steps=10, steps_per_call=1)
        got = self._run(cfg, steps=10, steps_per_call=5)
        assert np.allclose(ref.losses, got.losses, rtol=1e-6, atol=1e-8)
        assert np.allclose(ref.accs, got.accs)
        _params_close(got.params, ref.params)

    def test_partial_final_chunk(self):
        cfg = DONNConfig(name="tp", **TINY)
        ref = self._run(cfg, steps=7, steps_per_call=1)
        got = self._run(cfg, steps=7, steps_per_call=4)  # 4 + 3 remainder
        assert len(got.losses) == 7
        assert np.allclose(ref.losses, got.losses, rtol=1e-6, atol=1e-8)
        _params_close(got.params, ref.params)

    def test_rng_codesign_chain_aligned(self):
        cfg = DONNConfig(name="tg", **TINY, codesign="gumbel")
        ref = self._run(cfg, steps=6, steps_per_call=1, needs_rng=True)
        got = self._run(cfg, steps=6, steps_per_call=3, needs_rng=True)
        assert np.allclose(ref.losses, got.losses, rtol=1e-6, atol=1e-8)
        _params_close(got.params, ref.params)

    def test_no_prefetch_same_result(self):
        cfg = DONNConfig(name="tn", **TINY)
        a = self._run(cfg, steps=6, steps_per_call=3, prefetch=0)
        b = self._run(cfg, steps=6, steps_per_call=3, prefetch=2)
        assert np.allclose(a.losses, b.losses)
        _params_close(a.params, b.params)

    def test_caller_params_survive_donation(self):
        cfg = DONNConfig(name="td", **TINY)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        xs, ys = synth_digits(128, seed=0)
        train_classifier(model, params, batch_iterator(xs, ys, 8, seed=1),
                         steps=4, steps_per_call=2)
        # the chunk driver donates its state; the caller's tree must stay
        # readable (train_classifier copies before donating)
        assert bool(jnp.all(jnp.isfinite(
            jax.tree.leaves(params)[0].astype(jnp.float32))))


class TestDonnStepsChunk:
    def test_segmentation_chunk_matches_sequential(self):
        from repro.launch.mesh import make_mesh
        from repro.nn import init_params
        from repro.runtime import donn_steps as ds

        cfg = DONNConfig(name="sc", n=48, depth=3, distance=0.05,
                         segmentation=True, skip_from=0, layer_norm=True)
        opt = AdamW(lr=0.05)
        r = np.random.default_rng(0)
        batches = [
            {"images": r.uniform(0, 1, (4, 28, 28)).astype(np.float32),
             "masks": (r.uniform(0, 1, (4, 48, 48)) > 0.5).astype(
                 np.float32)}
            for _ in range(4)
        ]
        sspecs = ds.donn_state_specs(cfg)
        st1 = init_params(sspecs, jax.random.PRNGKey(0))
        step = jax.jit(ds.make_donn_train_step(cfg, opt))
        ref_losses = []
        for b in batches:
            st1, m = step(st1, b)
            ref_losses.append(float(m["loss"]))

        mesh = make_mesh((1,), ("data",))
        fn, s_sh, b_sh, _ = ds.compile_donn_train_chunk(cfg, mesh,
                                                        optimizer=opt)
        st2 = jax.device_put(init_params(sspecs, jax.random.PRNGKey(0)),
                             s_sh)
        losses = []
        for chunk in stack_batches(iter(batches), 2):
            st2, m = fn(st2, chunk)
            losses.extend(np.asarray(m["loss"]).tolist())
        assert np.allclose(ref_losses, losses, rtol=1e-6, atol=1e-8)
        _params_close(st2["params"], st1["params"])


class TestRemat:
    def test_layer_remat_values_and_grads_match(self):
        cfg0 = DONNConfig(name="r0", **TINY)
        cfgr = dataclasses.replace(cfg0, name="r1", remat="layer")
        m0, mr = build_model(cfg0), build_model(cfgr)
        p = m0.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(4, seed=2)
        x = jnp.asarray(xs)
        np.testing.assert_allclose(m0.apply(p, x), mr.apply(p, x),
                                   rtol=1e-6, atol=1e-7)
        loss = lambda m: (lambda q: jnp.sum(m.apply(q, x)))
        g0 = jax.grad(loss(m0))(p)
        gr = jax.grad(loss(mr))(p)
        _params_close(gr, g0, rtol=1e-6)

    def test_layer_remat_reaches_backward_jaxpr(self):
        cfgr = DONNConfig(name="rj", **TINY, remat="layer")
        m = build_model(cfgr)
        p = m.init(jax.random.PRNGKey(0))
        x = jnp.zeros((2, 28, 28), jnp.float32)
        jx = str(jax.make_jaxpr(
            jax.grad(lambda q: jnp.sum(m.apply(q, x)))
        )(p))
        assert "remat" in jx or "checkpoint" in jx

    def test_segment_remat_heterogeneous(self):
        layers = (
            LayerSpec(distance=0.05, size=48),
            LayerSpec(distance=0.05, size=48),
            LayerSpec(distance=0.05, size=32, pixel_size=54e-6),
        )
        base = DONNConfig(name="rh", n=48, depth=3, distance=0.05,
                          det_size=6, layers=layers)
        cfgr = dataclasses.replace(base, remat="segment")
        m0, mr = build_model(base), build_model(cfgr)
        p = m0.init(jax.random.PRNGKey(0))
        xs, _ = synth_digits(2, seed=3)
        x = jnp.asarray(xs)
        g0 = jax.grad(lambda q: jnp.sum(m0.apply(q, x)))(p)
        gr = jax.grad(lambda q: jnp.sum(mr.apply(q, x)))(p)
        _params_close(gr, g0, rtol=1e-6)

    def test_invalid_remat_rejected(self):
        with pytest.raises(ValueError, match="remat"):
            DONNConfig(name="bad", remat="everything")

    def test_remat_survives_spec_round_trip(self):
        import repro.core.dsl as lr
        from repro.core.models import config_static_key

        cfg = DONNConfig(name="rt", **TINY, remat="layer")
        _, cfg2 = lr.from_spec(lr.to_spec(cfg))
        assert cfg2.remat == "layer"
        assert config_static_key(cfg2) == config_static_key(cfg)
        assert pp.plan_cache_key(cfg2, 1.0) == pp.plan_cache_key(cfg, 1.0)


class TestPipelineHelpers:
    def test_stack_batches_shapes_and_total(self):
        it = iter([(np.full((2, 3), i, np.float32), np.full((2,), i))
                   for i in range(10)])
        chunks = list(stack_batches(it, 4, total=9))
        assert [c[0].shape[0] for c in chunks] == [4, 4, 1]
        assert chunks[0][0].shape == (4, 2, 3)
        # order preserved: chunk 1 carries batches 4..7
        assert np.all(chunks[1][1][0] == 4)

    def test_device_prefetch_preserves_order(self):
        batches = [{"x": np.full((2,), i, np.float32)} for i in range(5)]
        out = list(device_prefetch(iter(batches), size=2))
        assert len(out) == 5
        for i, b in enumerate(out):
            assert isinstance(b["x"], jax.Array)
            assert float(b["x"][0]) == i

    def test_device_prefetch_rejects_bad_size(self):
        with pytest.raises(ValueError):
            list(device_prefetch(iter([]), size=0))


class TestExecutableReuse:
    def test_train_step_shared_across_model_rebuilds(self):
        cfg = DONNConfig(name="xr", **TINY)
        xs, ys = synth_digits(16, seed=0)
        xb, yb = jnp.asarray(xs[:8]), jnp.asarray(ys[:8])
        opt = AdamW(lr=0.1)

        def one_run():
            model = build_model(cfg)  # fresh model object each run
            params = model.init(jax.random.PRNGKey(0))
            step = make_train_step(model, opt, 10)
            s = opt.init(params)
            step(params, s, jnp.asarray(0), xb, yb, jax.random.PRNGKey(0))

        one_run()
        before = pp.plan_cache_stats()
        one_run()
        after = pp.plan_cache_stats()
        assert after["exec_hits"] > before["exec_hits"]
        assert after["exec_misses"] == before["exec_misses"]

    def test_chunk_driver_uses_executable_cache(self):
        cfg = DONNConfig(name="xc", **TINY)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=0.1)
        xs = jnp.zeros((2, 4, 28, 28), jnp.float32)
        ys = jnp.zeros((2, 4), jnp.int32)
        before = pp.plan_cache_stats()
        chunk = make_train_chunk(model, opt, 10)
        p, s, rng, *_ = chunk(params, opt.init(params), 0, xs, ys,
                              jax.random.PRNGKey(0))
        chunk2 = make_train_chunk(build_model(cfg), opt, 10)
        chunk2(p, s, 2, xs, ys, rng)
        after = pp.plan_cache_stats()
        assert after["exec_misses"] == before["exec_misses"] + 1
        assert after["exec_hits"] > before["exec_hits"]

    def test_unkeyable_optimizer_falls_back(self):
        assert optimizer_cache_key(AdamW(lr=0.1)) is not None
        assert optimizer_cache_key(AdamW(lr=lambda s: 0.1)) is None
        # schedule-driven optimizer still trains (plain jit path)
        cfg = DONNConfig(name="xs", **TINY)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=lambda s: 0.1)
        chunk = make_train_chunk(model, opt, 10)
        xs = jnp.zeros((2, 4, 28, 28), jnp.float32)
        ys = jnp.zeros((2, 4), jnp.int32)
        p, *_ = chunk(params, opt.init(params), 0, xs, ys,
                      jax.random.PRNGKey(0))
        assert bool(jnp.all(jnp.isfinite(jax.tree.leaves(p)[0])))


class TestSpatialGates:
    """Unsupported configs must be rejected loudly (single-device mesh)."""

    def _mesh(self):
        from repro.runtime.sharding import make_mesh_2d

        return make_mesh_2d(model=1)

    @pytest.mark.parametrize("kw", [
        dict(pad=True),
        dict(approximation="fraunhofer"),
        dict(codesign="gumbel"),
        dict(tf_dtype="bfloat16"),
    ])
    def test_unsupported_config_raises(self, kw):
        from repro.runtime.donn_steps import make_donn_spatial_loss

        cfg = DONNConfig(name="g", n=48, depth=3, distance=0.05, **kw)
        with pytest.raises(NotImplementedError):
            make_donn_spatial_loss(cfg, self._mesh())

    @pytest.mark.parametrize("kw", [
        dict(segmentation=True, skip_from=0),
        dict(channels=3),
        dict(layers=(LayerSpec(distance=0.05, size=32),) * 3),
    ])
    def test_formerly_gated_families_now_build(self, kw):
        # seg-with-skip, RGB and hetero SegmentedPlan moved off the
        # reject list when the rules-table loss took over (ISSUE 10)
        from repro.runtime.donn_steps import make_donn_sharded_loss

        cfg = DONNConfig(name="g3", n=48, depth=3, distance=0.05, **kw)
        assert callable(make_donn_sharded_loss(cfg, self._mesh()))

    def test_indivisible_rows_raise(self):
        import jax as _jax

        if len(_jax.devices()) != 1:
            pytest.skip("single-device gate test")
        # n % k check needs k > 1; emulate via a fake mesh shape
        from repro.runtime.donn_steps import make_donn_spatial_loss

        class FakeMesh:
            shape = {"model": 5}

        cfg = DONNConfig(name="g2", n=48, depth=2, distance=0.05)
        with pytest.raises(ValueError, match="divide"):
            make_donn_spatial_loss(cfg, FakeMesh())


class TestBenchRollupCheck:
    def _run_mod(self):
        path = (pathlib.Path(__file__).resolve().parent.parent
                / "benchmarks" / "run.py")
        spec = importlib.util.spec_from_file_location("bench_run", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_stale_tier1_flags_stale_and_missing(self):
        mod = self._run_mod()
        fresh = {s: {"stale": False} for s in mod.TIER1_SUITES}
        assert mod.stale_tier1(fresh) == []
        fresh["hetero"]["stale"] = True
        del fresh["dse_batched"]
        assert mod.stale_tier1(fresh) == ["dse_batched", "hetero"]

    def test_committed_summary_has_fresh_tier1(self):
        mod = self._run_mod()
        root = pathlib.Path(__file__).resolve().parent.parent
        summary = (root / "BENCH_summary.json")
        if not summary.exists():
            pytest.skip("no committed summary")
        import json

        assert mod.stale_tier1(json.loads(summary.read_text())) == []
