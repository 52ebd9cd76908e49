"""Deployment inference engine tests (ISSUE-5).

Pins the three serving invariants:
- **frozen bit-identity**: the frozen-plane fast path reproduces the
  training-path (codesign) forward bit-for-bit at eval, for every model
  family, codesign mode, kernel backend and heterogeneous stacks;
- **bucket-padding numerics**: padded rows of a micro-batch never perturb
  the real rows (per-sample agreement at rtol <= 1e-5; bit-exact here);
- **donation safety**: donated request buffers never alias a live caller
  array.

Multi-device dispatch runs in a subprocess with a forced 4-device host
platform (like tests/test_distributed.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import DONNConfig, build_model
from repro.core import propagation as pp
from repro.core.config import LayerSpec
from repro.data.pipeline import bucket_for, pad_batch
from repro.runtime.inference import (
    DeployedDONN, InferenceEngine, MicroBatcher, freeze,
)

RNG = np.random.default_rng(0)


def _digits(b, shape=(28, 28), seed=0):
    return np.random.default_rng(seed).random((b,) + shape, np.float32)


def _model(seed=0, **kw):
    kw.setdefault("n", 32)
    kw.setdefault("depth", 3)
    kw.setdefault("distance", 0.05)
    kw.setdefault("det_size", 6)
    cfg = DONNConfig(**kw)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


class TestFrozenBitIdentity:
    """frozen-plane inference == the codesign forward, bitwise."""

    @pytest.mark.parametrize("kw", [
        dict(name="fz-none"),
        dict(name="fz-qat", codesign="qat"),
        dict(name="fz-qat-nl", codesign="qat", response_gamma=1.2),
        dict(name="fz-gum", codesign="gumbel"),
        dict(name="fz-ptq", codesign="ptq", device_levels=16),
    ])
    def test_classify_modes(self, kw):
        model, params = _model(**kw)
        x = _digits(4)
        ref = np.asarray(jax.jit(lambda p, xx: model.apply(p, xx))(params, x))
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4,))
        np.testing.assert_array_equal(eng.infer(x), ref)

    def test_multi_channel(self):
        model, params = _model(name="fz-rgb", channels=3, det_size=4)
        x = _digits(3, shape=(3, 28, 28))
        ref = np.asarray(jax.jit(lambda p, xx: model.apply(p, xx))(params, x))
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4,))
        np.testing.assert_array_equal(eng.infer(x), ref)

    def test_segmentation_with_skip(self):
        model, params = _model(name="fz-seg", segmentation=True, skip_from=0,
                               layer_norm=True, codesign="qat")
        x = _digits(3)
        # eval reference: train=False (no layer norm) — the serving path
        ref = np.asarray(jax.jit(lambda p, xx: model.apply(p, xx))(params, x))
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4,))
        np.testing.assert_array_equal(eng.infer(x), ref)

    def test_heterogeneous_segmented_plan(self):
        model, params = _model(
            name="fz-het",
            layers=(LayerSpec(0.05, size=40), LayerSpec(0.05, size=40),
                    LayerSpec(0.05, codesign="qat", device_levels=4)),
        )
        x = _digits(2)
        ref = np.asarray(jax.jit(lambda p, xx: model.apply(p, xx))(params, x))
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(2,))
        np.testing.assert_array_equal(eng.infer(x), ref)

    def test_frozen_fast_path_skips_codesign(self):
        """forward(frozen=...) must not re-quantize the folded planes."""
        model, params = _model(name="fz-skipq", codesign="qat")
        plan = model.plan
        fz = plan.frozen_modulation(model.stacked_phases(params))
        u = model.encode(jnp.asarray(_digits(1)))
        out = plan.apply(None, u, frozen=fz)
        # reference: codesign applied exactly once, then a plain forward
        eff = plan._codesign_stack(model.stacked_phases(params), None)
        cfg_none = DONNConfig(**{**model.cfg.__dict__, "codesign": "none"})
        plain = pp.plan_from_config(cfg_none, model.gamma)
        want = plain.apply(eff, u)
        # the fold precomputes exp under jit while this eager reference
        # runs it op-by-op — agreement at the repo's standard tolerance
        # (the *jitted* end-to-end comparison above is bit-exact)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


class TestBucketPadding:
    def test_bucket_for(self):
        assert bucket_for(1, (1, 2, 4)) == 1
        assert bucket_for(3, (1, 2, 4)) == 4
        assert bucket_for(9, (1, 2, 4)) == 4  # over the top: largest bucket
        with pytest.raises(ValueError):
            bucket_for(0, (1, 2))

    def test_pad_batch_fresh_buffer(self):
        x = np.ones((2, 4, 4), np.float32)
        out = pad_batch(x, 4)
        assert out.shape == (4, 4, 4)
        assert np.all(out[2:] == 0.0) and np.all(out[:2] == 1.0)
        # fresh buffer even when already at bucket size (donation safety)
        same = pad_batch(x, 2)
        assert same is not x and not np.shares_memory(same, x)
        with pytest.raises(ValueError):
            pad_batch(x, 1)

    def test_padded_rows_match_per_sample_apply(self):
        """Every partially-filled bucket agrees with unbatched apply."""
        model, params = _model(name="bp", codesign="qat")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4, 8))
        apply1 = jax.jit(lambda p, xx: model.apply(p, xx))
        for b in (1, 3, 5, 8, 11):
            x = _digits(b, seed=b)
            got = eng.infer(x)
            ref = np.concatenate(
                [np.asarray(apply1(params, x[i:i + 1])) for i in range(b)]
            )
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)

    def test_micro_batcher_matches_direct_apply(self):
        model, params = _model(name="mb", codesign="qat")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(2, 8))
        eng.warmup()
        mb = MicroBatcher(eng, max_wait_ms=5.0)
        x = _digits(5, seed=7)
        futs = [mb.submit(x[i]) for i in range(5)]
        got = np.stack([f.result(timeout=60) for f in futs])
        mb.close()
        ref = np.asarray(
            jax.jit(lambda p, xx: model.apply(p, xx))(params, x)
        )
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)
        assert eng.stats["requests"] == 5

    def test_micro_batcher_rejects_malformed_at_submit(self):
        """Validation fails bad requests at the door, before batching."""
        model, params = _model(name="mbx")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(2,))
        mb = MicroBatcher(eng, max_wait_ms=50.0)
        with pytest.raises(ValueError):
            mb.submit(np.zeros((14, 14), np.float32))  # wrong image shape
        with pytest.raises(TypeError):
            mb.submit(np.array([["a"] * 28] * 28))  # non-numeric dtype
        good = mb.submit(_digits(1)[0])  # rejects never reach the worker
        out = good.result(timeout=60)
        mb.close()
        assert out.shape == (model.cfg.num_classes,)
        assert mb.stats["submitted"] == 1 and mb.stats["failed"] == 0

    def test_micro_batcher_bisects_poisoned_group(self):
        """With validation off, a poison request that breaks the whole
        group fails only its own future; neighbors still get results."""
        model, params = _model(name="mbp")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4,))
        mb = MicroBatcher(eng, max_wait_ms=150.0, validate=False)
        x = _digits(2, seed=8)
        # a 0-d scalar can't stack with images AND fails when served alone
        good1 = mb.submit(x[0])
        poison = mb.submit(np.float32(0.5))
        good2 = mb.submit(x[1])
        with pytest.raises(Exception):
            poison.result(timeout=60)
        ref = np.asarray(
            jax.jit(lambda p, xx: model.apply(p, xx))(params, x)
        )
        np.testing.assert_allclose(good1.result(timeout=60), ref[0],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(good2.result(timeout=60), ref[1],
                                   rtol=1e-5, atol=1e-7)
        mb.close()
        assert mb.stats["failed"] == 1 and mb.stats["served"] == 2

    def test_micro_batcher_deadline_flush(self):
        """Fewer requests than the largest bucket still get served."""
        model, params = _model(name="mbd")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(32,))
        mb = MicroBatcher(eng, max_wait_ms=1.0)
        fut = mb.submit(_digits(1)[0])
        out = fut.result(timeout=60)
        mb.close()
        assert out.shape == (model.cfg.num_classes,)
        assert eng.stats["padded_rows"] == 31


class TestDonationSafety:
    def test_donation_never_aliases_live_request_buffers(self):
        """Caller arrays survive a donated inference, even at exact bucket
        size, and repeated calls with the same array work."""
        model, params = _model(name="dn")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(4,), donate=True)
        x_host = _digits(4, seed=3)
        x_dev = jnp.asarray(x_host)  # a live, caller-owned device buffer
        out1 = eng.infer(x_dev)
        # the caller's buffer must still be readable and unchanged
        np.testing.assert_array_equal(np.asarray(x_dev), x_host)
        out2 = eng.infer(x_dev)
        np.testing.assert_array_equal(out1, out2)

    def test_donate_matches_nondonate(self):
        model, params = _model(name="dn2", codesign="qat")
        dep = freeze(model, params)
        x = _digits(4, seed=4)
        a = InferenceEngine(dep, buckets=(4,), donate=True).infer(x)
        b = InferenceEngine(dep, buckets=(4,), donate=False).infer(x)
        np.testing.assert_array_equal(a, b)


class TestWarmupAndCaching:
    def test_warmup_pays_all_compiles(self):
        """After warmup, serving adds no new executable-cache misses."""
        model, params = _model(name="wu")
        dep = freeze(model, params)
        eng = InferenceEngine(dep, buckets=(2, 4))
        eng.warmup()
        misses = pp.plan_cache_stats()["exec_misses"]
        eng.infer(_digits(2))
        eng.infer(_digits(4))
        eng.infer(_digits(3))  # pads into the 4-bucket
        assert pp.plan_cache_stats()["exec_misses"] == misses

    def test_same_arch_shares_executables_across_params(self):
        """Frozen planes are traced inputs: two deployments of one
        architecture share one compiled program per bucket."""
        model, p1 = _model(name="sh1", codesign="qat", seed=1)
        _, p2 = _model(name="sh2", codesign="qat", seed=2)
        e1 = InferenceEngine(freeze(model, p1), buckets=(2,))
        e1.warmup()
        misses = pp.plan_cache_stats()["exec_misses"]
        e2 = InferenceEngine(freeze(model, p2), buckets=(2,))
        e2.warmup()
        assert pp.plan_cache_stats()["exec_misses"] == misses
        x = _digits(2, seed=9)
        r1, r2 = e1.infer(x), e2.infer(x)
        assert not np.allclose(r1, r2)  # different params, different outputs


class TestMultiDevice:
    def test_dp_dispatch_matches_single_device(self):
        code = """
import jax, numpy as np
from repro.core import DONNConfig, build_model
from repro.runtime.inference import freeze, InferenceEngine

assert jax.device_count() == 4
cfg = DONNConfig(name="dp", n=32, depth=3, distance=0.05, det_size=6,
                 codesign="qat")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
dep = freeze(model, params)
x = np.random.default_rng(0).random((8, 28, 28), np.float32)
ref = InferenceEngine(dep, buckets=(8,)).infer(x)
got = InferenceEngine(dep, buckets=(8,), mesh_devices=4,
                      dp_min_bucket=4).infer(x)
rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
assert rel <= 1e-5, rel
# small buckets stay single-device (below dp_min_bucket)
e = InferenceEngine(dep, buckets=(2, 8), mesh_devices=4, dp_min_bucket=8)
small = e.infer(x[:2])
np.testing.assert_allclose(small, ref[:2], rtol=1e-5, atol=1e-7)
print("DP_OK", rel)
"""
        r = run_subprocess(code, device_count=4)
        assert r.returncode == 0, r.stderr
        assert "DP_OK" in r.stdout


class TestFreezeValidation:
    def test_freeze_rejects_non_models(self):
        with pytest.raises(TypeError):
            freeze(object(), {})

    def test_static_key_drops_name(self):
        model, params = _model(name="a-name")
        model2, _ = _model(name="b-name")
        assert (freeze(model, params).static_key()
                == freeze(model2, params).static_key())

    def test_engine_validates_buckets_and_devices(self):
        model, params = _model(name="val")
        dep = freeze(model, params)
        with pytest.raises(ValueError):
            InferenceEngine(dep, buckets=())
        with pytest.raises(ValueError):
            InferenceEngine(dep, buckets=(0, 2))
        with pytest.raises(ValueError):
            InferenceEngine(dep, mesh_devices=jax.device_count() + 1)
        assert isinstance(dep, DeployedDONN)
