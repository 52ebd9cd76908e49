"""Fault-tolerance suite (ISSUE-7): the failure drills, end to end.

Driven by the injectors in ``repro.testing.faults``, this pins the
resilience contracts:

- **artifact sufficiency**: a killed engine recovered from a serialized
  artifact (``save_deployed``/``load_deployed``) serves bit-identically
  to the original ``freeze()`` — for every model family, including
  heterogeneous segmented plans; corrupted artifacts (bit-rot or falsified
  checksums) are rejected at load, never served;
- **overload behavior**: a full admission queue sheds with
  ``OverloadedError``; an expired deadline fails only its own future
  while the rest of the traffic is served; an unclean shutdown fails the
  stranded futures instead of abandoning their callers;
- **training guardrails**: a poisoned (NaN) batch is skipped device-side
  as an exact no-op — final params bit-identical to a run that never saw
  the batch — and a fully-poisoned chunk rolls back to the last good
  checkpoint with the same guarantee.
"""
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import DONNConfig, build_model
from repro.core.config import LayerSpec
from repro.core.train_utils import train_classifier
from repro.data import batch_iterator, synth_digits
from repro.runtime.inference import InferenceEngine, MicroBatcher, freeze
from repro.runtime.resilience import (
    ARTIFACT_FILE, PLANES_DIR, DeadlineExceededError, EngineSupervisor,
    OverloadedError, load_deployed, save_deployed,
)
from repro.testing import (
    FlakyEngine, SlowEngine, corrupt_chunk, flip_crc, perturb_frozen,
    poison_batches,
)


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


# --------------------------------------------------------------------------
# Serialized frozen artifacts
# --------------------------------------------------------------------------
class TestArtifactRoundTrip:
    @pytest.mark.parametrize("kw", [
        dict(name="ar-qat", codesign="qat"),
    ])
    def test_save_load_bit_identical(self, tmp_path, kw):
        model, params = _model(**kw)
        dep = freeze(model, params)
        x = _digits(2)
        ref = InferenceEngine(dep, buckets=(2,)).infer(x)
        save_deployed(dep, tmp_path)
        dep2 = load_deployed(tmp_path)
        assert dep2.family == dep.family
        np.testing.assert_array_equal(
            InferenceEngine(dep2, buckets=(2,)).infer(x), ref
        )

    def test_heterogeneous_roundtrip(self, tmp_path):
        model, params = _model(
            name="ar-het",
            layers=(LayerSpec(0.05, size=40), LayerSpec(0.05, size=40),
                    LayerSpec(0.05, codesign="qat", device_levels=4)),
        )
        dep = freeze(model, params)
        x = _digits(2)
        ref = InferenceEngine(dep, buckets=(2,)).infer(x)
        save_deployed(dep, tmp_path)
        dep2 = load_deployed(tmp_path)
        assert dep2.heterogeneous and len(dep2.frozen) == len(dep.frozen)
        np.testing.assert_array_equal(
            InferenceEngine(dep2, buckets=(2,)).infer(x), ref
        )

    def test_multi_channel_roundtrip(self, tmp_path):
        model, params = _model(name="ar-rgb", channels=3, det_size=4)
        dep = freeze(model, params)
        x = _digits(2, shape=(3, 28, 28))
        ref = InferenceEngine(dep, buckets=(2,)).infer(x)
        save_deployed(dep, tmp_path)
        np.testing.assert_array_equal(
            InferenceEngine(load_deployed(tmp_path), buckets=(2,)).infer(x),
            ref,
        )

    def test_corrupt_chunk_rejected_at_load(self, tmp_path):
        model, params = _model(name="ar-rot")
        save_deployed(freeze(model, params), tmp_path)
        corrupt_chunk(tmp_path / PLANES_DIR, 0)
        with pytest.raises(IOError):
            load_deployed(tmp_path)

    def test_flipped_crc_rejected_at_load(self, tmp_path):
        model, params = _model(name="ar-crc")
        save_deployed(freeze(model, params), tmp_path)
        flip_crc(tmp_path / PLANES_DIR, 0)
        with pytest.raises(IOError):
            load_deployed(tmp_path)

    def test_missing_and_foreign_artifacts_rejected(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_deployed(tmp_path / "nope")
        model, params = _model(name="ar-fmt")
        save_deployed(freeze(model, params), tmp_path)
        meta_path = tmp_path / ARTIFACT_FILE
        meta = json.loads(meta_path.read_text())
        meta["format"] = 99
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ValueError):
            load_deployed(tmp_path)


# --------------------------------------------------------------------------
# Engine supervision
# --------------------------------------------------------------------------
class TestSupervisor:
    def test_killed_engine_recovers_bit_identical(self, tmp_path):
        """Kill the engine; the supervisor must restart it from the
        artifact and serve the retried request identically to freeze()."""
        model, params = _model(name="sup", codesign="qat")
        dep = freeze(model, params)
        x = _digits(2)
        ref = InferenceEngine(dep, buckets=(2,)).infer(x)
        save_deployed(dep, tmp_path)

        current = {}

        def factory(deployed):
            current["engine"] = FlakyEngine(
                InferenceEngine(deployed, buckets=(2,))
            )
            return current["engine"]

        sup = EngineSupervisor(tmp_path, engine_factory=factory,
                               max_restarts=2).start()
        assert sup.ready and sup.health_check()
        np.testing.assert_array_equal(sup.infer(x), ref)
        current["engine"].kill()
        assert not sup.health_check()
        # the failed request restarts from disk and is retried once
        np.testing.assert_array_equal(sup.infer(x), ref)
        s = sup.stats()
        assert s["restarts"] == 1 and s["ready"]
        assert s["errors"] >= 1 and 0 < s["error_rate"] < 1

    def test_restart_budget_exhausted(self, tmp_path):
        model, params = _model(name="sup-b")
        save_deployed(freeze(model, params), tmp_path)

        def factory(deployed):
            eng = FlakyEngine(InferenceEngine(deployed, buckets=(1,)))
            eng.kill()  # every replacement is born dead
            return eng

        sup = EngineSupervisor(tmp_path, engine_factory=factory,
                               max_restarts=0).start()
        with pytest.raises(RuntimeError):
            sup.infer(_digits(1)[0])
        assert not sup.ready


# --------------------------------------------------------------------------
# Hardened micro-batching
# --------------------------------------------------------------------------
def _slow_batcher(delay_s: float, **kw):
    model, params = _model(name="mb-slow", depth=2)
    eng = InferenceEngine(freeze(model, params), buckets=(1,))
    eng.warmup()
    return MicroBatcher(SlowEngine(eng, delay_s), **kw), model


class TestMicroBatcherResilience:
    def test_overload_sheds(self):
        mb, _ = _slow_batcher(0.3, max_wait_ms=1.0, max_queue=2)
        first = mb.submit(_digits(1)[0])
        time.sleep(0.1)  # the worker takes `first` in-flight
        admitted = [mb.submit(_digits(1, seed=s)[0]) for s in (1, 2)]
        with pytest.raises(OverloadedError):
            mb.submit(_digits(1, seed=3)[0])
        assert mb.stats["shed"] == 1
        for f in [first] + admitted:
            assert f.result(timeout=60) is not None
        assert mb.close()

    def test_deadline_fails_only_its_own_future(self):
        mb, model = _slow_batcher(0.3, max_wait_ms=1.0)
        blocker = mb.submit(_digits(1)[0])
        time.sleep(0.1)  # worker is now busy for ~0.3s
        ok = mb.submit(_digits(1, seed=1)[0])
        doomed = mb.submit(_digits(1, seed=2)[0], timeout_ms=50.0)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=60)
        # neighbors are unaffected: both still serve normally
        assert blocker.result(timeout=60).shape == (model.cfg.num_classes,)
        assert ok.result(timeout=60).shape == (model.cfg.num_classes,)
        assert mb.stats["expired"] == 1
        mb.close()

    def test_unclean_close_fails_stranded_futures(self):
        mb, _ = _slow_batcher(2.0, max_wait_ms=1.0)
        inflight = mb.submit(_digits(1)[0])
        time.sleep(0.1)
        pending = mb.submit(_digits(1, seed=1)[0])
        assert mb.close(timeout=0.2) is False  # worker wedged in the call
        for f in (inflight, pending):
            with pytest.raises(RuntimeError):
                f.result(timeout=1)

    def test_submit_after_close_raises(self):
        model, params = _model(name="mb-cl", depth=2)
        mb = MicroBatcher(InferenceEngine(freeze(model, params),
                                          buckets=(1,)))
        assert mb.close()
        with pytest.raises(RuntimeError):
            mb.submit(_digits(1)[0])

    def test_concurrent_submit_many_threads(self):
        model, params = _model(name="mb-thr", codesign="qat")
        eng = InferenceEngine(freeze(model, params), buckets=(2, 8))
        eng.warmup()
        mb = MicroBatcher(eng, max_wait_ms=2.0)
        x = _digits(24, seed=11)
        results = np.zeros((24, model.cfg.num_classes), np.float32)

        def worker(lo):
            futs = [(i, mb.submit(x[i])) for i in range(lo, lo + 6)]
            for i, f in futs:
                results[i] = f.result(timeout=60)

        threads = [threading.Thread(target=worker, args=(lo,))
                   for lo in range(0, 24, 6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert mb.close()
        ref = np.asarray(jax.jit(lambda p, xx: model.apply(p, xx))(params, x))
        np.testing.assert_allclose(results, ref, rtol=1e-5, atol=1e-7)
        assert mb.stats["submitted"] == 24 and mb.stats["served"] == 24


# --------------------------------------------------------------------------
# Training guardrails: skip / rollback
# --------------------------------------------------------------------------
def _train(model, params, stream, steps, **kw):
    return train_classifier(model, params, stream, steps=steps, lr=0.2,
                            steps_per_call=4, prefetch=0, **kw)


def _stream(xs, ys, skip_steps=()):
    it = batch_iterator(xs, ys, 16, seed=1)
    return (b for i, b in enumerate(it) if i not in set(skip_steps))


class TestTrainGuardrails:
    def test_poisoned_step_skipped_bit_identical(self):
        """A NaN batch is a device-side no-op: final params match a run
        that never saw the batch, bit for bit."""
        model, params = _model(name="tg-skip", codesign="qat")
        xs, ys = synth_digits(256, seed=0)
        res = _train(model, params,
                     poison_batches(_stream(xs, ys), [2]), 8, guard=True)
        assert res.skipped_steps == 1 and res.rollbacks == 0
        assert np.isnan(res.losses[2]) and len(res.losses) == 8
        ref = _train(model, params, _stream(xs, ys, skip_steps=[2]), 7)
        for a, b in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fully_poisoned_chunk_rolls_back(self, tmp_path):
        """A whole-chunk NaN storm restores the last good checkpoint and
        resumes — final params match a run without those batches."""
        model, params = _model(name="tg-roll", codesign="qat")
        xs, ys = synth_digits(256, seed=0)
        res = _train(model, params,
                     poison_batches(_stream(xs, ys), [4, 5, 6, 7]), 12,
                     guard=True, ckpt_dir=tmp_path, ckpt_every=4)
        assert res.rollbacks == 1
        assert len(res.losses) == 8  # rolled-back chunk's metrics dropped
        ref = _train(model, params,
                     _stream(xs, ys, skip_steps=[4, 5, 6, 7]), 8)
        for a, b in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rollback_budget_exhausted_raises(self, tmp_path):
        model, params = _model(name="tg-bud", codesign="qat")
        xs, ys = synth_digits(256, seed=0)
        with pytest.raises(RuntimeError):
            _train(model, params,
                   poison_batches(_stream(xs, ys), range(4, 20)), 20,
                   guard=True, ckpt_dir=tmp_path, ckpt_every=4,
                   max_rollbacks=1)

    def test_guard_requires_chunked_driver(self):
        model, params = _model(name="tg-one")
        xs, ys = synth_digits(64, seed=0)
        with pytest.raises(ValueError):
            train_classifier(model, params, _stream(xs, ys), steps=2,
                             guard=True, steps_per_call=1)

    def test_guarded_clean_run_matches_unguarded(self):
        """With no faults the guard must be numerically invisible."""
        model, params = _model(name="tg-clean", codesign="qat")
        xs, ys = synth_digits(256, seed=0)
        res = _train(model, params, _stream(xs, ys), 8, guard=True)
        ref = _train(model, params, _stream(xs, ys), 8)
        assert res.skipped_steps == 0
        for a, b in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Physics faults on frozen planes
# --------------------------------------------------------------------------
class TestPerturbFrozen:
    def test_zero_faults_is_identity(self):
        model, params = _model(name="pf-id", codesign="qat")
        dep = freeze(model, params)
        same = perturb_frozen(dep)
        assert same.frozen[0] is dep.frozen[0]
        assert same.frozen[1] is dep.frozen[1]

    @pytest.mark.parametrize("kw", [
        dict(phase_sigma=0.5), dict(dead_frac=0.3), dict(shift_px=2),
    ])
    def test_faults_change_outputs_not_the_original(self, kw):
        model, params = _model(name="pf-ch", codesign="qat")
        dep = freeze(model, params)
        x = _digits(2)
        ref = InferenceEngine(dep, buckets=(2,)).infer(x)
        pert = perturb_frozen(dep, seed=3, **kw)
        got = InferenceEngine(pert, buckets=(2,)).infer(x)
        assert not np.array_equal(got, ref)
        # the original deployment is untouched by the perturbation
        np.testing.assert_array_equal(
            InferenceEngine(dep, buckets=(2,)).infer(x), ref
        )

    def test_jnp_cartesian_preserves_amplitude(self):
        """In the cartesian convention phase noise must move both split
        planes while preserving |gamma * exp(j theta)|."""
        model, params = _model(name="pf-amp", codesign="qat")
        dep = freeze(model, params)
        pert = perturb_frozen(dep, phase_sigma=0.4, seed=5)
        amp0 = np.hypot(np.asarray(dep.frozen[0]), np.asarray(dep.frozen[1]))
        amp1 = np.hypot(np.asarray(pert.frozen[0]),
                        np.asarray(pert.frozen[1]))
        np.testing.assert_allclose(amp1, amp0, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Checkpoint discovery under damage (latest_step fallback)
# --------------------------------------------------------------------------
class TestLatestStepFallback:
    def test_dangling_pointer_falls_back_to_newest_valid(self, tmp_path):
        s = {"w": np.arange(4, dtype=np.float32)}
        ckpt.save(tmp_path, 1, s)
        ckpt.save(tmp_path, 2, s)
        # damage the newest step's manifest: LATEST now dangles
        (tmp_path / "step_00000002" / "MANIFEST.json").write_text("not json")
        assert ckpt.latest_step(tmp_path) == 1
        assert ckpt.valid_steps(tmp_path) == [1]

    def test_missing_pointer_scans_directories(self, tmp_path):
        s = {"w": np.arange(4, dtype=np.float32)}
        ckpt.save(tmp_path, 3, s)
        ckpt.save(tmp_path, 5, s)
        (tmp_path / "LATEST").unlink()
        assert ckpt.latest_step(tmp_path) == 5

    def test_empty_dir_is_none(self, tmp_path):
        assert ckpt.latest_step(tmp_path) is None
        assert ckpt.valid_steps(tmp_path / "missing") == []
