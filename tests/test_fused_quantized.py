"""Serving surfaces: quantized frozen planes, the rfft first hop, artifact
format 2, and the retired ``use_pallas`` flag.

Four invariants:

- **quantized frozen planes** (``freeze(plane_dtype=...)``): the f32 path
  stays bit-identical to the default, bf16 stays within the documented
  5e-2 output tolerance, int8 is finite and close, and every dtype
  round-trips through ``save_deployed``/``load_deployed`` and serves
  through ``InferenceEngine`` bit-identically to its own ``freeze``;
- **rfft first hop** (``freeze(rfft_first=True)``): half-spectrum entry
  agrees with the full-spectrum forward, invalid deployments are rejected
  eagerly, and the engine output is bit-identical to the deployed
  forward;
- **artifact format 2**: format-1 artifacts still load, unknown formats
  are rejected with a clear error before any deserialization;
- **``use_pallas``**: a config, DSL spec or artifact that asks for the
  removed Pallas path is refused with the path that replaced it, and
  specs or artifacts that name it false still load.
"""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DONNConfig, build_model
from repro.core import dsl
from repro.core.config import LayerSpec
from repro.models.config import get_config
from repro.runtime.inference import InferenceEngine, freeze
from repro.runtime.resilience import (
    ARTIFACT_FILE, load_deployed, save_deployed, validate_artifact,
)

TINY = dict(name="fq", n=32, depth=3, distance=0.05, det_size=6)


def _model(seed=0, **kw):
    cfg = DONNConfig(**{**TINY, **kw})
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def _digits(b, shape=(28, 28), seed=0):
    return np.random.default_rng(seed).random((b,) + shape, np.float32)


# --------------------------------------------------------------------------
class TestQuantizedPlanes:
    def test_invalid_dtype_rejected(self):
        model, params = _model(name="qp-bad")
        with pytest.raises(ValueError, match="plane_dtype"):
            freeze(model, params, plane_dtype="float16")

    def test_f32_path_bit_identical_to_default(self):
        model, params = _model(name="qp-f32", codesign="qat")
        x = jnp.asarray(_digits(3))
        a = freeze(model, params)
        b = freeze(model, params, plane_dtype="float32")
        assert a.plane_dtype == b.plane_dtype == "float32"
        np.testing.assert_array_equal(
            np.asarray(a.forward(x)), np.asarray(b.forward(x))
        )

    @pytest.mark.parametrize("dtype,tol", [("bfloat16", 5e-2),
                                           ("int8", 2e-1)])
    def test_quantized_delta_bounded(self, dtype, tol):
        model, params = _model(name="qp-delta", codesign="qat")
        x = jnp.asarray(_digits(4))
        ref = np.asarray(freeze(model, params).forward(x))
        got = np.asarray(freeze(model, params, plane_dtype=dtype).forward(x))
        assert np.all(np.isfinite(got))
        delta = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-12)
        assert delta <= tol, f"{dtype}: {delta:.3e} > {tol}"
        # class predictions survive the quantization at this scale
        np.testing.assert_array_equal(
            np.argmax(got, -1), np.argmax(ref, -1)
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_roundtrip_and_serving_bit_identical(self, dtype, tmp_path):
        model, params = _model(name="qp-rt", codesign="qat")
        x = _digits(2)
        dep = freeze(model, params, plane_dtype=dtype)
        assert dep.plane_dtype == dtype
        ref = np.asarray(dep.forward(jnp.asarray(x)))
        save_deployed(dep, tmp_path)
        dep2 = load_deployed(tmp_path)
        assert dep2.plane_dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(dep2.forward(jnp.asarray(x))), ref
        )
        # engine vs the *jitted* forward: both sides compiled, bit-exact
        eng = InferenceEngine(dep2, buckets=(2,))
        np.testing.assert_array_equal(
            eng.infer(x), np.asarray(jax.jit(dep2.forward)(jnp.asarray(x)))
        )

    def test_segmented_quantized_planes(self):
        layers = (LayerSpec(0.05, size=40), LayerSpec(0.05, size=40),
                  LayerSpec(0.05, codesign="qat", device_levels=4))
        model, params = _model(name="qp-het", layers=layers)
        x = jnp.asarray(_digits(2))
        ref = np.asarray(freeze(model, params).forward(x))
        got = np.asarray(
            freeze(model, params, plane_dtype="bfloat16").forward(x)
        )
        delta = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-12)
        assert delta <= 5e-2


# --------------------------------------------------------------------------
class TestRfftFirstHop:
    def test_agrees_with_full_spectrum(self):
        model, params = _model(name="rf-agree", codesign="qat")
        x = jnp.asarray(_digits(3))
        ref = np.asarray(freeze(model, params).forward(x))
        got = np.asarray(freeze(model, params, rfft_first=True).forward(x))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    def test_engine_bit_identical_to_deployed_forward(self):
        model, params = _model(name="rf-eng")
        x = _digits(2)
        dep = freeze(model, params, rfft_first=True)
        ref = np.asarray(jax.jit(dep.forward)(jnp.asarray(x)))
        eng = InferenceEngine(dep, buckets=(2,))
        np.testing.assert_array_equal(eng.infer(x), ref)

    def test_engine_distinct_from_plain_executable(self):
        """rfft and plain deployments must not share cached executables."""
        model, params = _model(name="rf-key")
        assert (freeze(model, params).static_key()
                != freeze(model, params, rfft_first=True).static_key())

    def test_roundtrip_preserves_rfft_flag(self, tmp_path):
        model, params = _model(name="rf-rt", codesign="qat")
        x = _digits(2)
        dep = freeze(model, params, rfft_first=True, plane_dtype="int8")
        ref = np.asarray(dep.forward(jnp.asarray(x)))
        save_deployed(dep, tmp_path)
        dep2 = load_deployed(tmp_path)
        assert dep2.rfft_first and dep2.plane_dtype == "int8"
        np.testing.assert_array_equal(
            np.asarray(dep2.forward(jnp.asarray(x))), ref
        )

    def test_heterogeneous_rejected(self):
        layers = (LayerSpec(0.05, size=40), LayerSpec(0.05, size=40),
                  LayerSpec(0.05,))
        model, params = _model(name="rf-het", layers=layers)
        with pytest.raises(ValueError, match="rfft"):
            freeze(model, params, rfft_first=True)

    def test_unsupported_plan_rejected(self):
        model, params = _model(name="rf-pad", pad=True)
        with pytest.raises(ValueError, match="rfft"):
            freeze(model, params, rfft_first=True)


# --------------------------------------------------------------------------
class TestArtifactFormat:
    def test_format_field_is_current(self, tmp_path):
        model, params = _model(name="af-cur")
        save_deployed(freeze(model, params), tmp_path)
        meta = json.loads((tmp_path / ARTIFACT_FILE).read_text())
        assert meta["format"] == 2
        assert meta["plane_dtype"] == "float32"
        assert meta["rfft_first"] is False

    def test_unknown_format_rejected_with_clear_error(self, tmp_path):
        model, params = _model(name="af-unk")
        save_deployed(freeze(model, params), tmp_path)
        meta_path = tmp_path / ARTIFACT_FILE
        meta = json.loads(meta_path.read_text())
        meta["format"] = 99
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ValueError, match=r"format 99.*reads formats"):
            load_deployed(tmp_path)

    def test_format_1_artifact_still_loads(self, tmp_path):
        """Legacy metas (no plane_dtype/rfft_first) imply f32 pairs."""
        model, params = _model(name="af-v1", codesign="qat")
        x = _digits(2)
        dep = freeze(model, params)
        ref = np.asarray(dep.forward(jnp.asarray(x)))
        save_deployed(dep, tmp_path)
        meta_path = tmp_path / ARTIFACT_FILE
        meta = json.loads(meta_path.read_text())
        meta["format"] = 1
        del meta["plane_dtype"], meta["rfft_first"]
        meta_path.write_text(json.dumps(meta))
        dep2 = load_deployed(tmp_path)
        assert dep2.plane_dtype == "float32" and not dep2.rfft_first
        np.testing.assert_array_equal(
            np.asarray(dep2.forward(jnp.asarray(x))), ref
        )


# --------------------------------------------------------------------------
def _pallas_spec(value):
    spec = dsl.to_spec(DONNConfig(**TINY))
    assert "use_pallas" not in spec
    return dict(spec, use_pallas=value)


def _pallas_artifact(tmp_path, value):
    model, params = _model(name="up-art")
    save_deployed(freeze(model, params), tmp_path)
    meta_path = tmp_path / ARTIFACT_FILE
    meta = json.loads(meta_path.read_text())
    meta["spec"]["use_pallas"] = value
    meta_path.write_text(json.dumps(meta))
    return tmp_path


def _benchmark_fields():
    """The fields of a benchmark configuration file, which name the flag."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "perfbench"
            / "configs" / "donn-mnist-5l.json")
    return json.loads(path.read_text())["fields"]


class TestUsePallasRejected:
    """``DONNConfig.use_pallas`` names the removed Pallas path: True is
    refused wherever it enters, naming the hop that replaced it."""

    @pytest.mark.parametrize("make", [
        lambda tmp: DONNConfig(use_pallas=True),
        lambda tmp: DONNConfig(**TINY, use_pallas=1),
        lambda tmp: dataclasses.replace(get_config("donn-mnist-5l"),
                                        use_pallas=True),
        lambda tmp: dsl.spec_to_config(_pallas_spec(True)),
        lambda tmp: dsl.from_spec(_pallas_spec(True)),
        lambda tmp: load_deployed(_pallas_artifact(tmp, True)),
        lambda tmp: validate_artifact(_pallas_artifact(tmp, True)),
        lambda tmp: DONNConfig(**dict(_benchmark_fields(), use_pallas=True)),
    ], ids=["config", "truthy", "registry-replace", "spec-to-config",
            "from-spec", "load-artifact", "validate-artifact",
            "benchmark-fields"])
    def test_true_is_refused_naming_the_replacement(self, make, tmp_path):
        with pytest.raises(ValueError, match="packed or folded real-DFT"):
            make(tmp_path)

    def test_specs_and_artifacts_naming_it_false_still_load(self, tmp_path):
        model, params = _model(name="up-art")
        x = _digits(2)
        ref = np.asarray(freeze(model, params).forward(jnp.asarray(x)))
        assert dsl.spec_to_config(_pallas_spec(False)) == \
            dsl.spec_to_config(dsl.to_spec(DONNConfig(**TINY)))
        dep = load_deployed(_pallas_artifact(tmp_path, False))
        np.testing.assert_array_equal(
            np.asarray(dep.forward(jnp.asarray(x))), ref)
        assert DONNConfig(**_benchmark_fields()) == get_config(
            "donn-mnist-5l")

    def test_serving_cli_has_no_pallas_flag(self, monkeypatch):
        from repro.launch import serve_donn

        monkeypatch.setattr(serve_donn, "use_persistent_compile_cache",
                            lambda: None)
        with pytest.raises(SystemExit):
            serve_donn.main(["--use-pallas", "--requests", "0"])
