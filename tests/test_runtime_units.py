"""Unit tests: sharding rule resolution, input specs, laser, nn module."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.laser import Laser, data_to_cplex, resize_to_grid
from repro.core.diffraction import Grid
from repro.launch.specs import cell_status, input_specs, shapes_for
from repro.models.config import LM_SHAPES, get_config
from repro.nn import ParamSpec, init_params, param_bytes, param_count
from repro.runtime.sharding import batch_sharding, resolve_pspec


def _mesh(shape, axes):
    devs = np.array(jax.devices()[:1] * int(np.prod(shape))).reshape(shape)
    return Mesh(devs, axes)


MESH = _mesh((2, 16, 16), ("pod", "data", "model"))
MESH1 = _mesh((16, 16), ("data", "model"))


class TestResolvePspec:
    def test_basic_tp(self):
        spec = resolve_pspec((4096, 16384), ("embed", "mlp"), MESH1)
        assert spec == P(("data",), "model") or spec == P("data", "model")

    def test_non_divisible_drops(self):
        # kv_heads=2 can't shard 16 ways -> replicated
        spec = resolve_pspec((40, 2, 128), ("layers", "kv_heads", "head"),
                             MESH1)
        assert spec[1] is None
        assert spec[2] == "model"  # head-dim fallback engages

    def test_duplicate_axis_first_wins(self):
        # both kv_heads and head map to model; kv divisible -> head dropped
        spec = resolve_pspec((16, 128), ("kv_heads", "head"), MESH1)
        assert spec[0] == "model"
        assert len(spec) < 2 or spec[1] is None

    def test_missing_mesh_axis_filtered(self):
        spec = resolve_pspec((256, 4096), ("batch", None), MESH1)
        # ("pod","data") rule -> only data exists on the single-pod mesh
        assert spec[0] in ("data", ("data",))

    def test_multi_axis_embed_zero(self):
        spec = resolve_pspec((4096,), ("embed",), MESH)
        assert spec[0] == ("data", "pod")


class TestBatchSharding:
    def test_divisible(self):
        s = batch_sharding(MESH, 2, batch_size=256)
        assert s.spec[0] == ("pod", "data")

    def test_batch_one_replicates(self):
        s = batch_sharding(MESH, 2, batch_size=1)
        assert s.spec == P(None, None) or all(x is None for x in s.spec)

    def test_partial_drop(self):
        # 2 divides pod but not pod*data
        s = batch_sharding(MESH, 2, batch_size=2)
        assert s.spec[0] in ("pod", ("pod",))


class TestInputSpecs:
    @pytest.mark.parametrize("arch", ["glm4-9b", "falcon-mamba-7b",
                                      "donn-mnist-5l"])
    def test_specs_are_abstract(self, arch):
        cfg = get_config(arch)
        for cell in shapes_for(cfg):
            if cell_status(cfg, cell):
                continue
            _, _, kind, specs = input_specs(arch, cell.name)
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)

    def test_long_500k_skips_full_attention(self):
        cfg = get_config("glm4-9b")
        cell = [c for c in LM_SHAPES if c.name == "long_500k"][0]
        assert cell_status(cfg, cell) is not None
        for a in ("mixtral-8x7b", "falcon-mamba-7b", "recurrentgemma-9b"):
            assert cell_status(get_config(a), cell) is None

    def test_decode_cache_rolling_for_swa(self):
        _, _, kind, specs = input_specs("mixtral-8x7b", "long_500k")
        assert kind == "decode"
        # rolling buffer: physical cache = window, not 524288
        assert specs["cache"]["k"].shape[2] == 4096

    def test_vlm_vision_stub(self):
        cfg, cell, kind, specs = input_specs("llama-3.2-vision-11b",
                                             "train_4k")
        assert specs["vision"].shape == (256, 1600, 4096)


class TestLaser:
    def test_gaussian_profile_peak_center(self):
        g = Grid(64, 10e-6)
        f = Laser(profile="gaussian", waist=100e-6).field(g)
        assert np.argmax(np.abs(f)) == 64 * 32 + 32 or np.abs(f)[32, 32] >= \
            np.abs(f).max() - 1e-6

    def test_plane_unit(self):
        f = Laser(profile="plane").field(Grid(16, 1e-5))
        np.testing.assert_allclose(np.abs(f), 1.0)

    def test_data_to_cplex_zero_phase(self):
        x = jnp.asarray(np.random.default_rng(0).random((2, 28, 28)),
                        jnp.float32)
        u = data_to_cplex(x, 64)
        assert u.dtype == jnp.complex64
        np.testing.assert_allclose(np.asarray(jnp.imag(u)), 0.0)

    def test_resize_embed_mode(self):
        x = jnp.ones((1, 8, 8))
        out = resize_to_grid(x, 16, mode="embed")
        assert out.shape == (1, 16, 16)
        assert float(out.sum()) == 64.0  # embedded, not scaled


class TestNNModule:
    def test_init_shapes_and_dtypes(self):
        specs = {
            "a": ParamSpec((4, 8), jnp.float32, ("embed", "mlp")),
            "b": ParamSpec((8,), jnp.bfloat16, ("mlp",), init="zeros"),
        }
        p = init_params(specs, jax.random.PRNGKey(0))
        assert p["a"].shape == (4, 8) and p["b"].dtype == jnp.bfloat16

    def test_param_count_and_bytes(self):
        specs = {"a": ParamSpec((4, 8), jnp.float32, ())}
        assert param_count(specs) == 32
        assert param_bytes(specs) == 128

    def test_uniform_phase_range(self):
        s = ParamSpec((64, 64), jnp.float32, (), init="uniform_phase")
        p = init_params({"x": s}, jax.random.PRNGKey(1))["x"]
        assert float(p.min()) >= 0.0 and float(p.max()) <= 2 * np.pi

    def test_logical_axes_rank_check(self):
        with pytest.raises(ValueError):
            ParamSpec((4, 8), jnp.float32, ("embed",))


class TestPersistentCompileCache:
    """Entry points keep XLA's compile cache at one fixed, ignored path."""

    @pytest.fixture
    def restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_unset_env_uses_repo_dir(self, monkeypatch, restore_cache_dir):
        from repro.core.cache import (
            REPO_COMPILE_CACHE, use_persistent_compile_cache,
        )

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = use_persistent_compile_cache()
        assert path == str(REPO_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        repo = REPO_COMPILE_CACHE.parent
        assert (repo / "src" / "repro").is_dir()
        ignored = (repo / ".gitignore").read_text().split()
        assert f"{REPO_COMPILE_CACHE.name}/" in ignored

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path,
                                    restore_cache_dir):
        from repro.core.cache import use_persistent_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_persistent_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
