"""The folded hop's one-pass mix kernel (``repro.kernels.fold_mix``)
against the jnp mix it replaces (``diffraction.modulate_folded``).

On a CPU the kernel runs in Pallas' interpreter; its v5e compile is
checked in ``tests/test_tpu_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.kernels import fold_mix

from conftest import run_subprocess


def _blocks(n, b, seed, channels=()):
    """A folded field (4, 2, b, *channels, m, m) and one layer's folded
    modulation (4, 2, *channels, m, m), folded as the plan folds them."""
    rng = np.random.default_rng(seed)
    shape = (b,) + channels + (n, n)
    u = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    phi = rng.uniform(0, 2 * np.pi, (1,) + channels + (n, n))
    mod = 1.05 * np.exp(1j * phi)
    return (df.fold_field(jnp.asarray(u, jnp.complex64)),
            df.fold_modulation(jnp.asarray(mod, jnp.complex64))[0])


# m = 193, 200, 250, 256, and odd n = 499, where the sine block's last row
# and column are zero
@pytest.mark.parametrize("n", [385, 400, 499, 500, 512])
@pytest.mark.parametrize("b", [1, 3])
def test_kernel_equals_jnp_mix(n, b):
    x, mod = _blocks(n, b, seed=n + b)
    m = (n + 1) // 2
    assert x.shape == (4, 2, b, m, m) and mod.shape == (4, 2, m, m)
    assert pp._mix_kernel_takes(x, mod)
    got = jax.jit(fold_mix.modulate_folded)(x, mod)
    want = jax.jit(df.modulate_folded)(x, mod)
    # the same float32 adds and multiplies in the same order
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [17, 193, 200, 250, 256])
def test_row_tiles_cover_the_plane(m):
    rows = fold_mix.tile_rows(m)
    tiles = -(-m // rows)
    assert rows % 8 == 0 and rows <= 128
    assert tiles * rows >= m > (tiles - 1) * rows


def test_kernel_gradients_equal_jnp_mix():
    x, mod = _blocks(33, 2, seed=1)
    g = jnp.asarray(np.random.default_rng(2).standard_normal(x.shape),
                    jnp.float32)

    def grads(fn):
        _, vjp = jax.vjp(fn, x, mod)
        return jax.jit(vjp)(g)

    got, want = grads(fold_mix.modulate_folded), grads(df.modulate_folded)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_multichannel_field_keeps_the_jnp_mix(monkeypatch):
    # a (..., C, m, m) field is not the kernel's plain (4, 2, B, m, m)
    monkeypatch.setattr(pp, "_mix_kernel_applies", pp._mix_kernel_takes)
    x, mod = _blocks(33, 2, seed=3, channels=(3,))
    assert x.ndim == 6 and mod.ndim == 5
    before = pp.hop_path_stats()["mix_kernel"]
    got = jax.jit(lambda v, w: pp._modulate_folded(v, w, 1))(x, mod)
    assert pp.hop_path_stats()["mix_kernel"] == before
    want = jax.jit(df.modulate_folded)(x, mod)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_rule_is_tpu_float32_plain_blocks(monkeypatch):
    x, mod = _blocks(33, 2, seed=4)
    if jax.default_backend() != "tpu":
        assert not pp._mix_kernel_applies(x, mod)
    monkeypatch.setattr(pp.jax, "default_backend", lambda: "tpu")
    assert pp._mix_kernel_applies(x, mod)
    assert not pp._mix_kernel_applies(x[..., 0, :, :], mod)
    assert not pp._mix_kernel_applies(x, mod[..., 1:, :])
    assert not pp._mix_kernel_applies(x.astype(jnp.bfloat16),
                                      mod.astype(jnp.bfloat16))


def test_unfolded_paths_never_import_pallas():
    # the kernel module is imported only where a folded scan body is traced
    # on a TPU: a training or emulation process on any other path keeps
    # Pallas (and its set-up cost) out
    script = """
import sys
import jax, jax.numpy as jnp
from repro.core import DONNConfig, build_model
from repro.core import propagation as pp
from repro.core.models import cached_apply
from repro.data import synth_digits

cfg = DONNConfig(n=64, depth=2, distance=0.05, det_size=8, codesign="qat",
                 device_levels=256)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
x = jnp.asarray(synth_digits(2, seed=0)[0])
cached_apply(cfg)(params, x).block_until_ready()
jax.grad(lambda p: jnp.sum(model.apply(p, x) ** 2))(params)
pp._packed_hop_applies = lambda n: True
pp._folded_hop_applies = lambda n: True
pp.clear_plan_cache()
cached_apply(cfg)(params, x).block_until_ready()
print(sorted(m for m in sys.modules if "pallas" in m))
"""
    out = run_subprocess(script, device_count=1, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
