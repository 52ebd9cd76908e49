"""The LM stack's RoPE Pallas kernel against its pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _rand(shape, dtype, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=shape), dtype)


class TestRope:
    @pytest.mark.parametrize("shape", [(2, 16, 64), (4, 33, 128), (1, 7, 32)])
    def test_matches_oracle(self, shape):
        x = _rand(shape, jnp.float32, 1)
        ang = np.random.default_rng(2).normal(size=(shape[-2], shape[-1] // 2))
        c, s = jnp.cos(ang).astype(jnp.float32), jnp.sin(ang).astype(jnp.float32)
        np.testing.assert_allclose(
            ops.apply_rope(x, c, s), ops.rope_ref(x, c, s),
            rtol=1e-5, atol=1e-5,
        )

    def test_norm_preserving(self):
        x = _rand((2, 16, 64), jnp.float32, 3)
        ang = np.random.default_rng(4).normal(size=(16, 32))
        out = ops.apply_rope(x, jnp.cos(ang).astype(jnp.float32),
                             jnp.sin(ang).astype(jnp.float32))
        np.testing.assert_allclose(
            jnp.sum(out**2, -1), jnp.sum(x**2, -1), rtol=1e-4
        )

    def test_inverse_rotation(self):
        x = _rand((2, 16, 64), jnp.float32, 5)
        ang = np.random.default_rng(6).normal(size=(16, 32))
        c = jnp.cos(ang).astype(jnp.float32)
        s = jnp.sin(ang).astype(jnp.float32)
        back = ops.apply_rope(ops.apply_rope(x, c, s), c, -s)
        np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-5)
