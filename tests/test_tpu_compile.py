"""v5e compile rehearsals for the main-path Pallas kernels.

Interpret mode runs the kernels on CPU but never hands them to Mosaic, so
block-shape alignment, VMEM limits and lowering gaps only show when the
kernels compile for a TPU.  The installed TPU compiler compiles for a
described (not attached) v5e, so these tests compile each kernel at the
padded widths of the registered configurations (n = 200, 350, 500) with a
batch > 1 and check that a Mosaic custom call is in the program.

The topology is described inside a fixture only: one process at a time may
load the TPU library, and each pytest-xdist worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.complex_mul import phase_tf_apply_pallas
from repro.kernels.intensity_readout import intensity_readout_pallas
from repro.kernels.spectral_hop import conj_phase_scale_pallas

WIDTHS = (200, 350, 500)
BATCH = 4
CLASSES = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of the way
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _padded(n, **block_max):
    bh, bw = ops._pick_blocks(n, n, **block_max)
    return bh, bw, ops._ceil_to(n, bh), ops._ceil_to(n, bw)


@pytest.mark.parametrize("n", WIDTHS)
def test_phase_tf_apply_compiles_for_v5e(one_chip, n):
    bh, bw, hp, wp = _padded(n)

    def fn(xr, xi, th, amp):
        return phase_tf_apply_pallas(xr, xi, th, amp, nb=BATCH, bh=bh, bw=bw,
                                     interpret=False)

    hlo = _compiled_text(fn, one_chip, (BATCH, hp, wp), (BATCH, hp, wp),
                         (1, hp, wp), (1, hp, wp))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", WIDTHS)
def test_conj_phase_scale_compiles_for_v5e(one_chip, n):
    bh, bw, hp, wp = _padded(n)

    def fn(xr, xi, th, amp):
        return conj_phase_scale_pallas(xr, xi, th, amp, sign=-1.0,
                                       scale=1.0 / (n * n), nb=BATCH, bh=bh,
                                       bw=bw, interpret=False)

    hlo = _compiled_text(fn, one_chip, (BATCH, hp, wp), (BATCH, hp, wp),
                         (1, hp, wp), (1, hp, wp))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", WIDTHS)
def test_intensity_readout_compiles_for_v5e(one_chip, n):
    bh, bw, hp, wp = _padded(n, max_h=32, max_w=256)

    def fn(ur, ui, masks):
        return intensity_readout_pallas(ur, ui, masks, bh=bh, bw=bw,
                                        interpret=False)

    hlo = _compiled_text(fn, one_chip, (BATCH, hp, wp), (BATCH, hp, wp),
                         (CLASSES, hp, wp))
    assert "tpu_custom_call" in hlo
