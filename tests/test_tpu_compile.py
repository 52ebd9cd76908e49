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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import diffraction as df
from repro.core import propagation as pp
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


def test_packed_hop_compiles_for_v5e_at_highest_precision(one_chip):
    n = 500
    m = (n + 1) // 2
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3], 532e-9)
    assert plan._packable

    def fn(ur, ui, hr, hi):
        return plan._packed_hop(jax.lax.complex(ur, ui), (hr, hi))

    hlo = _compiled_text(fn, one_chip, (BATCH, n, n), (BATCH, n, n),
                         (n, n), (n, n))
    # XLA's TPU backend writes a dot as a 1x1 convolution.  Four stages,
    # each one dot batched over the four folded blocks, write (block,
    # real/imaginary, batch, m, m) and contract m, not n; the field's fold
    # and unfold are six plain dots each, whose 0/+-1 matrices do the
    # mirrored reads (a TPU runs a reversal as a pass of its own)
    matmuls = [line for line in hlo.splitlines()
               if re.search(r"\b(dot|convolution)\(", line)]
    assert len(matmuls) == 16
    assert all("operand_precision={highest,highest}" in line
               for line in matmuls)
    stages = [line for line in matmuls
              if re.search(rf"= f32\[4,2,{BATCH},{m},{m}\]", line)]
    assert len(stages) == 4
    assert not any(str(n) in line.split(", metadata")[0] for line in stages)
    assert "reverse(" not in hlo
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    # neither the stacked real pair nor the folded blocks are copied or
    # transposed on their own
    assert "transpose(" not in entry
    assert not re.search(rf"= f32\[2,{BATCH},{n},{n}\]\S* copy\(", entry)
    assert not re.search(rf"= f32\[4,2,{BATCH},{m},{m}\]\S* copy\(", entry)
