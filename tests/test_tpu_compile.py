"""v5e compile rehearsals for the main path's hop, modulation and readout.

A CPU run never hands these programs to the TPU compiler, so precision,
layout and lowering choices only show when they compile for a TPU.  The
installed TPU compiler compiles for a described (not attached) v5e, so
these tests compile each stage at the widths of the registered
configurations (n = 200, 350, 500) with a batch > 1 and read the
compiled program.

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


def _matmuls(hlo):
    """XLA's TPU backend writes a dot as a 1x1 convolution."""
    return [line for line in hlo.splitlines()
            if re.search(r"\b(dot|convolution)\(", line)]


@pytest.mark.parametrize("n", WIDTHS)
def test_packed_hop_compiles_for_v5e_at_highest_precision(one_chip, n):
    m = (n + 1) // 2
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3], 532e-9)
    assert plan._packable

    def fn(ur, ui, hr, hi):
        return plan._packed_hop(jax.lax.complex(ur, ui), (hr, hi))

    hlo = _compiled_text(fn, one_chip, (BATCH, n, n), (BATCH, n, n),
                         (n, n), (n, n))
    matmuls = _matmuls(hlo)
    assert all("operand_precision={highest,highest}" in line
               for line in matmuls)
    assert "reverse(" not in hlo
    if not pp._folded_hop_applies(n):
        # two real stages a transform on the stacked (real, imaginary)
        # pair, each contracting n
        assert len(matmuls) == 4
        assert all(re.search(rf"= f32\[2,{BATCH},{n},{n}\]", line)
                   for line in matmuls)
        return
    # Four stages, each one dot batched over the four folded blocks, write
    # (block, real/imaginary, batch, m, m) and contract m, not n; the
    # field's fold and unfold are six plain dots each, whose 0/+-1 matrices
    # do the mirrored reads (a TPU runs a reversal as a pass of its own)
    assert len(matmuls) == 16
    stages = [line for line in matmuls
              if re.search(rf"= f32\[4,2,{BATCH},{m},{m}\]", line)]
    assert len(stages) == 4
    assert not any(str(n) in line.split(", metadata")[0] for line in stages)
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    # neither the stacked real pair nor the folded blocks are copied or
    # transposed on their own
    assert "transpose(" not in entry
    assert not re.search(rf"= f32\[2,{BATCH},{n},{n}\]\S* copy\(", entry)
    assert not re.search(rf"= f32\[4,2,{BATCH},{m},{m}\]\S* copy\(", entry)


@pytest.mark.parametrize("n", WIDTHS)
def test_folded_modulation_compiles_for_v5e_without_a_matmul(one_chip, n):
    # the mix of the four folded blocks: butterflies of adds; as a 4 x 4
    # dot the v5e laid the block axis out second-minor and ran it six
    # times as long (diffraction._hadamard)
    m = (n + 1) // 2
    hlo = _compiled_text(df.modulate_folded, one_chip, (4, 2, BATCH, m, m),
                         (4, 2, m, m))
    assert not _matmuls(hlo)
    assert "reverse(" not in hlo


@pytest.mark.parametrize("n", WIDTHS)
def test_readout_compiles_for_v5e_at_highest_precision(one_chip, n):
    def fn(ur, ui, masks):
        return df.readout(jax.lax.complex(ur, ui), masks)

    hlo = _compiled_text(fn, one_chip, (BATCH, n, n), (BATCH, n, n),
                         (CLASSES, n, n))
    matmuls = _matmuls(hlo)
    assert len(matmuls) == 1
    assert "operand_precision={highest,highest}" in matmuls[0]


def test_phase_gradient_compiles_for_v5e_on_the_packed_hop(one_chip,
                                                           monkeypatch):
    # the training cell's plane size: the forward and the adjoint of the
    # packed hop, every matmul at HIGHEST on the stacked (real, imaginary)
    # pair (XLA's TPU FFT lowers to convolutions of another shape)
    n, depth = 200, 2
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3] * (depth + 1),
                              532e-9, gamma=1.12)
    assert not pp._folded_hop_applies(n)

    def loss(phis, ur, ui):
        return jnp.sum(df.intensity(plan.apply(phis,
                                               jax.lax.complex(ur, ui))))

    hlo = _compiled_text(jax.grad(loss), one_chip, (depth, n, n),
                         (BATCH, n, n), (BATCH, n, n))
    matmuls = _matmuls(hlo)
    # four stages a hop: depth + 1 forward hops, and the adjoints of all
    # but the first (the phases' gradient needs no hop before the first
    # mask)
    assert len(matmuls) == 4 * (depth + 1) + 4 * depth
    assert all("operand_precision={highest,highest}" in line
               and re.search(rf"= f32\[2,{BATCH},{n},{n}\]", line)
               for line in matmuls)
