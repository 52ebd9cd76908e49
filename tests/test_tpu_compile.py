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
import numpy as np
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


def test_folded_forward_compiles_for_v5e_with_one_mix_kernel_a_layer(
        one_chip, monkeypatch):
    # the kernel is chosen as on a TPU (``default_backend()`` is the CPU
    # here, where it would run interpreted)
    from repro.kernels import fold_mix

    n, depth = 500, 2
    m = (n + 1) // 2
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    monkeypatch.setattr(pp, "_mix_kernel_applies", pp._mix_kernel_takes)
    monkeypatch.setattr(fold_mix, "_interpret", lambda: False)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3] * (depth + 1),
                              532e-9, gamma=1.05)
    assert pp._folded_hop_applies(n)

    def fn(phis, ur, ui):
        return df.intensity(plan.apply(phis, jax.lax.complex(ur, ui)))

    before = pp.hop_path_stats()["mix_kernel"]
    hlo = _compiled_text(fn, one_chip, (depth, n, n), (BATCH, n, n),
                         (BATCH, n, n))
    assert pp.hop_path_stats()["mix_kernel"] - before == depth
    assert all("operand_precision={highest,highest}" in line
               for line in _matmuls(hlo))
    # one Pallas call per modulated layer, under the modulate stage
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == depth
    smap = pp.hlo_stage_map(hlo)
    module = hlo.split()[1].rstrip(",")
    for line in kernels:
        assert "fold_mix" in line
        name = pp._HLO_INSTR.match(line).group(1)
        assert smap[(module, name)] == "modulate"
    # between a layer's last inverse dot and the next forward dot the
    # blocks go through the kernel alone: no loop pass of the program (the
    # scan is unrolled at this depth) writes them, and nothing copies or
    # transposes them
    blocks = f"f32[4,2,{BATCH},{m},{m}]"
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert "while(" not in entry
    for line in entry.splitlines():
        head = line.split(", metadata")[0]
        if "kind=kLoop" in head:
            assert blocks not in head.split(" fusion(")[0]
        if re.search(r" (copy|transpose)\(", head):
            assert blocks not in head


@pytest.mark.parametrize("manual", [False, True],
                         ids=["compiler-partitioned", "shard_map"])
def test_folded_forward_over_four_chips_compiles_for_v5e(topo, monkeypatch,
                                                        manual):
    # a Pallas kernel cannot be partitioned by the compiler: a program
    # sharded over the mesh (the train steps' activation_sharding) keeps
    # the jnp mix, and a shard_map body runs the kernel on its shard
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import fold_mix
    from repro.runtime import sharding as shd

    n, depth = 400, 1
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    monkeypatch.setattr(pp, "_mix_kernel_applies", pp._mix_kernel_takes)
    monkeypatch.setattr(fold_mix, "_interpret", lambda: False)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3] * (depth + 1),
                              532e-9)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    fwd = lambda p, ur, ui: df.intensity(plan.apply(p, jax.lax.complex(ur,
                                                                       ui)))
    if manual:
        fwd = jax.shard_map(fwd, mesh=mesh,
                            in_specs=(P(), P("data"), P("data")),
                            out_specs=P("data"), check_vma=False)

    def fn(p, ur, ui):
        with shd.activation_sharding(mesh):
            return fwd(p, ur, ui)

    rows = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((depth, n, n), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))] + [
        jax.ShapeDtypeStruct((BATCH, n, n), jnp.float32, sharding=rows)
    ] * 2
    before = pp.hop_path_stats()["mix_kernel"]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    kernels = pp.hop_path_stats()["mix_kernel"] - before
    assert kernels == (depth if manual else 0)
    assert hlo.count('custom_call_target="tpu_custom_call"') == kernels


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
    # the mix kernel chosen as on a TPU: unfolded, no layer reaches it
    monkeypatch.setattr(pp, "_mix_kernel_applies", pp._mix_kernel_takes)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3] * (depth + 1),
                              532e-9, gamma=1.12)
    assert not pp._folded_hop_applies(n)

    def loss(phis, ur, ui):
        return jnp.sum(df.intensity(plan.apply(phis,
                                               jax.lax.complex(ur, ui))))

    before = pp.hop_path_stats()["mix_kernel"]
    hlo = _compiled_text(jax.grad(loss), one_chip, (depth, n, n),
                         (BATCH, n, n), (BATCH, n, n))
    assert pp.hop_path_stats()["mix_kernel"] == before
    assert "tpu_custom_call" not in hlo
    matmuls = _matmuls(hlo)
    # four stages a hop: depth + 1 forward hops, and the adjoints of all
    # but the first (the phases' gradient needs no hop before the first
    # mask)
    assert len(matmuls) == 4 * (depth + 1) + 4 * depth
    assert all("operand_precision={highest,highest}" in line
               and re.search(rf"= f32\[2,{BATCH},{n},{n}\]", line)
               for line in matmuls)
