"""Stage scopes of the forward, the stage map of compiled programs, the
compile counter and the program's host spans."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.core import DONNConfig, LayerSpec, build_model
from repro.core import propagation as pp
from repro.core.models import cached_apply, clear_emulation_caches

TINY = dict(n=64, depth=3, distance=0.05, det_size=8, codesign="qat",
            device_levels=256)
# every stage a path runs, by path
JNP_STAGES = {"encode", "masks", "fft", "tf_mul", "ifft", "modulate",
              "readout"}
PALLAS_STAGES = {"encode", "masks", "fft", "tf_mul", "ifft", "fused_hop",
                 "readout"}


def _images(b=4, seed=0):
    return np.random.default_rng(seed).random((b, 28, 28)).astype(np.float32)


def _stages_of(cfg, x):
    clear_emulation_caches()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    out = cached_apply(cfg)(params, x)
    return set(pp.stage_map().values()), out, params


@pytest.mark.parametrize("use_pallas,expected", [(False, JNP_STAGES),
                                                 (True, PALLAS_STAGES)])
def test_stage_map_names_every_stage_of_the_path(use_pallas, expected):
    cfg = DONNConfig(**TINY, use_pallas=use_pallas)
    stages, _, _ = _stages_of(cfg, _images())
    assert stages == expected


def _instructions(module_text):
    """(module, name, opcode, op_name or None) of every instruction."""
    module = module_text.split()[1].rstrip(",")
    for line in module_text.splitlines():
        instr = pp._HLO_INSTR.match(line)
        if instr:
            op_name = pp._HLO_OP_NAME.search(line)
            yield (module, instr.group(1), instr.group(2),
                   op_name.group(1) if op_name else None)


def _program_ops(cfg):
    """{(stage, opcode): count} of the cached programs and the op_names of
    their instructions that map to no stage."""
    stages, _, _ = _stages_of(cfg, _images())
    smap = pp.stage_map()
    ops, unscoped = {}, set()
    for compiled in pp._EXEC_CACHE.values():
        for module, name, opcode, op_name in _instructions(compiled.as_text()):
            stage = smap.get((module, name))
            ops[stage, opcode] = ops.get((stage, opcode), 0) + 1
            if stage is None and opcode not in pp._CONTAINER_OPS:
                unscoped.add(op_name)
    clear_emulation_caches()
    return stages, ops, unscoped


def test_packed_hop_ops_map_to_fft_tf_mul_ifft(monkeypatch):
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    cfg = DONNConfig(**TINY)
    stages, _, _ = _stages_of(cfg, _images())
    assert stages == JNP_STAGES
    assert pp.plan_from_config(cfg, 1.0)._packable
    dots = {}
    smap = pp.stage_map()
    for compiled in pp._EXEC_CACHE.values():
        text = compiled.as_text()
        module = text.split()[1].rstrip(",")
        for line in text.splitlines():
            instr = pp._HLO_INSTR.match(line)
            if instr and instr.group(2) == "dot":
                stage = smap.get((module, instr.group(1)))
                dots[stage] = dots.get(stage, 0) + 1
    clear_emulation_caches()
    hops = cfg.depth + 1  # two matmuls per transform, two transforms per hop
    assert dots == {"fft": 2 * hops, "ifft": 2 * hops, "readout": 1}


def test_folded_hop_ops_map_to_fft_tf_mul_ifft(monkeypatch):
    cfg = DONNConfig(**TINY)
    _, _, fft_unscoped = _program_ops(cfg)
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    monkeypatch.setattr(pp, "_folded_hop_applies", lambda n: True)
    assert pp.plan_from_config(cfg, 1.0)._packable
    stages, ops, unscoped = _program_ops(cfg)
    assert stages == JNP_STAGES
    dots = {stage: k for (stage, opcode), k in ops.items() if opcode == "dot"}
    hops = cfg.depth + 1
    # the folded hop: two dots per transform, each batched over the four
    # folded blocks; the field's fold (six dots, once a forward: two for
    # the rows, one per block for the columns) belongs to fft, its unfold
    # to ifft, the modulation planes' fold to masks, and each layer's mix
    # of the blocks (adds and multiplies, no dot) to modulate
    assert dots == {"fft": 2 * hops + 6, "ifft": 2 * hops + 6, "masks": 6,
                    "readout": 1}
    assert ops.get(("modulate", "multiply"), 0) > 0
    # no new op lacks a stage: what does is the scan's own slicing, as on
    # the FFT path, or what the compiler made without a source op
    assert unscoped <= fft_unscoped | {None}


def test_segmented_plan_names_its_stitch():
    cfg = DONNConfig(n=48, depth=3, distance=0.05, det_size=6, layers=(
        LayerSpec(distance=0.04, size=48),
        LayerSpec(distance=0.05, size=32, pixel_size=54e-6),
        LayerSpec(distance=0.05, size=32, pixel_size=54e-6),
    ))
    stages, _, _ = _stages_of(cfg, _images())
    assert {"stitch", "fft", "ifft", "modulate", "readout"} <= stages


@pytest.mark.parametrize("use_pallas", [False, True])
def test_scoped_outputs_agree_with_eager(use_pallas):
    cfg = DONNConfig(**TINY, use_pallas=use_pallas)
    x = _images()
    _, got, params = _stages_of(cfg, x)
    want = build_model(dataclasses.replace(cfg, engine="eager")).apply(
        params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_compile_counter_counts_new_shapes_only():
    clear_emulation_caches()
    cfg = DONNConfig(**TINY)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    apply = cached_apply(cfg)
    np.asarray(apply(params, _images(4)))
    c0 = pp.compile_stats()
    np.asarray(apply(params, _images(4, seed=1)))  # same shape
    assert pp.compile_stats() == c0
    np.asarray(apply(params, _images(5)))  # a new batch size
    c1 = pp.compile_stats()
    assert c1["compiles"] == c0["compiles"] + 1
    assert c1["cache_loads"] == c0["cache_loads"]


def test_compile_and_dispatch_spans_reach_the_trace(tmp_path):
    clear_emulation_caches()
    cfg = DONNConfig(**TINY)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        np.asarray(cached_apply(cfg)(params, _images(3)))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {pp.COMPILE_SPAN, pp.DISPATCH_SPAN} <= names


HLO = """HloModule jit_run, entry_computation_layout={()->f32[2]}

%body (p: (s32[], c64[2,8])) -> (s32[], c64[2,8]) {
  %fft.3 = c64[2,8]{1,0} fft(%x), fft_type=FFT, metadata={op_name="jit(run)/while/body/closed_call/donn.fft/jit(fft)/fft"}
  %multiply_fusion.1 = c64[2,8]{1,0:T(8,128)} fusion(%fft.3), kind=kLoop, metadata={op_name="jit(run)/while/body/donn.modulate/donn.tf_mul/mul"}
  %copy.2 = c64[2,8]{1,0} copy(%multiply_fusion.1), metadata={op_name="jit(run)/while/body/closed_call/mul"}
}

ENTRY %main () -> f32[2] {
  %while.5 = (s32[], c64[2,8]{1,0}) while(%tuple), condition=%cond, body=%body, metadata={op_name="jit(run)/donn.masks/while"}
  ROOT %reduce.7 = f32[2]{0} reduce(%a, %b), metadata={op_name="jit(run)/transpose(jvp(donn.readout))/reduce_sum"}
  %add.9 = f32[2]{0} add(%a, %b)
}
"""


def test_hlo_stage_map_takes_the_innermost_program_scope():
    assert pp.hlo_stage_map(HLO) == {
        ("jit_run", "fft.3"): "fft",
        ("jit_run", "multiply_fusion.1"): "tf_mul",
        ("jit_run", "reduce.7"): "readout",
    }
    with pytest.raises(ValueError):
        pp.hlo_stage_map("not hlo")


def test_unknown_stage_is_refused():
    with pytest.raises(ValueError):
        pp.stage("fftx")
    assert all(s.isidentifier() for s in pp.STAGES)
