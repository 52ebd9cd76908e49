"""Spans, scopes and counters of the training steps: the ``loss`` and
``optimizer`` stages in the compiled chunk, the ``donn.train_dispatch``
host span, ``train_stats()``, and forward programs mapped as before."""
import glob
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core import DONNConfig, build_model
from repro.core import propagation as pp
from repro.core.models import cached_apply, clear_emulation_caches
from repro.core.train_utils import (make_train_chunk, make_train_step,
                                    train_stats)
from repro.optim import AdamW

REPO = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(n=64, depth=3, distance=0.05, det_size=8, codesign="qat",
            device_levels=256)
FORWARD_STAGES = ("encode", "masks", "fft", "tf_mul", "ifft", "modulate",
                  "fused_hop", "readout", "stitch")


def _chunk_inputs(cfg, steps=3, b=4):
    rng = np.random.default_rng(0)
    xs = rng.random((steps, b, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (steps, b)).astype(np.int32)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return params, xs, ys


def test_train_chunk_maps_loss_and_optimizer_ops():
    clear_emulation_caches()
    cfg = DONNConfig(**TINY)
    opt = AdamW(lr=1e-3)
    params, xs, ys = _chunk_inputs(cfg)
    chunk = make_train_chunk(build_model(cfg), opt, 10)
    out = chunk(params, opt.init(params), 0, xs, ys, jax.random.PRNGKey(1))
    np.asarray(out[3])
    stages = set(pp.stage_map().values())
    clear_emulation_caches()
    assert {"loss", "optimizer"} <= stages
    assert {"fft", "ifft", "modulate", "readout"} <= stages


def test_train_step_maps_loss_and_optimizer_ops():
    clear_emulation_caches()
    cfg = DONNConfig(**TINY)
    opt = AdamW(lr=1e-3)
    params, xs, ys = _chunk_inputs(cfg)
    step = make_train_step(build_model(cfg), opt, 10)
    np.asarray(step(params, opt.init(params), 0, xs[0], ys[0],
                    jax.random.PRNGKey(1))[2])
    stages = set(pp.stage_map().values())
    clear_emulation_caches()
    assert {"loss", "optimizer"} <= stages


def test_forward_maps_as_before(monkeypatch):
    """The paper's 500^2, 30-layer emulation forward: its map holds no
    training stage, and is the map that the forward stages alone give."""
    fields = json.loads((REPO / "perfbench" / "configs" /
                         "donn-xl-500.json").read_text())["fields"]
    cfg = DONNConfig(**fields)
    clear_emulation_caches()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).random((1, 28, 28)).astype(np.float32)
    np.asarray(cached_apply(cfg)(params, x))
    got = pp.stage_map()
    monkeypatch.setattr(pp, "STAGES", FORWARD_STAGES)
    before = pp.stage_map()
    clear_emulation_caches()
    assert got == before
    assert set(got.values()) == {"encode", "masks", "fft", "tf_mul", "ifft",
                                 "modulate", "readout"}


@pytest.mark.parametrize("lr", [1e-3, lambda step: 1e-3])
def test_train_stats_count_chunks_and_steps(lr):
    # a schedule is not cache-keyable, so that chunk runs its own jit
    cfg = DONNConfig(**TINY)
    opt = AdamW(lr=lr)
    params, xs, ys = _chunk_inputs(cfg)
    chunk = make_train_chunk(build_model(cfg), opt, 10)
    before = train_stats()
    state, rng = opt.init(params), jax.random.PRNGKey(1)
    params, state, rng, _, _ = chunk(params, state, 0, xs, ys, rng)
    chunk(params, state, 3, xs[:2], ys[:2], rng)
    after = train_stats()
    clear_emulation_caches()
    assert after["chunks"] - before["chunks"] == 2
    assert after["steps"] - before["steps"] == 5


def test_train_dispatch_span_reaches_the_trace(tmp_path):
    cfg = DONNConfig(**TINY)
    opt = AdamW(lr=1e-3)
    params, xs, ys = _chunk_inputs(cfg)
    chunk = make_train_chunk(build_model(cfg), opt, 10)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = chunk(params, opt.init(params), 0, xs, ys,
                    jax.random.PRNGKey(1))
        np.asarray(out[3])
    finally:
        jax.profiler.stop_trace()
    clear_emulation_caches()
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert pp.TRAIN_DISPATCH_SPAN in names
    assert pp.TRAIN_DISPATCH_SPAN == "donn.train_dispatch"
