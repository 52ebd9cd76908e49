"""Roofline: measured achieved-vs-peak compute/bandwidth per tier-1 cell.

For every tier-1 bench suite this builds one *representative* jitted
computation (the suite's steady-state hot program, at reduced problem
size where the full protocol would be slow), compiles it, and reads the
XLA cost model off the compiled executable
(``Compiled.cost_analysis()``: ``flops`` and ``bytes accessed``).
Dividing by measured wall time gives achieved GFLOP/s and GB/s; dividing
those by *measured* machine peaks gives the roofline fraction and which
roof (compute vs memory) the cell sits under.

Peaks are calibrated live by two microbenchmarks — a large f32 matmul
(compute roof) and a large strided saxpy (memory roof) — on the same
backend, same process, so the fractions compare like with like rather
than against a datasheet number this container cannot hit.

Every row is *measured in this invocation* (this suite is tier-1: the CI
``--check`` gate fails when the artifact is stale).  Cells whose backend
does not expose the cost-model keys degrade to ``cost_model=unavailable``
rows instead of failing the run.

Rows persist to ``artifacts/bench/BENCH_roofline.json``.

    PYTHONPATH=src:. python benchmarks/bench_roofline.py
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn, write_bench_json
from repro.core import DONNConfig, LayerSpec, build_model
from repro.core.train_utils import mse_softmax_loss
from repro.kernels import ops as kops
from repro.optim import AdamW
from repro.runtime.inference import freeze


# --------------------------------------------------------------- peaks
def _measure_peaks() -> dict:
    """Machine roofs, measured in-process on the active backend."""
    n = 1024
    r = np.random.default_rng(0)
    a = jnp.asarray(r.normal(size=(n, n)), jnp.float32)
    b = jnp.asarray(r.normal(size=(n, n)), jnp.float32)
    mm = jax.jit(lambda u, v: u @ v)
    us = time_fn(mm, a, b, warmup=2, iters=5)
    peak_flops = (2.0 * n**3) / (us / 1e6)

    sx = jax.jit(lambda v: v * 1.0009765625 + 1.0)
    bw = {}
    # two memory roofs: DRAM (far past cache) and last-level cache (the
    # ceiling that actually binds the cache-resident bench cells)
    for tag, m in (("dram", 1 << 25), ("cache", 1 << 21)):
        x = jnp.zeros((m,), jnp.float32)
        us = time_fn(sx, x, warmup=3, iters=10)
        bw[tag] = (2.0 * 4 * m) / (us / 1e6)  # one read + one write stream
    return {"peak_gflops": peak_flops / 1e9,
            "peak_gbs": max(bw.values()) / 1e9,
            "dram_gbs": bw["dram"] / 1e9, "cache_gbs": bw["cache"] / 1e9}


# --------------------------------------------------------------- cells
def _cell_propagation_plan():
    cfg = DONNConfig(name="cls", n=128, depth=16, distance=0.1, det_size=12)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (8, 128, 128)),
                    jnp.float32)
    return lambda p, xb: model.apply(p, xb), (params, x)


def _cell_dse_batched():
    # the batched-DSE compute shape: K candidate forwards in one vmapped
    # program (shared statics, per-candidate parameters as traced inputs)
    cfg = DONNConfig(name="dse", n=64, depth=8, det_size=8)
    model = build_model(cfg)
    k = 8
    params = [model.init(jax.random.PRNGKey(i)) for i in range(k)]
    pstack = jax.tree.map(lambda *ls: jnp.stack(ls), *params)
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (8, 64, 64)),
                    jnp.float32)
    fn = lambda ps, xb: jax.vmap(lambda p: model.apply(p, xb))(ps)
    return fn, (pstack, x)


def _cell_hetero():
    layers = (
        LayerSpec(distance=0.08, size=64, device_levels=256, codesign="qat"),
        LayerSpec(distance=0.10, size=64, device_levels=256, codesign="qat"),
        LayerSpec(distance=0.06, size=48, pixel_size=48e-6, device_levels=4,
                  codesign="qat"),
        LayerSpec(distance=0.06, size=48, pixel_size=48e-6, device_levels=4,
                  codesign="qat"),
    )
    cfg = DONNConfig(name="het", n=64, depth=len(layers), distance=0.10,
                     det_size=8, layers=layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (8, 64, 64)),
                    jnp.float32)
    return lambda p, xb: model.apply(p, xb), (params, x)


def _cell_train_throughput():
    cfg = DONNConfig(name="tr", n=64, depth=8, det_size=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = AdamW(lr=0.1)
    opt_state = optimizer.init(params)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.uniform(0, 1, (8, 64, 64)), jnp.float32)
    y = jnp.asarray(r.integers(0, 10, (8,)), jnp.int32)

    def step(p, st, xb, yb):
        def loss_fn(pp_):
            return mse_softmax_loss(model.apply(pp_, xb), yb, 10)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, st = optimizer.update(grads, st, p, jnp.asarray(0))
        return p, st, loss

    return step, (params, opt_state, x, y)


def _frozen_forward_cell(cfg_kw: dict, batch: int):
    cfg = DONNConfig(**cfg_kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dep = freeze(model, params)
    shape = ((batch, cfg.n, cfg.n) if cfg.channels == 1
             else (batch, cfg.channels, cfg.n, cfg.n))
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, shape),
                    jnp.float32)
    fn = lambda xb, fz: dep.forward(xb, frozen=fz)
    return fn, (x, tuple(dep.frozen))


def _cell_inference_throughput():
    # the serving hot program: frozen-plane forward at the bucket size
    return _frozen_forward_cell(
        dict(name="inf", n=64, depth=8, det_size=8), batch=8)


def _cell_resilience():
    # the resilience suite's served program (small classify cell, bucket 4)
    return _frozen_forward_cell(
        dict(name="rz", n=32, depth=3, distance=0.05, det_size=6,
             codesign="qat"), batch=4)


def _cell_kernel_breakdown():
    n, batch = 256, 8
    r = np.random.default_rng(0)
    ur = jnp.asarray(r.normal(size=(batch, n, n)), jnp.float32)
    ui = jnp.asarray(r.normal(size=(batch, n, n)), jnp.float32)
    th = jnp.asarray(r.uniform(0, 6.28, (n, n)), jnp.float32)
    amp = jnp.ones((n, n), jnp.float32)
    fn = lambda a, b: kops.fused_spectral_hop(a, b, th, amp, th, amp)
    return fn, (ur, ui)


CELLS = (
    ("propagation_plan", _cell_propagation_plan),
    ("dse_batched", _cell_dse_batched),
    ("hetero", _cell_hetero),
    ("train_throughput", _cell_train_throughput),
    ("inference_throughput", _cell_inference_throughput),
    ("resilience", _cell_resilience),
    ("kernel_breakdown", _cell_kernel_breakdown),
)


def _bench_cell(name: str, make, peaks: dict, rows: list) -> dict:
    fn, args = make()
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    us = min(time_fn(compiled, *args, warmup=2, iters=5) for _ in range(3))
    flops = cost.get("flops")
    nbytes = cost.get("bytes accessed")
    if flops is None or nbytes is None:
        derived = (f"cost_model=unavailable(keys={sorted(cost)[:4]})"
                   if cost else "cost_model=unavailable")
        row(f"roofline/{name}", us, derived)
        rows.append({"name": f"roofline/{name}", "us": us,
                     "derived": derived})
        return {"fraction": None}
    sec = us / 1e6
    gflops = flops / sec / 1e9
    gbs = nbytes / sec / 1e9
    f_frac = gflops / peaks["peak_gflops"]
    b_frac = gbs / peaks["peak_gbs"]
    frac = max(f_frac, b_frac)
    bound = "compute" if f_frac >= b_frac else "memory"
    derived = (f"achieved={gflops:.2f}gflops/{gbs:.2f}gbs,"
               f"peak_frac={frac:.3f},bound={bound},"
               f"flops={flops:.3g},bytes={nbytes:.3g}")
    row(f"roofline/{name}", us, derived)
    rows.append({"name": f"roofline/{name}", "us": us, "derived": derived})
    return {"fraction": round(frac, 4), "bound": bound,
            "gflops": round(gflops, 3), "gbs": round(gbs, 3)}


def main():
    rows: list = []
    peaks = _measure_peaks()
    derived = (f"peak={peaks['peak_gflops']:.1f}gflops/"
               f"{peaks['peak_gbs']:.1f}gbs"
               "(measured:matmul+saxpy-microbench)")
    row("roofline/peaks", 0.0, derived)
    rows.append({"name": "roofline/peaks", "us": 0.0, "derived": derived})
    cells = {}
    for name, make in CELLS:
        cells[name] = _bench_cell(name, make, peaks, rows)
    write_bench_json(
        "roofline", rows,
        meta={"backend": jax.default_backend(),
              "peaks": {k: round(v, 3) for k, v in peaks.items()},
              "cells": cells},
    )


if __name__ == "__main__":
    main()
