"""Propagation engine: eager per-layer loop vs scan-based PropagationPlan.

Times the jit'd multi-layer forward (the paper's FFT2 / ComplexMM / iFFT2
hot path, Fig. 9) on the three workload shapes — classify, multi-channel
RGB, and segmentation-with-skip — with the per-layer eager loop
(``engine="eager"``, the seed's path) against the stacked ``lax.scan``
plan (``engine="scan"``, the default).

Two metrics per cell:

- ``first_call``: trace + compile + execute of a fresh jit — the cost every
  DSE candidate / fresh geometry pays.  The scan body is traced once
  regardless of depth, so this is where the engine wins (and the win grows
  with depth; steady-state HLO is identical work, XLA unrolls the eager
  loop into the same op sequence).
- ``steady``: post-compile per-call latency.

An unroll sweep (depth-16 classify cell, ``scan_unroll`` in {1, 2, 4, 8,
default}) tracks the steady-state trajectory of the scan-tuning knob
across PRs: the rolled loop (unroll=1) pays XLA:CPU while-loop overhead,
the tuned default (full unroll at this depth, ``default_scan_unroll``)
recovers it.

Rows print in the standard CSV schema and persist to
``artifacts/bench/BENCH_propagation_plan.json``.

    PYTHONPATH=src python benchmarks/bench_propagation_plan.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn, write_bench_json
from repro.core import DONNConfig, build_model
from repro.core.propagation import default_scan_unroll


CELLS = [
    ("classify", dict(name="cls", n=128, depth=16, distance=0.1, det_size=12),
     (8, 128, 128)),
    ("rgb", dict(name="rgb", n=64, depth=6, distance=0.05, det_size=8,
                 channels=3, num_classes=6), (8, 3, 64, 64)),
    ("segmentation", dict(name="seg", n=64, depth=6, distance=0.05,
                          segmentation=True, skip_from=1, layer_norm=True),
     (8, 64, 64)),
]


def _steady(fn, params, x, reps: int = 3, iters: int = 10) -> float:
    """min-of-reps steady-state timing (robust to shared-CPU noise)."""
    return min(
        time_fn(fn, params, x, warmup=1, iters=iters) for _ in range(reps)
    )


def _bench_cell(label: str, cfg_kw: dict, x_shape, rows: list):
    r = np.random.default_rng(0)
    x = jnp.asarray(r.uniform(0.0, 1.0, x_shape), jnp.float32)
    first, steady = {}, {}
    for engine in ("eager", "scan"):
        cfg = DONNConfig(**cfg_kw, engine=engine)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        # fresh jit per engine: first_call (compile) is part of the protocol
        fn = jax.jit(lambda p, xb: model.apply(p, xb))  # lightlint: disable=LR104
        t0 = time.perf_counter()
        jax.block_until_ready(fn(params, x))
        first[engine] = (time.perf_counter() - t0) * 1e6
        steady[engine] = _steady(fn, params, x)
        name = f"prop_plan/{label}/{engine}"
        derived = (f"first_call={first[engine]/1e6:.2f}s,"
                   f"depth={cfg.depth},n={cfg.n}")
        row(name, steady[engine], derived)
        rows.append({"name": name, "us": steady[engine], "derived": derived})
    sp_first = first["eager"] / first["scan"]
    sp_steady = steady["eager"] / steady["scan"]
    name = f"prop_plan/{label}/speedup"
    derived = (f"first_call_scan_vs_eager={sp_first:.2f}x,"
               f"steady_scan_vs_eager={sp_steady:.2f}x")
    row(name, steady["scan"], derived)
    rows.append({"name": name, "us": steady["scan"], "derived": derived})
    return {"first_call": round(sp_first, 3), "steady": round(sp_steady, 3)}


def _bench_unroll_sweep(rows: list) -> dict:
    """Steady-state unroll trajectory on the depth-16 classify cell."""
    label, cfg_kw, x_shape = CELLS[0]
    r = np.random.default_rng(0)
    x = jnp.asarray(r.uniform(0.0, 1.0, x_shape), jnp.float32)
    eager = build_model(DONNConfig(**cfg_kw, engine="eager"))
    params = eager.init(jax.random.PRNGKey(0))
    t_eager = _steady(jax.jit(lambda p, xb: eager.apply(p, xb)), params, x,
                      reps=5, iters=20)
    depth = DONNConfig(**cfg_kw).depth
    out = {}
    for unroll in (1, 2, 4, 8, None):
        cfg = DONNConfig(**cfg_kw, scan_unroll=unroll)
        model = build_model(cfg)
        # one distinct program per unroll factor: fresh jit is the point
        us = _steady(jax.jit(lambda p, xb: model.apply(p, xb)), params, x,  # lightlint: disable=LR104
                     reps=5, iters=20)
        eff = default_scan_unroll(depth) if unroll is None else unroll
        tag = "default" if unroll is None else str(unroll)
        name = f"prop_plan/unroll/{tag}"
        derived = (f"unroll={eff},steady_vs_eager={t_eager / us:.2f}x,"
                   f"depth={depth}")
        row(name, us, derived)
        rows.append({"name": name, "us": us, "derived": derived})
        out[tag] = round(t_eager / us, 3)
    return out


def main():
    rows: list = []
    speeds = {}
    for label, cfg_kw, x_shape in CELLS:
        speeds[label] = _bench_cell(label, cfg_kw, x_shape, rows)
    speeds["unroll_steady_vs_eager"] = _bench_unroll_sweep(rows)
    write_bench_json(
        "propagation_plan", rows,
        meta={"backend": jax.default_backend(), "speedups": speeds},
    )


if __name__ == "__main__":
    main()
