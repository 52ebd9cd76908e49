"""Shared benchmark utilities. Every benchmark prints CSV rows:
``name,us_per_call,derived`` (derived = the paper-facing quantity).

Benchmarks that feed the perf trajectory additionally persist their rows
as ``artifacts/bench/BENCH_<suite>.json`` via ``write_bench_json`` so
tooling can diff numbers across PRs.
"""
from __future__ import annotations

import importlib
import json
import os
import pathlib
import subprocess
import sys
import time

import jax

REPO = pathlib.Path(__file__).resolve().parent.parent
ARTIFACTS = REPO / "artifacts" / "bench"


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Wall-time a callable returning jax arrays; us per call."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def time_host_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Wall-time a pure-host (numpy) callable; us per call."""
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6


def row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


def write_bench_json(suite: str, rows: list, meta: dict | None = None):
    """Persist ``BENCH_<suite>.json``: {suite, meta, rows:[{name,us,derived}]}."""
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = ARTIFACTS / f"BENCH_{suite}.json"
    path.write_text(json.dumps(
        {"suite": suite, "meta": meta or {}, "rows": rows}, indent=2,
    ))
    print(f"# wrote {path}", flush=True)
    return path


def mesh_dims(n_devices: int) -> tuple:
    """(data, model) split of ``n_devices``: model takes half, data the rest."""
    model = max(n_devices // 2, 1)
    return n_devices // model, model


def run_multi_device_cell(module: str, fn: str, cpu_devices: int) -> dict:
    """Run ``module.fn(n_devices) -> dict`` for a cell that needs a mesh.

    On TPU the cell runs in this process on the real chips: this process
    already holds them, so a child process could not reach them.  On CPU
    it runs in a child with ``cpu_devices`` forced host devices, because
    the host device count is fixed when the backend starts.
    """
    if jax.default_backend() == "tpu":
        return getattr(importlib.import_module(module), fn)(jax.device_count())
    code = (f"import json; from {module} import {fn}; "
            f"print('RESULT ' + json.dumps({fn}({cpu_devices})))")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cpu_devices}"
    env["PYTHONPATH"] = os.pathsep.join((str(REPO / "src"), str(REPO)))
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"{module}.{fn} failed:\n{r.stderr}")
    return json.loads(r.stdout.split("RESULT ")[1])
