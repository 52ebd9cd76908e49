"""One run of one cell: set up, measure a window, check, report.

``run_cell`` is driven by ``BENCHMARK.json`` and the files it names
(``spec``): the cell's configuration file says what to build and which
plain reference to compare with, its traffic mix names the module in
``traffic/<kind>.py`` that drives it and the parameters it reads, the cell's own file
holds the limits of the correctness check, and every metric is computed by
its reader in ``metrics/<name>.py``.  Nothing here knows a cell by name.

A traffic module provides::

    SPANS                         names of the host spans it records
    setup(ctx) -> state           build, load and warm up; counted as set-up
    window(state, seconds, span, tracer) -> dict
                                  the measured window, inside ``tracer``
    release(state)                free the program's state
    compare(state, result, reference, phases) -> {check name: value}

``variant`` swaps the timed path for the control ("control": the
program's own lower-precision path) or for a planted fault
("fault:<name>"); the benchmark's own runs use "program".
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from perfbench import spec, trace as trace_mod

FAULTS = ("altered_answer", "half_batch")


def fault_of(variant: str) -> Optional[str]:
    """The fault a "fault:<name>" variant plants, else None."""
    if not variant.startswith("fault:"):
        return None
    fault = variant.split(":", 1)[1]
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return fault


def plant(out, fault: Optional[str]):
    """One batch's answers (a row each) as ``fault`` leaves them where they
    are produced; a traffic kind calls it where its batches come back."""
    if fault is None:
        return out
    out = np.array(out)
    if fault == "altered_answer":
        out[0] = out[0][::-1]  # one answer's classes reversed
    elif len(out) > 1:  # half_batch: the second half is never computed
        half = len(out) // 2
        out[half:] = out[:len(out) - half]
    return out

@dataclasses.dataclass
class Context:
    """What a traffic module's ``setup`` gets."""

    cfg: object  # the program's DONNConfig, as the configuration file says
    fields: dict  # that configuration's fields
    mix: dict  # the traffic mix's parameters
    cell: dict  # the cell's own file
    seed: int
    params: dict  # phase masks made from the seed, on the device
    variant: str


@dataclasses.dataclass
class Run:
    """What a metric reader gets: one run's raw measurements."""

    fields: dict
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    samples: int  # input samples whose work completed in the window
    calls: int  # device calls (forwards or router dispatches) in it
    frozen: bool  # the masks were folded into planes ahead of time
    latencies_ms: Optional[list]  # per answered request, from its due time
    counters: dict  # program counters, as deltas over the window
    trace: Optional[dict]  # trace.reduce() of the traced window
    peak: Optional[dict]  # the device's row of peaks.json


def span(name: str):
    """A host span in the profiler's trace (no cost to speak of when the
    profiler is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """The measured window: a ``window`` span, traced when ``on``."""

    def __init__(self, on: bool, spans=()):
        self.on = on
        self.spans = spans
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-") if on else None
        self._span = None

    def __enter__(self):
        import jax

        if self.on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = span(trace_mod.WINDOW)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._span.__exit__(*exc)
        if self.on:
            jax.profiler.stop_trace()
        return False

    def read(self) -> Optional[dict]:
        if not self.on:
            return None
        try:
            events = trace_mod.load(trace_mod.find_xplane(self.dir),
                                    self.spans)
            return trace_mod.reduce(events)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def seed_key(seed: int):
    """A PRNG key holding 64 bits of state drawn from any whole seed."""
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(state.astype(np.uint32), impl="threefry2x32")


def make_params(seed: int, depth: int, n: int) -> dict:
    """Uniform phase masks on [0, 2 pi), made on the device in one call."""
    import jax
    import jax.numpy as jnp

    def build(key):
        phases = jax.random.uniform(key, (depth, n, n), jnp.float32, 0.0,
                                    2.0 * math.pi)
        return {"phase": {f"layer_{i}": phases[i] for i in range(depth)}}

    return jax.jit(build)(seed_key(seed))


def build_config(fields: dict, registry: Optional[str]):
    """The program's config from the file's fields; where the file names a
    registry entry, the two must be equal."""
    from repro.core.config import DONNConfig
    from repro.models.config import get_config

    cfg = DONNConfig(**fields)
    if registry is not None and get_config(registry) != cfg:
        raise ValueError(f"configuration file disagrees with the registry "
                         f"entry {registry!r}")
    return cfg


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@dataclasses.dataclass
class Cell:
    """A cell's entries and the files they name."""

    bench: dict
    workload: dict
    cfg_file: dict
    mix: dict
    cell: dict
    traffic: object
    reference: object


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = spec.load_benchmark(root)
    errs = spec.validate(bench)
    if errs:
        raise ValueError("BENCHMARK.json: " + "; ".join(errs))
    w = spec.workload(bench, workload)
    entry = spec.config_entry(bench, w["config"])
    with open(root / entry["file"]) as f:
        cfg_file = json.load(f)
    mix = spec.load_json(root, "mixes", w["traffic"])
    return Cell(bench=bench, workload=w, cfg_file=cfg_file, mix=mix,
                cell=spec.load_json(root, "cells", workload),
                traffic=spec.load_module(root, "traffic", mix["kind"]),
                reference=spec.load_module(root, "references",
                                           cfg_file["reference"]))


def make_context(c: Cell, seed: int, variant: str) -> Context:
    """The program's config (the control's where asked) and seeded masks."""
    fields = c.cfg_file["fields"]
    cfg = build_config(fields, c.cfg_file.get("registry"))
    if variant == "control":
        cfg = dataclasses.replace(cfg, **c.cell["control"].get("config", {}))
    return Context(cfg=cfg, fields=fields, mix=c.mix, cell=c.cell,
                   seed=seed, params=make_params(seed, cfg.depth, cfg.n),
                   variant=variant)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_chip: bool = True,
             variant: str = "program", out=None, err=None) -> dict:
    """Run one cell once; print its lines; return the result object.

    Raises ``SystemExit(1)`` where the chip the cell needs is missing.
    """
    out = out or sys.stdout
    err = err or sys.stderr
    root = pathlib.Path(root)
    c = load_cell(root, workload)
    w, cell, traffic = c.workload, c.cell, c.traffic
    metrics = spec.per_layer_for(c.bench, workload) if trace else \
        spec.e2e_for(c.bench, workload)
    readers = {m["name"]: spec.load_module(root, "metrics", m["name"])
               for m in metrics}
    with open(root / spec.BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)

    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < w["chips"]):
        print(f"[perfbench] {workload} needs {w['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s)", file=err)
        raise SystemExit(1)
    peak = peaks.get(devs[0].device_kind)
    if require_chip and peak is None:
        print(f"[perfbench] no peaks for device kind "
              f"{devs[0].device_kind!r} in peaks.json", file=err)
        raise SystemExit(1)

    ctx = make_context(c, seed, variant)
    cfg, fields, params = ctx.cfg, ctx.fields, ctx.params
    state = traffic.setup(ctx)
    setup_s = time.perf_counter() - t_start

    win_s = min(seconds, cell["trace_seconds"]) if trace else seconds
    tracer = Tracer(trace, traffic.SPANS)
    res = traffic.window(state, win_s, span, tracer)
    device = device_info(w["chips"])
    reduced = tracer.read()
    # the reference runs after the program's state is freed, so it
    # neither sets the memory peak nor competes with the program for it
    phases = np.stack([np.asarray(params["phase"][f"layer_{i}"])
                       for i in range(cfg.depth)])
    params.clear()
    traffic.release(state)
    reference = c.reference.Reference(fields)
    checks = traffic.compare(state, res, reference, phases)

    limits = cell["limits"]
    correct = all(k in limits and v <= limits[k] for k, v in checks.items())
    run = Run(fields=fields, setup_s=setup_s,
              window_s=res["window_s"], attempted=res["attempted"],
              failed=res["failed"], samples=res["samples"],
              calls=res["calls"], frozen=res["frozen"],
              latencies_ms=res.get("latencies_ms"),
              counters=res.get("counters", {}), trace=reduced, peak=peak)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": values,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": limits.get(k)}
                        for k, v in checks.items()}
    for line in res.get("notes", []):
        print(f"[perfbench] {line}", file=err)
    for k, v in checks.items():
        print(f"[perfbench] check {k} = {v!r} (limit {limits.get(k)!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
