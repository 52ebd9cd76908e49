"""A copy of the benchmark with one small fixture cell added as files.

``make(tmp)`` copies ``BENCHMARK.json`` and ``perfbench/`` into ``tmp``
and adds a CPU-sized configuration, two mixes and two cells by files and
entries alone, the way a later change adds a cell.  Tests drive the
harness on it with the chip check skipped.
"""
from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "name": "donn-tiny", "n": 64, "pixel_size": 3.6e-05,
    "wavelength": 5.32e-07, "distance": 0.05, "distances": None, "depth": 3,
    "approximation": "rs", "band_limit": True, "pad": False,
    "num_classes": 10, "det_size": 8, "detector_layout": "grid",
    "gamma": 1.12, "codesign": "qat", "device_levels": 256,
    "response_gamma": 1.0, "channels": 1, "segmentation": False,
    "skip_from": None, "layer_norm": False, "layers": None,
    "use_pallas": False, "engine": "scan", "input_size": 28,
    "scan_unroll": None, "tf_dtype": "float32", "remat": "none",
}
EMULATE_CELL = "tiny-emulate-b4"
SERVE_CELL = "tiny-serve-poisson"


def _dump(path: pathlib.Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=2) + "\n")


def make(tmp) -> pathlib.Path:
    root = pathlib.Path(tmp) / "checkout"
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    pb = root / "perfbench"
    _dump(pb / "configs" / "donn-tiny.json",
          {"source": "CPU-sized fixture", "reference": "donn_classifier",
           "fields": TINY})
    _dump(pb / "mixes" / "emulate_b4.json",
          {"kind": "emulate", "batch": 4, "pool": 16})
    _dump(pb / "mixes" / "poisson_r100.json",
          {"kind": "serve_poisson", "rate_hz": 100, "pool": 32,
           "replicas": 1, "gap_seed": 0})
    # the emulation fixture is held to the limit of the chip cell it
    # shrinks, so the CPU tests show that limit separating the program
    # from its control and from each planted fault; the serving fixture,
    # whose chip cell awaits its knee sweep, to the same limit
    cell = json.loads((pb / "cells" / "xl500-emulate-b64.json").read_text())
    _dump(pb / "cells" / f"{EMULATE_CELL}.json",
          {**cell, "trace_seconds": 0.5, "check_calls": 2})
    _dump(pb / "cells" / f"{SERVE_CELL}.json",
          {"limits": cell["limits"], "check_requests": 64,
           "trace_seconds": 0.5, "control": {"plane_dtype": "bfloat16"}})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "donn-tiny", "source": "https://arxiv.org/abs/2306.11268",
         "file": "perfbench/configs/donn-tiny.json", "reduced": ["n"],
         "why": "fixture"})
    bench["workloads"] += [
        {"name": EMULATE_CELL, "config": "donn-tiny",
         "traffic": "emulate_b4", "chips": 1, "why": "fixture"},
        {"name": SERVE_CELL, "config": "donn-tiny",
         "traffic": "poisson_r100", "chips": 1, "why": "fixture"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "samples_per_s" or m["name"].endswith(".emulate"):
            m["workloads"].append(EMULATE_CELL)
    # the serving cell brings its end-to-end metric and per-layer metrics
    # as entries; their readers are files under metrics/
    bench["end_to_end"].append(
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.2, "source": "host_clock", "workloads": [SERVE_CELL]})
    for name, unit, better, source, layer in (
            ("idle_share.serve", "%", "lower", "device_trace", "device"),
            ("device_ms_per_batch.serve", "ms", "lower", "device_trace",
             "engine"),
            ("mean_batch.serve", "req/dispatch", "higher", "program_counter",
             "service")):
        bench["per_layer"].append(
            {"name": name, "unit": unit, "better": better, "source": source,
             "layer": layer, "moves": "serve_p95_ms",
             "workloads": [SERVE_CELL]})
    _dump(root / "BENCHMARK.json", bench)
    return root
