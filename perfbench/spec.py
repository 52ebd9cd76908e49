"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric lives in a file of its own under the benchmark directory, so a
cell, a mix or a metric is added by adding files and entries, never by
editing code:

    configs/<config>.json     one model configuration, as it is run
    mixes/<traffic>.json      one traffic mix: the parameters its kind reads
    cells/<workload>.json     one cell: the limits of its correctness check
    traffic/<kind>.py         one general generator per traffic kind
    metrics/<metric>.py       one reader per metric: ``read(run)``
    references/<name>.py      a plain reference, named by a configuration

``validate`` holds ``BENCHMARK.json`` to its format rules.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

BENCH_DIR = "perfbench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
LAYER_SOURCES = {"device_trace", "program_span", "program_counter",
                 "host_clock"}


def load_benchmark(root: pathlib.Path) -> dict:
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(root: pathlib.Path, subdir: str, name: str) -> dict:
    """``<root>/perfbench/<subdir>/<name>.json``."""
    path = pathlib.Path(root) / BENCH_DIR / subdir / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def load_module(root: pathlib.Path, subdir: str, name: str):
    """Import ``<root>/perfbench/<subdir>/<name>.py`` by its file path.

    Metric names hold dots, so readers are loaded from their path and not
    through the import system's dotted names.
    """
    path = pathlib.Path(root) / BENCH_DIR / subdir / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {subdir} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{subdir}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def e2e_for(bench: dict, cell: str) -> list:
    """End-to-end metrics the cell reports (those without ``workloads``
    are reported by every cell)."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> list:
    """Per-layer metrics the cell reports in its traced run."""
    moved = {m["name"] for m in e2e_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in moved:
            out.append(m)
    return out


def _text_ok(s) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s)


def validate(bench: dict) -> list:
    """Format errors in ``BENCHMARK.json``; empty when sound."""
    errs = []
    if set(bench) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)}")
        return errs
    cmd = bench["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_text_ok(w) for w in cmd)):
        errs.append("command must be 1..32 one-line strings of 1..200 chars")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                errs.append(f"command word {w!r} leaves the checkout")
    paths = bench["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH_RE.match(p) for p in paths)):
        errs.append("paths must be 1..16 relative paths of [A-Za-z0-9_.-/]")
    rs = bench["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errs.append("run_seconds must be a whole number from 1 to 51")

    def check_name(kind, n):
        if not (isinstance(n, str) and NAME_RE.match(n)):
            errs.append(f"{kind} name {n!r} breaks the name rule")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    configs = bench["configs"]
    if not 1 <= len(configs) <= 24:
        errs.append("configs must hold 1..24 entries")
    files = set()
    for c in configs:
        if set(c) != CONFIG_KEYS:
            errs.append(f"config {c.get('name')!r} keys {sorted(c)}")
            continue
        check_name("config", c["name"])
        for k in ("source", "why"):
            if not _text_ok(c[k]):
                errs.append(f"config {c['name']} {k} must be one line")
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16):
            errs.append(f"config {c['name']} reduced must list <= 16 keys")
        else:
            for k in c["reduced"]:
                check_name("reduced key", k)
        if not under_paths(c["file"]) or c["file"] in files:
            errs.append(f"config {c['name']} file {c['file']!r} must lie "
                        "under paths and belong to it alone")
        files.add(c["file"])

    cells = bench["workloads"]
    if not 1 <= len(cells) <= 24:
        errs.append("workloads must hold 1..24 cells")
    cfg_names = {c["name"] for c in configs if "name" in c}
    pairs = set()
    for w in cells:
        if set(w) != WORKLOAD_KEYS:
            errs.append(f"workload {w.get('name')!r} keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            check_name(f"workload {k}", w[k])
        if w["config"] not in cfg_names:
            errs.append(f"workload {w['name']} names unknown config")
        if w["chips"] not in (1, 4):
            errs.append(f"workload {w['name']} chips must be 1 or 4")
        if not _text_ok(w["why"]):
            errs.append(f"workload {w['name']} why must be one line")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            errs.append(f"config/traffic pair {pair} appears twice")
        pairs.add(pair)
    n4 = sum(w.get("chips") == 4 for w in cells)
    if n4 > max(1, len(cells) // 2):
        errs.append("too many four-chip cells")
    used = {w.get("config") for w in cells}
    for c in cfg_names - used:
        errs.append(f"config {c} is used by no cell")
    cell_names = [w.get("name") for w in cells]

    def check_metric(m, keys, sources, kind):
        if not (keys <= set(m) <= keys | {"workloads"}):
            errs.append(f"{kind} metric {m.get('name')!r} keys {sorted(m)}")
            return False
        check_name(f"{kind} metric", m["name"])
        if not UNIT_RE.match(m["unit"]):
            errs.append(f"metric {m['name']} unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"metric {m['name']} better must be lower|higher")
        if m["source"] not in sources:
            errs.append(f"metric {m['name']} source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                errs.append(f"metric {m['name']} lists unknown cell {c}")
        return True

    e2e = bench["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        errs.append("end_to_end must hold 1..16 metrics")
    for m in e2e:
        if check_metric(m, E2E_KEYS, E2E_SOURCES, "end-to-end"):
            b = m["bound"]
            if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
                errs.append(f"metric {m['name']} bound must be 0.01..0.25")
    if "setup_s" not in {m.get("name") for m in e2e}:
        errs.append("end_to_end must hold setup_s")
    layer = bench["per_layer"]
    if not 1 <= len(layer) <= 128:
        errs.append("per_layer must hold 1..128 metrics")
    e2e_names = {m.get("name") for m in e2e}
    for m in layer:
        if check_metric(m, LAYER_KEYS, LAYER_SOURCES, "per-layer"):
            if not _text_ok(m["layer"]):
                errs.append(f"metric {m['name']} layer must be one line")
            if m["moves"] not in e2e_names:
                errs.append(f"metric {m['name']} moves unknown metric")
    names = [m.get("name") for m in e2e + layer]
    if len(names) != len(set(names)):
        errs.append("metric names repeat")
    if len(cell_names) != len(set(cell_names)):
        errs.append("workload names repeat")
    if len(cfg_names) != len(configs):
        errs.append("config names repeat")
    if errs:
        return errs
    for w in cell_names:
        reported = {m["name"] for m in e2e_for(bench, w)}
        if "setup_s" not in reported or len(reported) < 2:
            errs.append(f"cell {w} must report setup_s and another "
                        "end-to-end metric")
        if not per_layer_for(bench, w):
            errs.append(f"cell {w} reports no per-layer metric")
    for m in layer:
        for w in m.get("workloads", []):
            if m["moves"] not in {e["name"] for e in e2e_for(bench, w)}:
                errs.append(f"metric {m['name']} lists cell {w}, which "
                            f"does not report {m['moves']}")
    if len(json.dumps(bench).encode()) > 64 * 1024:
        errs.append("BENCHMARK.json exceeds 64 KiB")
    return errs
