"""Render the README perf-trajectory table from BENCH_summary.json.

Reads the rolled-up benchmark summary (written by ``benchmarks/run.py``)
and prints a GitHub-markdown table of the headline speedup per tier-1
suite — the source of the table embedded in README.md.

    PYTHONPATH=src:. python tools/bench_table.py [path/to/BENCH_summary.json]
"""
from __future__ import annotations

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

def _pick(meta: dict, *keys) -> dict:
    """{cell: first present numeric key} over a suite's speedups meta."""
    out = {}
    for cell, v in meta.get("speedups", {}).items():
        if not isinstance(v, dict):
            if isinstance(v, (int, float)):
                out[cell] = v
            continue
        for k in keys:
            if isinstance(v.get(k), (int, float)):
                out[cell] = v[k]
                break
    return out


def _resilience_headline(meta: dict) -> str:
    """Not a speedup suite: headline the resilience numbers directly."""
    s = meta.get("summary", {})
    parts = []
    cold = s.get("cold_start", {}).get("load_warm_ms")
    if isinstance(cold, (int, float)):
        parts.append(f"cold_start {cold:g}ms")
    shed = s.get("overload", {}).get("shed_rate")
    if isinstance(shed, (int, float)):
        parts.append(f"shed_rate {shed:g}")
    noise = s.get("phase_noise", {})
    clean, worst = noise.get("clean"), noise.get("1.0")
    if isinstance(clean, (int, float)) and isinstance(worst, (int, float)):
        parts.append(f"acc {clean:g}->{worst:g} @ sigma 1.0")
    return ", ".join(parts)


def _serving_fleet_headline(meta: dict) -> str:
    """Latency-under-load + fault outcomes, not a speedup suite."""
    s = meta.get("summary", {})
    parts = []
    r2 = s.get("poisson", {}).get("r2", {})
    if isinstance(r2.get("p50_ms"), (int, float)):
        parts.append(f"p50 {r2['p50_ms']:g}ms / p99 {r2['p99_ms']:g}ms (r2)")
    win = s.get("continuous_vs_deadline", {}).get("p50_win")
    if isinstance(win, (int, float)):
        parts.append(f"continuous {win:g}x vs deadline")
    fk = s.get("failover_kill", {})
    if fk.get("dropped") == 0:
        parts.append("kill: 0 dropped")
    if s.get("drain_swap", {}).get("dropped") == 0:
        parts.append("swap: 0 dropped")
    return ", ".join(parts)


# suite -> (PR, headline metric extractor, description)
HEADLINES = {
    "propagation_plan": (
        "1-2", lambda m: _fmt_map(_pick(m, "steady"), "x"),
        "fused scan forward vs eager (steady state)"),
    "dse_batched": (
        "2", lambda m: _fmt_map(_pick(m, "speedup"), "x"),
        "K-candidate batched emulation vs sequential build+jit+run (cold)"),
    "hetero": (
        "3", lambda m: _fmt_map(_pick(m, "cold", "steady"), "x"),
        "ragged-depth batched DSE + segmented-plan forward"),
    "train_throughput": (
        "4", lambda m: _fmt_map(_pick(m, "steady", "speedup"), "x"),
        "chunked donated training vs seed-style per-step loop"),
    "inference_throughput": (
        "5", lambda m: _fmt_map(_pick(m, "steady_b32"), "x"),
        "frozen bucketed serving vs per-request apply (batch 32)"),
    "resilience": (
        "7", _resilience_headline,
        "overload shedding, artifact cold-start, phase-noise robustness"),
    "serving_fleet": (
        "9", _serving_fleet_headline,
        "continuous-batching fleet: Poisson latency, failover, warm swap"),
}


def _fmt_map(d: dict, suffix: str = "") -> str:
    items = [(k, v) for k, v in d.items() if isinstance(v, (int, float))]
    return ", ".join(f"{k} {v:g}{suffix}" for k, v in sorted(items))


def render(summary_path: pathlib.Path) -> str:
    summary = json.loads(summary_path.read_text())
    lines = [
        "| PR | suite | headline speedups | what it measures |",
        "|----|-------|-------------------|------------------|",
    ]
    order = sorted(HEADLINES, key=lambda s: HEADLINES[s][0])
    for suite in order:
        pr, extract, desc = HEADLINES[suite]
        cell = summary.get(suite)
        if cell is None:
            continue
        head = extract(cell.get("meta", {})) or "—"
        stale = " (stale)" if cell.get("stale") else ""
        lines.append(f"| {pr} | `{suite}`{stale} | {head} | {desc} |")
    return "\n".join(lines)


def render_plane_dtype(summary_path: pathlib.Path) -> str:
    """Quantized-plane serving table (family x plane dtype)."""
    summary = json.loads(summary_path.read_text())
    meta = summary.get("inference_throughput", {}).get("meta", {})
    cells = meta.get("speedups", {}).get("plane_dtype", {})
    lines = [
        "| family | plane dtype | req/s (b32) | max output delta vs f32 |",
        "|--------|-------------|-------------|-------------------------|",
    ]
    for family in sorted(cells):
        for dtype in ("float32", "bfloat16", "int8"):
            v = cells[family].get(dtype)
            if not isinstance(v, dict):
                continue
            rps = v.get("req_per_sec")
            delta = v.get("max_rel_delta")
            lines.append(
                f"| {family} | `{dtype}` | {rps:g} | {delta:.1e} |"
            )
    return "\n".join(lines) if len(lines) > 2 else ""


def render_serving_fleet(summary_path: pathlib.Path) -> str:
    """Latency-under-load table (scenario x p50/p99/outcome)."""
    summary = json.loads(summary_path.read_text())
    s = summary.get("serving_fleet", {}).get("meta", {}).get("summary", {})
    if not s:
        return ""
    inf = (summary.get("inference_throughput", {}).get("meta", {})
           .get("speedups", {}).get("latency_under_load", {}))
    lines = [
        "| scenario | p50 | p99 | outcome |",
        "|----------|-----|-----|---------|",
    ]

    def add(label, cell, outcome):
        p50, p99 = cell.get("p50_ms"), cell.get("p99_ms")
        if not isinstance(p50, (int, float)):
            return
        lines.append(f"| {label} | {p50:g}ms | {p99:g}ms | {outcome} |")

    if inf:
        add(f"50% util, 1 replica ({inf.get('rate_hz', '?'):g} req/s)",
            inf, "open-loop Poisson baseline")
    add("Poisson, 1 replica", s.get("poisson", {}).get("r1", {}), "healthy")
    add("Poisson, 2 replicas", s.get("poisson", {}).get("r2", {}), "healthy")
    cvd = s.get("continuous_vs_deadline", {})
    if isinstance(cvd.get("p50_continuous_ms"), (int, float)):
        lines.append(
            f"| continuous vs deadline batching "
            f"| {cvd['p50_continuous_ms']:g}ms vs "
            f"{cvd['p50_deadline_ms']:g}ms | — "
            f"| p50 win {cvd.get('p50_win', '?'):g}x |")
    fk = s.get("failover_kill", {})
    add("mid-run replica kill", fk,
        f"{fk.get('dropped', '?')} dropped, bit-identical retries")
    add("1 slow replica (25ms stall)", s.get("slow_replica", {}),
        "probation keeps the tail")
    ds = s.get("drain_swap", {})
    if isinstance(ds.get("swap_ms"), (int, float)):
        lines.append(
            f"| drain + rolling warm swap | swap {ds['swap_ms']:g}ms | — "
            f"| {ds.get('dropped', '?')} dropped, no admission gap |")
    return "\n".join(lines) if len(lines) > 2 else ""


START = "<!-- bench-table:start -->"
END = "<!-- bench-table:end -->"
PD_START = "<!-- plane-dtype-table:start -->"
PD_END = "<!-- plane-dtype-table:end -->"
FLEET_START = "<!-- serving-fleet-table:start -->"
FLEET_END = "<!-- serving-fleet-table:end -->"


def inject_readme(table: str, readme: pathlib.Path,
                  start: str = START, end: str = END) -> None:
    """Replace the marked block in README.md with the rendered table."""
    text = readme.read_text()
    if start not in text or end not in text:
        raise SystemExit(f"no {start}/{end} markers in {readme}")
    head, rest = text.split(start, 1)
    _, tail = rest.split(end, 1)
    readme.write_text(f"{head}{start}\n{table}\n{end}{tail}")
    print(f"# updated {readme} ({start})")


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    path = pathlib.Path(args[0]) if args else REPO / "BENCH_summary.json"
    table = render(path)
    pd_table = render_plane_dtype(path)
    fleet_table = render_serving_fleet(path)
    if "--write-readme" in sys.argv:
        inject_readme(table, REPO / "README.md")
        if pd_table:
            inject_readme(pd_table, REPO / "README.md", PD_START, PD_END)
        if fleet_table:
            inject_readme(fleet_table, REPO / "README.md",
                          FLEET_START, FLEET_END)
    else:
        print(table)
        for t in (pd_table, fleet_table):
            if t:
                print()
                print(t)


if __name__ == "__main__":
    main()
