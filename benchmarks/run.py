"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping (DESIGN.md §7):
  fig5+table3 -> bench_dse          fig7  -> bench_regularization
  fig8        -> bench_runtime      fig9  -> bench_kernel_breakdown
  fig10       -> bench_scaling      table4 -> bench_energy
  table5      -> bench_rgb          fig13 -> bench_segmentation
  hetero      -> bench_hetero (segmented plans + ragged-depth DSE)
  train_throughput -> bench_train_throughput (chunked training drivers)
  inference_throughput -> bench_inference_throughput (deployment engine)
  resilience  -> bench_resilience (overload shed, cold-start, noise curves)
  serving_fleet -> bench_serving_fleet (Poisson fleet latency, failover, swap)

Usage: ``python benchmarks/run.py [--check] [filter ...]`` — any number
of substring filters selects the suites to run (all when none given).

After the suites run, every ``artifacts/bench/BENCH_*.json`` artifact is
rolled up into a repo-top-level ``BENCH_summary.json`` (suite -> meta/
speedups), the per-PR perf-trajectory record CI uploads.  Artifacts a run
did not rewrite are marked ``stale``; ``--check`` (the CI gate) fails the
invocation when any *tier-1* suite cell is stale or missing, so partial
CI runs can't silently present old numbers as current — run every tier-1
suite in ONE invocation when checking.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import time
import traceback

# suites whose cells gate CI: they must be fresh in the uploaded summary
TIER1_SUITES = ("propagation_plan", "dse_batched", "hetero",
                "train_throughput", "inference_throughput", "resilience",
                "serving_fleet", "kernel_breakdown")


def stale_tier1(summary: dict) -> list:
    """Tier-1 suites that are stale or absent in a rolled-up summary."""
    return sorted(
        s for s in TIER1_SUITES
        if s not in summary or summary[s].get("stale", True)
    )


def write_summary(started_at: float, failed: list) -> pathlib.Path:
    """Roll artifacts/bench/BENCH_*.json metas up into ./BENCH_summary.json.

    Artifacts not rewritten by this invocation (filtered-out or failed
    suites still carry their committed numbers) are marked ``stale`` so
    the uploaded trajectory record never presents old numbers as current.
    """
    from benchmarks.common import ARTIFACTS

    root = ARTIFACTS.parent.parent
    summary = {"_failed_suites": sorted(failed)} if failed else {}
    for path in sorted(ARTIFACTS.glob("BENCH_*.json")):
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        summary[data.get("suite", path.stem)] = {
            "meta": data.get("meta", {}),
            "rows": len(data.get("rows", [])),
            "artifact": str(path.relative_to(root)),
            # floor() the threshold: coarse (1s) filesystem mtimes truncate
            # downward, so an artifact written the same second the run
            # started must still count as fresh (--check gates CI on this)
            "stale": path.stat().st_mtime < math.floor(started_at),
        }
    out = root / "BENCH_summary.json"
    out.write_text(json.dumps(summary, indent=2, sort_keys=True))
    print(f"# wrote {out}", flush=True)
    return out


def main() -> None:
    from repro.core.cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    from benchmarks import (
        bench_dse,
        bench_dse_batched,
        bench_energy,
        bench_hetero,
        bench_inference_throughput,
        bench_kernel_breakdown,
        bench_propagation_plan,
        bench_regularization,
        bench_resilience,
        bench_rgb,
        bench_runtime,
        bench_scaling,
        bench_segmentation,
        bench_serving_fleet,
        bench_train_throughput,
    )

    args = sys.argv[1:]
    check = "--check" in args
    filters = [a for a in args if not a.startswith("-")]
    suites = [
        ("fig8_runtime", bench_runtime.main),
        ("fig9_kernel_breakdown", bench_kernel_breakdown.main),
        ("propagation_plan", bench_propagation_plan.main),
        ("dse_batched", bench_dse_batched.main),
        ("hetero", bench_hetero.main),
        ("train_throughput", bench_train_throughput.main),
        ("inference_throughput", bench_inference_throughput.main),
        ("resilience", bench_resilience.main),
        ("serving_fleet", bench_serving_fleet.main),
        ("fig10_scaling", bench_scaling.main),
        ("fig7_regularization", bench_regularization.main),
        ("fig5_table3_dse", bench_dse.main),
        ("table4_energy", bench_energy.main),
        ("table5_rgb", bench_rgb.main),
        ("fig13_segmentation", bench_segmentation.main),
    ]
    started_at = time.time()
    failed: list = []
    for name, fn in suites:
        if filters and not any(f in name for f in filters):
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}", flush=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    out = write_summary(started_at, failed)
    if check:
        stale = stale_tier1(json.loads(out.read_text()))
        if stale:
            print(f"# STALE tier-1 bench cells: {', '.join(stale)} — "
                  "run those suites in this invocation", flush=True)
            sys.exit(1)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
