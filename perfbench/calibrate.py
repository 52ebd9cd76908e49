"""Readings that set a cell's numbers, taken on the chip in one process.

    python3 perfbench/calibrate.py sweep --workload <serving cell> \
        --rates 500,1000,2000 --seconds 4 --seed <n>
    python3 perfbench/calibrate.py limits --workload <cell> \
        --seeds 1,2,3 --variants program,control --seconds 3

``sweep`` sets the cell up once and offers one open-loop window per rate,
reporting for each the answered rate, shed and failed requests, latency
percentiles and whether the backlog grew (the median latency of the last
quarter of the window against the first): the knee is the highest rate
answered in full with no growing backlog.  ``limits`` runs the cell once
per seed and variant ("program", "control", "fault:<name>") and prints
each checked number, the readings that the limits in ``cells/`` are set
from.  One JSON object per reading goes to standard output.
"""
import argparse
import io
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _pct(xs, q):
    import math

    xs = sorted(xs)
    return xs[math.ceil(q * len(xs)) - 1] if xs else None


def sweep(args) -> None:
    import numpy as np

    from perfbench import harness

    c = harness.load_cell(ROOT, args.workload)
    traffic, mix = c.traffic, c.mix
    ctx = harness.make_context(c, args.seed, "program")
    state = traffic.setup(ctx)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            state["mix"] = dict(mix, rate_hz=rate)
            res = traffic.window(state, args.seconds, harness.span,
                                harness.Tracer(False))
            lat = res["latencies_ms"]
            q = max(1, len(lat) // 4)
            print(json.dumps({
                "rate_hz": rate, "offered": res["attempted"],
                "answered": res["samples"], "failed": res["failed"],
                "answered_per_s": res["samples"] / res["window_s"],
                "p50_ms": _pct(lat, 0.5), "p95_ms": _pct(lat, 0.95),
                "p99_ms": _pct(lat, 0.99),
                "first_quarter_median_ms": float(np.median(lat[:q])),
                "last_quarter_median_ms": float(np.median(lat[-q:])),
                "mean_batch": res["counters"]["served"]
                / max(res["counters"]["dispatches"], 1),
                "notes": res["notes"]}), flush=True)
    finally:
        traffic.release(state)


def limits(args) -> None:
    from perfbench import harness

    for variant in args.variants.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            res = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, t_start=t0, variant=variant,
                                   out=io.StringIO(), err=io.StringIO())
            print(json.dumps({
                "variant": variant, "seed": seed,
                "checks": {k: v["value"] for k, v in res["checks"].items()},
                "attempted": res["attempted"], "failed": res["failed"],
                "run_s": time.perf_counter() - t0}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=4.0)
    s.add_argument("--seed", type=int, default=1)
    s = sub.add_parser("limits")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--variants", default="program,control")
    s.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax

    from repro.core.cache import use_persistent_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("[calibrate] readings are taken on the chip only",
              file=sys.stderr)
        return 1
    use_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    sweep(args) if args.cmd == "sweep" else limits(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
