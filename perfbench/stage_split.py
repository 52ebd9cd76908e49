"""Device time of one cell's window by stage of the forward.

    python3 perfbench/stage_split.py --workload <cell> --seed <n> \
        --seconds <s>

Sets the cell up as ``run.py`` does, measures a window of ``--seconds``
with the profiler off and then a window of the cell's ``trace_seconds``
with it on, and prints one JSON line:

- ``samples_per_s``: both windows' rates, whose difference is what the
  profiler and the program's spans cost;
- ``metrics``: the per-stage metrics (``STAGE_METRICS``, readers under
  ``metrics/``) and the cell's accepted per-layer metrics, each computed
  by its reader from the traced window;
- ``breakdown``: ``trace.reduce``'s ``device_ops`` and ``idle_gaps`` and
  the join's ``stages`` (self seconds per stage) and ``idle_gaps_program``
  (idle seconds per ``donn.*`` span);
- ``counters``: the program's ``compile_stats()`` delta over the traced
  window.

It checks no output (``run.py`` does).  Without a TPU it exits with 1.
"""
import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# metric -> unit
STAGE_METRICS = {
    "fft_ms_per_call.emulate": "ms",
    "elementwise_ms_per_call.emulate": "ms",
    "readout_ms_per_call.emulate": "ms",
    "fft_roofline.emulate": "%",
    "unscoped_share.emulate": "%",
    "compiles.emulate": "compiles",
}


def split(root, workload: str, seed: int, seconds: float, *,
          t_start: float, require_chip: bool = True) -> dict:
    """One untraced and one traced window of the cell; the result line."""
    import jax

    from perfbench import harness, spec, stages, trace
    from repro.core import propagation as pp

    root = pathlib.Path(root)
    c = harness.load_cell(root, workload)
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"[perfbench] {workload} needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    with open(root / spec.BENCH_DIR / "peaks.json") as f:
        peak = json.load(f).get(devs[0].device_kind)
    ctx = harness.make_context(c, seed, "program")
    state = c.traffic.setup(ctx)
    setup_s = time.perf_counter() - t_start

    plain = c.traffic.window(state, seconds, harness.span,
                             harness.Tracer(False))
    tracer = harness.Tracer(True, c.traffic.SPANS)
    before = pp.compile_stats()
    res = c.traffic.window(state, min(seconds, c.cell["trace_seconds"]),
                           harness.span, tracer)
    after = pp.compile_stats()
    smap = pp.stage_map()
    try:
        events = stages.load(trace.find_xplane(tracer.dir), c.traffic.SPANS)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    device = harness.device_info(c.workload["chips"])
    c.traffic.release(state)

    reduced = trace.reduce(events)
    if reduced is not None:
        reduced["stages"] = stages.stage_seconds(events, smap)
        reduced["idle_gaps_program"] = stages.program_gaps(events)
    counters = {k: after[k] - before[k] for k in after}
    run = harness.Run(
        fields=ctx.fields, setup_s=setup_s, window_s=res["window_s"],
        attempted=res["attempted"], failed=res["failed"],
        samples=res["samples"], calls=res["calls"], frozen=res["frozen"],
        latencies_ms=res.get("latencies_ms"),
        counters={**res.get("counters", {}),
                  "compiles": sum(counters.values())},
        trace=reduced, peak=peak)
    units = {**STAGE_METRICS,
             **{m["name"]: m["unit"]
                for m in spec.per_layer_for(c.bench, workload)}}
    metrics = {}
    for name, unit in units.items():
        v = spec.load_module(root, "metrics", name).read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit}
    out = {"workload": workload, "seed": seed, "setup_s": setup_s,
           "samples_per_s": {"untraced": plain["samples"] / plain["window_s"],
                             "traced": res["samples"] / res["window_s"]},
           "calls": res["calls"], "metrics": metrics, "counters": counters,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {k: reduced[k] for k in (
            "device_ops", "idle_gaps", "stages", "idle_gaps_program")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from repro.core.cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out = split(ROOT, args.workload, args.seed, args.seconds,
                t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
