"""DONN serving launcher: freeze a trained model, serve a request stream.

The deployment end of the train -> freeze -> serve flow: builds a DONN
(optionally quick-trains it on the synthetic set), freezes it into a
``DeployedDONN`` artifact (codesign response + modulation planes folded
once), warms the bucketed AOT executables, then drives a synthetic
request load through the micro-batching dispatcher and reports
requests/sec plus latency percentiles — and the shed/expired counts when
the resilience knobs engage.

Artifact flow (``repro.runtime.resilience``): ``--save-artifact DIR``
persists the frozen deployment after freezing; ``--artifact DIR``
cold-starts serving from a previously saved artifact with **no model
build, training or freezing at all** — the crashed-replica recovery path.
The artifact's format version and architecture spec are validated
*before* any warmup, so a stale or corrupt artifact exits with a clear
error instead of failing mid-deploy.  ``--replicas N`` serves through the
continuous-batching ``FleetRouter`` (``repro.runtime.fleet``) over N
engine replicas instead of the single-engine ``MicroBatcher``.

Offline demo at laptop scale; the same engine objects back the
throughput benchmark (``benchmarks/bench_inference_throughput.py``).

Examples:
  PYTHONPATH=src python -m repro.launch.serve_donn --family classify \
      --n 64 --depth 4 --codesign qat --requests 256 --max-wait-ms 2 \
      --save-artifact /tmp/donn_artifact
  PYTHONPATH=src python -m repro.launch.serve_donn \
      --artifact /tmp/donn_artifact --requests 256 --max-queue 64 \
      --timeout-ms 20
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.core import DONNConfig, build_model
from repro.core.cache import use_persistent_compile_cache
from repro.runtime.inference import (
    DEFAULT_BUCKETS, InferenceEngine, MicroBatcher, freeze,
)
from repro.runtime.resilience import (
    DeadlineExceededError, OverloadedError, load_deployed, save_deployed,
    validate_artifact,
)


def build_cfg(args) -> DONNConfig:
    kw = dict(
        name=f"serve-{args.family}", n=args.n, depth=args.depth,
        distance=args.distance, det_size=args.det_size,
        codesign=args.codesign, response_gamma=args.response_gamma,
    )
    if args.family == "rgb":
        kw["channels"] = 3
    elif args.family == "segmentation":
        kw.update(segmentation=True, skip_from=0, layer_norm=True)
    return DONNConfig(**kw)


def main(argv=None):
    use_persistent_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="classify",
                    choices=("classify", "rgb", "segmentation"))
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--distance", type=float, default=0.05)
    ap.add_argument("--det-size", type=int, default=8)
    ap.add_argument("--codesign", default="qat")
    ap.add_argument("--response-gamma", type=float, default=1.2,
                    help="nonlinear device response (1.0 = ideal)")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="quick-train on synth digits before freezing")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--buckets", default=",".join(map(str, DEFAULT_BUCKETS)))
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission bound: beyond this, requests are shed "
                         "with OverloadedError (0 = unbounded)")
    ap.add_argument("--timeout-ms", type=float, default=0.0,
                    help="per-request deadline: undispatched requests fail "
                         "with DeadlineExceededError (0 = none)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip submit-time shape/dtype validation")
    ap.add_argument("--artifact", default=None,
                    help="serve from a saved artifact dir (skips build/"
                         "train/freeze entirely)")
    ap.add_argument("--save-artifact", default=None,
                    help="persist the frozen deployment to this dir")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="data-parallel dispatch over N devices (0 = off)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through a continuous-batching FleetRouter "
                         "over N replicas (0 = single MicroBatcher)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.artifact:
        # Validate format version + architecture spec BEFORE any engine
        # warmup, so a bad artifact exits cleanly instead of mid-deploy.
        try:
            meta = validate_artifact(args.artifact)
        except (FileNotFoundError, ValueError) as e:
            print(f"[serve_donn] ERROR: artifact {args.artifact!r} failed "
                  f"pre-deploy validation: {e}", file=sys.stderr)
            sys.exit(2)
        t0 = time.perf_counter()
        deployed = load_deployed(args.artifact)
        t_freeze = time.perf_counter() - t0
        print(f"[serve_donn] cold-started from {args.artifact} "
              f"(format {meta['format']}, family {meta['family']!r}) in "
              f"{t_freeze * 1e3:.0f}ms (no training state touched)")
        cfg = deployed.cfg
    else:
        cfg = build_cfg(args)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(args.seed))
        if args.train_steps > 0 and args.family == "classify":
            from repro.core.train_utils import train_classifier
            from repro.data import batch_iterator, synth_digits

            xs, ys = synth_digits(512, seed=args.seed)
            res = train_classifier(model, params,
                                   batch_iterator(xs, ys, 32, seed=1),
                                   steps=args.train_steps, lr=0.3,
                                   steps_per_call=8)
            params = res.params
            print(f"[serve_donn] trained {args.train_steps} steps "
                  f"({res.wall_time_s:.1f}s, final loss "
                  f"{res.losses[-1]:.4f})")

        t0 = time.perf_counter()
        deployed = freeze(model, params)
        jax.block_until_ready(deployed.frozen)
        t_freeze = time.perf_counter() - t0
    if args.save_artifact:
        save_deployed(deployed, args.save_artifact)
        print(f"[serve_donn] saved artifact to {args.save_artifact}")
    buckets = tuple(int(b) for b in args.buckets.split(","))
    n_replicas = max(args.replicas, 0)
    engines = []
    for _ in range(n_replicas or 1):
        engine = InferenceEngine(
            deployed, buckets=buckets,
            mesh_devices=args.mesh_devices or None,
        )
        compiles = engine.warmup()
        engines.append(engine)
    engine = engines[0]
    verb = "loaded" if args.artifact else "froze"
    print(f"[serve_donn] {verb} {cfg.name} in {t_freeze * 1e3:.0f}ms; "
          f"warmed {len(compiles)} buckets x{len(engines)} replica(s) in "
          f"{sum(compiles.values()):.2f}s")

    rng = np.random.default_rng(args.seed)
    n = cfg.input_size
    shape = ((cfg.channels, n, n) if deployed.family == "multi" else (n, n))
    reqs = [rng.random(shape, dtype=np.float32)
            for _ in range(args.requests)]

    if n_replicas:
        from repro.runtime.fleet import FleetRouter

        mb = FleetRouter(engines, max_queue=args.max_queue or None,
                         validate=not args.no_validate)
        print(f"[serve_donn] continuous-batching fleet: "
              f"{n_replicas} replica(s)")
    else:
        mb = MicroBatcher(engine, max_wait_ms=args.max_wait_ms,
                          max_queue=args.max_queue or None,
                          validate=not args.no_validate)
    timeout_ms = args.timeout_ms or None
    lat, shed, expired = [], 0, 0
    t0 = time.perf_counter()
    futs = []
    for x in reqs:
        try:
            futs.append((time.perf_counter(),
                         mb.submit(x, timeout_ms=timeout_ms)))
        except OverloadedError:
            shed += 1
    for t_sub, f in futs:
        try:
            f.result(timeout=120)
            lat.append(time.perf_counter() - t_sub)
        except DeadlineExceededError:
            expired += 1
    dt = time.perf_counter() - t0
    clean = mb.close()

    lat_ms = np.sort(np.asarray(lat)) * 1e3
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
    rps = len(lat) / dt
    print(f"[serve_donn] {len(lat)}/{args.requests} requests served in "
          f"{dt:.2f}s ({rps:.1f} req/s; p50 {p50:.1f}ms p99 {p99:.1f}ms; "
          f"shed {shed}, expired {expired}; "
          f"{sum(e.stats['batches'] for e in engines)} batches, "
          f"{sum(e.stats['padded_rows'] for e in engines)} padded rows, "
          f"mesh={args.mesh_devices or 1}, replicas={n_replicas or 1}, "
          f"clean_close={clean})")
    return rps


if __name__ == "__main__":
    main()
