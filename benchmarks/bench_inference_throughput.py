"""Deployment serving throughput: frozen bucketed engine vs per-request apply.

The naive way to serve a trained DONN is the training-path forward per
request: a jit dispatch per call, codesign quantization re-applied every
time (a 256-level argmin per element for realistic nonlinear-response
devices), ``exp(j theta)`` and the phase stack rebuilt per call, batch 1.
The deployment engine (``repro.runtime.inference``) freezes all of that
once and serves shape-bucketed, donated, micro-batched AOT executables.

Cells (CPU; honest on a 2-core container — batching wins come from
dispatch amortization + batched FFT, the big win from the codesign fold):

- ``infer/<family>/b<B>``: steady-state requests/sec at bucket B through
  the warmed engine vs the *warm* per-request jitted apply loop (the
  steady baseline — a fresh-jit baseline would flatter us) — plus honest
  ``cold`` rows: first-request latency, naive (trace+compile+run on
  request 1) vs engine (freeze + ``warmup()`` paid at deploy, then a warm
  first request).
- ``classify_plain``: codesign="none" — no fold win, isolates pure
  batching/dispatch gains (below the 5x headline; reported honestly).
- ``classify_qat_nl``: 8-bit SLM with measured-style nonlinear response
  (response_gamma=1.2) — the LightRidge deployment story; the codesign
  fold dominates (the acceptance >= 5x cell, in practice ~100x+).
- depth sweep (4/8/16) and the RGB / segmentation families.
- ``plane_dtype``: quantized frozen planes (f32 / bf16 / int8) per model
  family — serving req/s and max output delta vs the f32 engine (bf16
  gated at 5e-2; int8 measured and reported).
- ``micro_batcher``: end-to-end dispatcher (queue + deadline) req/s.
- ``latency_under_load``: p50/p99 submit-to-result latency of the
  continuous-batching fleet under open-loop Poisson arrivals at ~50% of
  measured capacity (the open-loop complement to the closed-loop rows).
- ``multi_device``: batch-DP engine vs single-device engine outputs
  (rtol <= 1e-5) and req/s — on CPU in a subprocess with 4 forced host
  devices (which oversubscribe the cores, so scaling is not expected to
  be linear there; the row pins layout correctness + agreement), on TPU
  in-process over the chips present.

Every family checks frozen outputs bit-identical to the training-path
(eval) forward.  Rows persist to
``artifacts/bench/BENCH_inference_throughput.json``.

    PYTHONPATH=src:. python benchmarks/bench_inference_throughput.py
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import (
    mesh_dims, row, run_multi_device_cell, write_bench_json,
)
from repro.core import DONNConfig, build_model
from repro.runtime.inference import InferenceEngine, MicroBatcher, freeze


def _requests(count, shape, seed=0):
    return np.random.default_rng(seed).random((count,) + shape, np.float32)


def _per_request_loop(apply_fn, params, reqs):
    """The naive serving loop: one jitted call + host sync per request."""
    t0 = time.perf_counter()
    for i in range(reqs.shape[0]):
        np.asarray(apply_fn(params, reqs[i:i + 1]))
    return time.perf_counter() - t0


def _engine_loop(engine, reqs, bucket):
    """Steady engine serving: warmed bucket executables, batches of B."""
    t0 = time.perf_counter()
    for lo in range(0, reqs.shape[0], bucket):
        engine.infer(reqs[lo:lo + bucket])
    return time.perf_counter() - t0


def _bench_family(label, cfg, rows, buckets=(1, 8, 32), n_reqs=64,
                  x_shape=(28, 28), reps=2) -> dict:
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(n_reqs, x_shape, seed=1)

    # --- cold: what request 1 costs each way ---
    t0 = time.perf_counter()
    apply_fn = jax.jit(lambda p, x: model.apply(p, x))
    np.asarray(apply_fn(params, reqs[:1]))  # trace+compile+run
    naive_cold_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    deployed = freeze(model, params)
    jax.block_until_ready(deployed.frozen)
    engine = InferenceEngine(deployed, buckets=buckets)
    engine.warmup()
    deploy_s = time.perf_counter() - t0  # paid once, at deploy time
    t0 = time.perf_counter()
    engine.infer(reqs[:1])
    engine_first_s = time.perf_counter() - t0

    # --- bit-identity: frozen serving == training-path forward at eval,
    # compared at equal batch shape (batch == bucket; XLA retiles the
    # detector contraction per batch shape, so cross-shape comparisons are
    # the padding criterion below, not the bit criterion) ---
    b_chk = buckets[-1]
    got = engine.infer(reqs[:b_chk])
    ref = np.asarray(apply_fn(params, reqs[:b_chk]))
    bit_identical = bool(np.array_equal(got, ref))
    # --- bucket padding: partially-filled buckets match per-sample apply ---
    got_pad = engine.infer(reqs[:3])
    ref_pad = np.asarray(apply_fn(params, reqs[:3]))
    pad_rel = float(np.max(np.abs(got_pad - ref_pad))
                    / max(np.max(np.abs(ref_pad)), 1e-12))
    padded_ok = pad_rel <= 1e-5

    # --- steady-state: warm loops, best of reps ---
    naive_s = min(_per_request_loop(apply_fn, params, reqs)
                  for _ in range(reps))
    naive_rps = n_reqs / naive_s
    name = f"infer/{label}/per_request"
    derived = f"req_per_sec={naive_rps:.1f},batch=1,warm_jit=True"
    row(name, naive_s / n_reqs * 1e6, derived)
    rows.append({"name": name, "us": naive_s / n_reqs * 1e6,
                 "derived": derived})

    speedups = {}
    for b in buckets:
        eng_s = min(_engine_loop(engine, reqs, b) for _ in range(reps))
        rps = n_reqs / eng_s
        speedups[b] = rps / naive_rps
        name = f"infer/{label}/b{b}"
        derived = (f"req_per_sec={rps:.1f},vs_per_request="
                   f"{speedups[b]:.2f}x,bit_identical={bit_identical}")
        row(name, eng_s / n_reqs * 1e6, derived)
        rows.append({"name": name, "us": eng_s / n_reqs * 1e6,
                     "derived": derived})

    name = f"infer/{label}/cold"
    derived = (f"naive_first_req_s={naive_cold_s:.3f},"
               f"deploy_freeze_warmup_s={deploy_s:.3f},"
               f"engine_first_req_s={engine_first_s:.4f}")
    row(name, naive_cold_s * 1e6, derived)
    rows.append({"name": name, "us": naive_cold_s * 1e6, "derived": derived})
    if not bit_identical or not padded_ok:
        raise AssertionError(
            f"{label}: bit_identical={bit_identical} pad_rel={pad_rel:.2e}"
        )
    return {"steady_b32": round(speedups.get(32, 0.0), 2),
            "speedups": {f"b{b}": round(s, 2) for b, s in speedups.items()},
            "req_per_sec_naive": round(naive_rps, 1),
            "bit_identical": bit_identical,
            "padded_rel_err": pad_rel,
            "engine_first_req_s": round(engine_first_s, 4)}


def _bench_plane_dtypes(rows) -> dict:
    """Quantized frozen planes: serving accuracy delta + req/s per dtype.

    The f32 path is the bit-identity baseline (``plane_dtype="float32"``
    is the default ``freeze`` — its identity against the training-path
    forward is pinned by every ``_bench_family`` cell above).  bf16 must
    stay within the documented 5e-2 output tolerance; int8 is measured
    and reported, not gated.
    """
    mk = lambda name, **kw: DONNConfig(
        name=name, distance=0.05, det_size=8, **kw
    )
    families = [
        ("classify", mk("pd-cls", n=64, depth=8, codesign="qat",
                        response_gamma=1.2), (28, 28)),
        ("rgb", mk("pd-rgb", n=64, depth=4, channels=3, codesign="qat",
                   response_gamma=1.2), (3, 28, 28)),
        ("segmentation", mk("pd-seg", n=64, depth=4, segmentation=True,
                            skip_from=0, layer_norm=True, codesign="qat",
                            response_gamma=1.2), (28, 28)),
    ]
    out = {}
    for label, cfg, x_shape in families:
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        reqs = _requests(32, x_shape, seed=4)
        ref = None
        fam = {}
        for dtype in ("float32", "bfloat16", "int8"):
            engine = InferenceEngine(
                freeze(model, params, plane_dtype=dtype), buckets=(32,)
            )
            engine.warmup()
            got = engine.infer(reqs)
            if dtype == "float32":
                ref = got
            dt = min(_engine_loop(engine, reqs, 32) for _ in range(2))
            rps = reqs.shape[0] / dt
            delta = float(np.max(np.abs(got - ref))
                          / max(np.max(np.abs(ref)), 1e-12))
            derived = f"req_per_sec={rps:.1f},max_rel_delta={delta:.2e}"
            if not cfg.segmentation:
                match = float(np.mean(
                    np.argmax(got, -1) == np.argmax(ref, -1)
                ))
                derived += f",argmax_match={match:.2f}"
            name = f"infer/plane_dtype/{label}/{dtype}"
            row(name, dt / reqs.shape[0] * 1e6, derived)
            rows.append({"name": name, "us": dt / reqs.shape[0] * 1e6,
                         "derived": derived})
            if dtype == "bfloat16" and delta > 5e-2:
                raise AssertionError(
                    f"{label}: bf16 plane delta {delta:.2e} > 5e-2"
                )
            fam[dtype] = {"req_per_sec": round(rps, 1),
                          "max_rel_delta": delta}
        out[label] = fam
    return out


def _bench_micro_batcher(rows) -> dict:
    """End-to-end dispatcher: single-image submits, deadline batching."""
    cfg = DONNConfig(name="inf-mb", n=64, depth=8, distance=0.05, det_size=8,
                     codesign="qat", response_gamma=1.2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(freeze(model, params), buckets=(8, 32))
    engine.warmup()
    reqs = _requests(128, (28, 28), seed=2)
    mb = MicroBatcher(engine, max_wait_ms=2.0)
    t0 = time.perf_counter()
    futs = [mb.submit(reqs[i]) for i in range(reqs.shape[0])]
    for f in futs:
        f.result(timeout=300)
    dt = time.perf_counter() - t0
    mb.close()
    rps = reqs.shape[0] / dt
    name = "infer/micro_batcher/submit_to_result"
    derived = (f"req_per_sec={rps:.1f},batches={engine.stats['batches']},"
               f"padded_rows={engine.stats['padded_rows']},max_wait_ms=2")
    row(name, dt / reqs.shape[0] * 1e6, derived)
    rows.append({"name": name, "us": dt / reqs.shape[0] * 1e6,
                 "derived": derived})
    return {"req_per_sec": round(rps, 1),
            "batches": engine.stats["batches"]}


def _bench_latency_under_load(rows) -> dict:
    """p50/p99 latency under open-loop Poisson load at ~50% utilization.

    The throughput cells above measure closed-loop batch serving; real
    traffic is open-loop.  This cell measures the continuous-batching
    fleet (``repro.runtime.fleet``) at half of the measured closed-loop
    capacity — the latency a user sees from a healthily-provisioned
    deployment (the saturated and faulted regimes live in
    ``bench_serving_fleet``).
    """
    from benchmarks.bench_serving_fleet import _percentiles, _poisson_load
    from repro.runtime.fleet import FleetRouter

    cfg = DONNConfig(name="inf-load", n=64, depth=8, distance=0.05,
                     det_size=8, codesign="qat", response_gamma=1.2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dep = freeze(model, params)
    bucket = 8
    engine = InferenceEngine(dep, buckets=(bucket,))
    engine.warmup()
    reqs = _requests(64, (28, 28), seed=6)
    cap_s = min(_engine_loop(engine, reqs, bucket) for _ in range(2))
    cap_rps = reqs.shape[0] / cap_s
    rate_hz = cap_rps / 2.0

    router = FleetRouter([engine])
    lat, _, shed, failed = _poisson_load(router, list(reqs), rate_hz, seed=7)
    router.close()
    if shed or failed:
        raise AssertionError(
            f"under-provisioned? shed={shed} failed={failed} at 50% load"
        )
    p50, p99 = _percentiles(lat)
    name = "infer/latency_under_load/p50_p99"
    derived = (f"p50_ms={p50:.2f},p99_ms={p99:.2f},"
               f"rate_hz={rate_hz:.0f},capacity_rps={cap_rps:.0f},"
               f"utilization=0.5,continuous_batching=True")
    row(name, p50 * 1e3, derived)
    rows.append({"name": name, "us": p50 * 1e3, "derived": derived})
    return {"p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
            "rate_hz": round(rate_hz, 1),
            "capacity_rps": round(cap_rps, 1)}


def _serving_loop(engine, reqs, bucket) -> float:
    """Best-of-2 closed-loop req/s through a warmed engine."""
    engine.warmup()
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        for lo in range(0, reqs.shape[0], bucket):
            engine.infer(reqs[lo:lo + bucket])
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return reqs.shape[0] / best


def multi_device_cell(n_devices: int) -> dict:
    """Batch-DP engine over ``n_devices`` vs the single-device engine."""
    assert jax.device_count() >= n_devices, jax.device_count()
    cfg = DONNConfig(name="inf-dp", n=64, depth=8, distance=0.05,
                     det_size=8, codesign="qat")
    model = build_model(cfg)
    dep = freeze(model, model.init(jax.random.PRNGKey(0)))
    reqs = _requests(64, (28, 28), seed=3)
    e1 = InferenceEngine(dep, buckets=(32,))
    edp = InferenceEngine(dep, buckets=(32,), mesh_devices=n_devices,
                          dp_min_bucket=8)
    rps1, rpsdp = _serving_loop(e1, reqs, 32), _serving_loop(edp, reqs, 32)
    a, b = e1.infer(reqs[:32]), edp.infer(reqs[:32])
    rel = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    return {"rps_single": rps1, "rps_dp": rpsdp, "rel_err": rel,
            "devices": n_devices}


def _bench_multi_device(rows) -> dict:
    """Batch-DP engine vs single device (4 forced host devices on CPU)."""
    res = run_multi_device_cell("benchmarks.bench_inference_throughput",
                                "multi_device_cell", cpu_devices=4)
    ok = res["rel_err"] <= 1e-5
    nd = res["devices"]
    name = f"infer/multi_device/dp{nd}_vs_single"
    derived = (f"rps_single={res['rps_single']:.1f},"
               f"rps_dp{nd}={res['rps_dp']:.1f},"
               f"rel_err={res['rel_err']:.2e},within_1e-5={ok},"
               f"devices={nd},backend={jax.default_backend()}")
    row(name, 1e6 / res["rps_dp"], derived)
    rows.append({"name": name, "us": 1e6 / res["rps_dp"],
                 "derived": derived})
    if not ok:
        raise AssertionError(f"dp{nd} rel err {res['rel_err']} > 1e-5")
    return {"rel_err": res["rel_err"],
            "rps_single": round(res["rps_single"], 1),
            f"rps_dp{nd}": round(res["rps_dp"], 1)}


def sharded_serving_cell(n_devices: int) -> dict:
    """Row-sharded (data x model) engine vs the single-device engine."""
    assert jax.device_count() >= n_devices, jax.device_count()
    data, mp = mesh_dims(n_devices)
    cfg = DONNConfig(name="inf-mp", n=256, depth=4, det_size=16,
                     codesign="qat")
    model = build_model(cfg)
    dep = freeze(model, model.init(jax.random.PRNGKey(0)))
    reqs = _requests(32, (28, 28), seed=5)
    e1 = InferenceEngine(dep, buckets=(8,))
    emp = InferenceEngine(dep, buckets=(8,), mesh_devices=data,
                          model_devices=mp, dp_min_bucket=8)
    rps1, rpsmp = _serving_loop(e1, reqs, 8), _serving_loop(emp, reqs, 8)
    a, b = e1.infer(reqs[:8]), emp.infer(reqs[:8])
    rel = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    bit = bool(np.array_equal(b, emp.infer(reqs[:8])))
    return {"rps_single": rps1, "rps_sharded": rpsmp, "rel_err": rel,
            "bit_consistent": bit, "mesh": [data, mp],
            "rows_per_device": cfg.n // mp}


def _bench_sharded_serving(rows) -> dict:
    """Row-sharded serving: (data x model) mesh vs single device.

    ``InferenceEngine(model_devices=k)`` shards the frozen modulation
    stacks, TF planes and detector masks over the ``model`` axis (each
    device serves from a 1/k-plane pencil, pencil-FFT hops) while
    buckets >= ``dp_min_bucket`` also shard the batch over ``data`` —
    the ISSUE-10 serving row (2 data x 4 model on 8 forced host devices
    on CPU; the chips present on TPU).  Checks rtol <= 1e-5 vs the
    single-device engine and bit-consistency across repeated sharded
    calls.
    """
    res = run_multi_device_cell("benchmarks.bench_inference_throughput",
                                "sharded_serving_cell", cpu_devices=8)
    ok = res["rel_err"] <= 1e-5 and res["bit_consistent"]
    data, mp = res["mesh"]
    name = f"infer/sharded_serving/{data}data_x_{mp}model_vs_single"
    derived = (f"rps_single={res['rps_single']:.1f},"
               f"rps_sharded={res['rps_sharded']:.1f},"
               f"rel_err={res['rel_err']:.2e},"
               f"bit_consistent={res['bit_consistent']},n=256,"
               f"rows_per_device={res['rows_per_device']},"
               f"backend={jax.default_backend()}")
    row(name, 1e6 / res["rps_sharded"], derived)
    rows.append({"name": name, "us": 1e6 / res["rps_sharded"],
                 "derived": derived})
    if not ok:
        raise AssertionError(f"sharded serving check failed: {res}")
    return {"rel_err": res["rel_err"],
            "bit_consistent": res["bit_consistent"],
            "rps_single": round(res["rps_single"], 1),
            "rps_sharded": round(res["rps_sharded"], 1)}


def main() -> None:
    rows: list = []
    mk = lambda name, **kw: DONNConfig(
        name=name, distance=0.05, det_size=8, **kw
    )
    speedups = {
        # the deployment headline: quantized nonlinear-response device,
        # codesign folded out of the hot path at freeze time
        "classify_qat_nl": _bench_family(
            "classify_qat_nl",
            mk("inf-qnl", n=100, depth=8, codesign="qat",
               response_gamma=1.2),
            rows, n_reqs=64),
        # no codesign: batching + dispatch amortization only (honest row)
        "classify_plain": _bench_family(
            "classify_plain", mk("inf-plain", n=100, depth=8), rows,
            buckets=(32,), n_reqs=64),
        # depth sweep at the qat_nl cell's geometry
        "classify_d4": _bench_family(
            "classify_d4",
            mk("inf-d4", n=64, depth=4, codesign="qat", response_gamma=1.2),
            rows, buckets=(32,), n_reqs=64),
        "classify_d16": _bench_family(
            "classify_d16",
            mk("inf-d16", n=64, depth=16, codesign="qat",
               response_gamma=1.2),
            rows, buckets=(32,), n_reqs=64),
        # the other two model families
        "rgb": _bench_family(
            "rgb", mk("inf-rgb", n=64, depth=4, channels=3,
                      codesign="qat", response_gamma=1.2),
            rows, buckets=(8, 32), n_reqs=32, x_shape=(3, 28, 28)),
        "segmentation": _bench_family(
            "segmentation",
            mk("inf-seg", n=64, depth=4, segmentation=True, skip_from=0,
               layer_norm=True, codesign="qat", response_gamma=1.2),
            rows, buckets=(8, 32), n_reqs=32),
        "plane_dtype": _bench_plane_dtypes(rows),
        "micro_batcher": _bench_micro_batcher(rows),
        "latency_under_load": _bench_latency_under_load(rows),
        "multi_device": _bench_multi_device(rows),
        "sharded_serving": _bench_sharded_serving(rows),
    }
    meta = {
        "backend": jax.default_backend(),
        "cores": os.cpu_count(),
        "speedups": speedups,
    }
    write_bench_json("inference_throughput", rows, meta)


if __name__ == "__main__":
    main()
