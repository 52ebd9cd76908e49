"""Training throughput: donated multi-step scanned driver vs per-step loop.

The seed trains DONNs with a per-batch Python loop: every step pays a jit
dispatch, a host rng split, a non-donated state re-allocation and two
blocking ``float()`` syncs.  The throughput engine makes *chunks* the unit
of compiled work: ``make_train_chunk`` scans ``steps_per_call`` optimizer
steps inside one jit with (params, opt_state) donated, metrics accumulate
on device, and the double-buffered device prefetcher keeps batch k+1 in
flight while step k computes.

Cells (CPU, depth-8 / n=64 classify — the ISSUE-4 acceptance cell), each
with two baselines so the win is attributable:

- ``per_step`` (the *seed-style* number): a fresh ``@jax.jit`` step
  closure per training run, exactly what the seed's ``train_classifier``
  builds on every call — so each run re-pays trace+compile, the overhead
  the executable cache kills.  Best-of-reps = its steady state.
- ``per_step_warm``: the same loop with the step closure hoisted across
  runs (compile excluded entirely) — the pure per-step host overhead
  (jit dispatch, host rng split, two blocking ``float()`` syncs,
  non-donated state realloc) vs the chunked driver.  At this cell's
  sizes the FFT chain dominates per-step compute, so this ratio is the
  conservative lower bound (batch 2 is the overhead-dominated regime,
  batch 8 compute-bound; on accelerators the crossover batch is far
  larger).

``train/segmentation`` and ``train/rng_codesign`` cover the other two
training families through the chunked drivers (agreement + a smaller
timing).  Every cell checks the chunked final params against the
per-step loop (identical rng chain; max |delta| / max |ref| <= 1e-5, in
practice bit-exact).  Rows persist to
``artifacts/bench/BENCH_train_throughput.json``.

    PYTHONPATH=src:. python benchmarks/bench_train_throughput.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    mesh_dims, row, run_multi_device_cell, write_bench_json,
)
from repro.core import DONNConfig, build_model
from repro.core.train_utils import (
    accuracy, make_train_chunk, mse_softmax_loss,
)
from repro.data import batch_iterator, synth_digits, synth_seg
from repro.data.pipeline import device_prefetch, stack_batches
from repro.optim import AdamW


def _seed_style_step(model, optimizer, num_classes: int,
                     needs_rng: bool = False):
    """The seed's train step: plain per-closure jit, no donation/caching."""

    def loss_fn(params, xb, yb, rng):
        logits = (model.apply(params, xb, rng) if needs_rng
                  else model.apply(params, xb))
        return mse_softmax_loss(logits, yb, num_classes), logits

    @jax.jit
    def step_fn(params, opt_state, step, xb, yb, rng):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, xb, yb, rng
        )
        params, opt_state = optimizer.update(grads, opt_state, params, step)
        return params, opt_state, loss, accuracy(logits, yb)

    return step_fn


def _per_step_loop(step_fn, optimizer, params, it, steps: int):
    """Seed-style loop: host rng split + two float() syncs per step."""
    opt_state = optimizer.init(params)
    params = jax.tree.map(jnp.array, params)
    rng = jax.random.PRNGKey(0)
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        xb, yb = next(it)
        rng, sub = jax.random.split(rng)
        params, opt_state, loss, acc = step_fn(
            params, opt_state, jnp.asarray(i), xb, yb, sub
        )
        losses.append(float(loss))
        float(acc)
    return params, losses, time.perf_counter() - t0


def _chunked_loop(chunk_fn, optimizer, params, it, steps: int,
                  steps_per_call: int):
    """Chunked driver fed by the device prefetcher; one sync per chunk."""
    opt_state = optimizer.init(params)
    params = jax.tree.map(jnp.array, params)
    opt_state = jax.tree.map(jnp.array, opt_state)
    rng = jax.random.PRNGKey(0)
    losses = []
    i = 0
    t0 = time.perf_counter()
    chunks = device_prefetch(stack_batches(it, steps_per_call, total=steps))
    for xs, ys in chunks:
        params, opt_state, rng, closs, cacc = chunk_fn(
            params, opt_state, i, xs, ys, rng
        )
        losses.extend(np.asarray(closs).tolist())
        i += int(xs.shape[0])
    return params, losses, time.perf_counter() - t0


def _rel_err(got, want) -> float:
    """max |delta| / max |ref| across the param pytree."""
    num = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    den = max(float(jnp.max(jnp.abs(b))) for b in jax.tree.leaves(want))
    return num / max(den, 1e-12)


def _bench_classify(batch: int, rows: list, reps: int = 3,
                    steps: int = 96, steps_per_call: int = 16) -> dict:
    label = f"classify_b{batch}"
    cfg = DONNConfig(name="tt", n=64, depth=8, distance=0.05, det_size=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    xs, ys = synth_digits(512, seed=0)
    opt = AdamW(lr=0.3)

    warm_step = _seed_style_step(model, opt, 10)  # hoisted across runs
    chunk_fn = make_train_chunk(model, opt, 10)  # executable-cached

    def run(kind):
        best, final, losses = None, None, None
        for _ in range(reps):
            it = batch_iterator(xs, ys, batch, seed=1)
            if kind == "per_step":
                # seed behavior: a fresh jit closure per training run
                final, losses, dt = _per_step_loop(
                    _seed_style_step(model, opt, 10), opt, params, it,
                    steps)
            elif kind == "per_step_warm":
                final, losses, dt = _per_step_loop(
                    warm_step, opt, params, it, steps)
            else:
                final, losses, dt = _chunked_loop(
                    chunk_fn, opt, params, it, steps, steps_per_call)
            best = dt if best is None else min(best, dt)
        return final, losses, steps / best  # steps/sec, best-of-reps

    p_ref, l_ref, sps_ref = run("per_step")
    _, _, sps_warm = run("per_step_warm")
    p_new, l_new, sps_new = run("chunked")
    err = _rel_err(p_new, p_ref)
    match = bool(err <= 1e-5 and np.allclose(l_ref, l_new, rtol=1e-6,
                                             atol=1e-7))
    for kind, sps in (("per_step", sps_ref), ("per_step_warm", sps_warm),
                      ("chunked", sps_new)):
        name = f"train/{label}/{kind}"
        derived = (f"steps_per_sec={sps:.1f},batch={batch},depth=8,n=64,"
                   f"steps_per_call={steps_per_call}")
        row(name, 1e6 / sps, derived)
        rows.append({"name": name, "us": 1e6 / sps, "derived": derived})
    speedup = sps_new / sps_ref
    warm_speedup = sps_new / sps_warm
    name = f"train/{label}/speedup"
    derived = (f"chunked_vs_seed_style={speedup:.2f}x,"
               f"chunked_vs_warm_loop={warm_speedup:.2f}x,"
               f"param_rel_err={err:.2e},match={match}")
    row(name, 1e6 / sps_new, derived)
    rows.append({"name": name, "us": 1e6 / sps_new, "derived": derived})
    return {"steady": round(speedup, 3),
            "warm_loop": round(warm_speedup, 3),
            "steps_per_sec": round(sps_new, 1),
            "param_rel_err": err, "match": match}


def _bench_segmentation(rows: list) -> dict:
    """Chunked coverage: segmentation rides the donn_steps chunk driver."""
    from repro.nn import init_params
    from repro.runtime import donn_steps as ds
    from repro.runtime import sharding as shd

    cfg = DONNConfig(name="tt-seg", n=64, depth=4, distance=0.05,
                     segmentation=True, skip_from=0, layer_norm=True)
    mesh = shd.make_mesh_2d(data=1)
    opt = AdamW(lr=0.05)
    steps, spc = 24, 8
    xs, ms = synth_seg(64, seed=1)
    it = batch_iterator(xs, ms, 8, seed=2)
    batches = [dict(zip(("images", "masks"), next(it)))
               for _ in range(steps)]
    sspecs = ds.donn_state_specs(cfg)
    st_ref = init_params(sspecs, jax.random.PRNGKey(0))
    step_fn = jax.jit(ds.make_donn_train_step(cfg, opt))
    l_ref = []
    t0 = time.perf_counter()
    for b in batches:
        st_ref, m = step_fn(st_ref, b)
        l_ref.append(float(m["loss"]))
    dt_ref = time.perf_counter() - t0

    fn, s_sh, b_sh, _ = ds.compile_donn_train_chunk(cfg, mesh, optimizer=opt)
    st = jax.device_put(init_params(sspecs, jax.random.PRNGKey(0)), s_sh)
    l_new = []
    t0 = time.perf_counter()
    for chunk in stack_batches(iter(batches), spc):
        st, m = fn(st, chunk)
        l_new.extend(np.asarray(m["loss"]).tolist())
    dt_new = time.perf_counter() - t0
    err = _rel_err(st["params"], st_ref["params"])
    match = bool(err <= 1e-5 and np.allclose(l_ref, l_new, rtol=1e-6,
                                             atol=1e-7))
    name = "train/segmentation/chunked"
    derived = (f"chunked_vs_per_step={dt_ref / dt_new:.2f}x,"
               f"param_rel_err={err:.2e},match={match},steps_per_call={spc}")
    row(name, dt_new / steps * 1e6, derived)
    rows.append({"name": name, "us": dt_new / steps * 1e6,
                 "derived": derived})
    return {"match": match, "param_rel_err": err,
            "speedup": round(dt_ref / dt_new, 3)}


def _bench_rng_codesign(rows: list) -> dict:
    """Chunked coverage: stochastic (gumbel) codesign, rng chain aligned."""
    cfg = DONNConfig(name="tt-rng", n=64, depth=4, distance=0.05, det_size=8,
                     codesign="gumbel")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    xs, ys = synth_digits(256, seed=0)
    opt = AdamW(lr=0.3)
    steps, spc = 24, 8
    step_fn = _seed_style_step(model, opt, 10, needs_rng=True)
    chunk_fn = make_train_chunk(model, opt, 10, needs_rng=True)
    p_ref, l_ref, dt_ref = _per_step_loop(
        step_fn, opt, params, batch_iterator(xs, ys, 4, seed=1), steps)
    p_new, l_new, dt_new = _chunked_loop(
        chunk_fn, opt, params, batch_iterator(xs, ys, 4, seed=1), steps, spc)
    err = _rel_err(p_new, p_ref)
    match = bool(err <= 1e-5 and np.allclose(l_ref, l_new, rtol=1e-6,
                                             atol=1e-7))
    name = "train/rng_codesign/chunked"
    derived = (f"chunked_vs_per_step={dt_ref / dt_new:.2f}x,"
               f"param_rel_err={err:.2e},match={match},steps_per_call={spc}")
    row(name, dt_new / steps * 1e6, derived)
    rows.append({"name": name, "us": dt_new / steps * 1e6,
                 "derived": derived})
    return {"match": match, "param_rel_err": err,
            "speedup": round(dt_ref / dt_new, 3)}


def large_plane_cell(n_devices: int) -> dict:
    """Train the n=1024 plane on a (data, model) mesh of ``n_devices``."""
    from repro.nn import init_params
    from repro.runtime import donn_steps as ds
    from repro.runtime import sharding as shd

    assert jax.device_count() >= n_devices, jax.device_count()
    data, model = mesh_dims(n_devices)
    cfg = DONNConfig(name="tt-1024", n=1024, depth=2, det_size=64)
    mesh = shd.make_mesh_2d(data=data, model=model)
    B = 4
    fn, s_shard, b_shard, sspecs = ds.compile_donn_train_step_sharded(
        cfg, mesh, optimizer=AdamW(lr=0.1), global_batch=B)
    state = jax.device_put(init_params(sspecs, jax.random.PRNGKey(0)),
                           s_shard)
    r = np.random.default_rng(0)
    batch = jax.device_put(
        {"images": r.random((B, 28, 28)).astype(np.float32),
         "labels": r.integers(0, 10, (B,)).astype(np.int32)}, b_shard)
    state, m = fn(state, batch)  # compile + warm
    jax.block_until_ready(state)
    losses, steps = [float(m["loss"])], 2
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = fn(state, batch)
        losses.append(float(m["loss"]))
    dt = time.perf_counter() - t0
    return {"steps_per_sec": steps / dt, "losses": losses,
            "mesh": [data, model], "rows_per_device": cfg.n // model,
            "finite": bool(np.all(np.isfinite(losses)))}


def _bench_large_plane(rows: list) -> dict:
    """n=1024 plane, spatial x data-parallel on a 2-D mesh.

    The ISSUE-10 acceptance cell: a field too large for one chip's plane
    budget trains through ``compile_donn_train_step_sharded`` on the 2-D
    ``(data, model)`` mesh — on 8 forced host devices (2 data x 4 model)
    each device holds a 256-row pencil of every 1024^2 plane (fields, TF
    stacks, phases, optimizer moments); on TPU the mesh spans the chips
    present.  The single-device row is recorded as skipped: at the
    per-chip budget this cell models (1/4 of the plane stack per device),
    no single device can materialize the full 1024^2 TF + phase + moment
    stacks, so the sharded path is the only runnable one.
    """
    res = run_multi_device_cell("benchmarks.bench_train_throughput",
                                "large_plane_cell", cpu_devices=8)
    if not res["finite"]:
        raise AssertionError(f"non-finite losses: {res['losses']}")
    sps = res["steps_per_sec"]
    data, model = res["mesh"]
    name = f"train/large_plane_n1024/sharded_{data}x{model}"
    derived = (f"steps_per_sec={sps:.3f},mesh={data}data_x_{model}model,"
               f"n=1024,depth=2,batch=4,"
               f"rows_per_device={res['rows_per_device']},"
               f"finite={res['finite']},backend={jax.default_backend()}")
    row(name, 1e6 / sps, derived)
    rows.append({"name": name, "us": 1e6 / sps, "derived": derived})
    name1 = "train/large_plane_n1024/single_device"
    derived1 = ("status=skipped,reason=infeasible_at_modeled_chip_budget:"
                "full 1024^2 TF+phase+moment stacks exceed the quarter-"
                "plane per-device budget this cell models; only the row-"
                "sharded path runs")
    row(name1, 0.0, derived1)
    rows.append({"name": name1, "us": 0.0, "derived": derived1})
    return {"steps_per_sec": round(sps, 3), "mesh": f"{data}x{model}",
            "rows_per_device": res["rows_per_device"],
            "single_device": "skipped"}


def main() -> None:
    rows: list = []
    speedups = {
        "classify_b2": _bench_classify(2, rows),
        "classify_b8": _bench_classify(8, rows),
        "segmentation": _bench_segmentation(rows),
        "rng_codesign": _bench_rng_codesign(rows),
        "large_plane_n1024": _bench_large_plane(rows),
    }
    meta = {
        "backend": jax.default_backend(),
        "depth": 8,
        "n": 64,
        "steps_per_call": 16,
        "speedups": speedups,
    }
    write_bench_json("train_throughput", rows, meta)


if __name__ == "__main__":
    main()
