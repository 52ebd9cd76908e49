"""Chip smoke run: the DONN train -> freeze -> serve path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded train + serve on a 2x2 mesh

One process drives the chip(s); it starts no child process.  With no
arguments it runs, on the registered full-width configurations:

- train   ``donn-mnist-5l`` (200^2, 5 layers, 256-level QAT) through
          ``build_model`` -> ``train_classifier(steps_per_call=...)`` at
          batch 64 on ``synth_digits``;
- serve   ``freeze`` -> saved artifact -> ``FleetRouter.from_artifact``
          (one supervised replica: ``InferenceEngine`` + ``warmup``)
          answering a few dozen requests, with 0 restarts, 0 retries and
          0 failed requests required;
- emulate one forward of ``donn-xl-500`` (500^2, 30 layers) through the
          compile-once ``cached_apply`` (the folded real-DFT hop).

Every output is compared with the plain float32 reference on the same
params -- the per-layer eager engine under
``jax.default_matmul_precision("highest")`` -- within ``TOL`` and with
argmax parity.

``--four-chips`` runs only the sharded path and what it is compared
with: ``compile_donn_train_step_sharded`` for ``donn-xl-500`` on
``make_mesh_2d(data=2, model=2)`` against the single-device step, and the
row-sharded ``InferenceEngine(mesh_devices=2, model_devices=2)`` against
the single-device engine.

The script refuses to run anywhere but on a TPU.  Progress goes to
stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.cache import use_persistent_compile_cache  # noqa: E402
from repro.core.models import (  # noqa: E402
    build_model, cached_apply, cached_model, clear_emulation_caches,
)
from repro.core.train_utils import mse_softmax_loss, train_classifier  # noqa: E402
from repro.data import batch_iterator, synth_digits  # noqa: E402
from repro.models.config import get_config  # noqa: E402

SEED = 0
TRAIN_BATCH = 64
STEPS_PER_CALL = 8
TRAIN_CHUNKS = 3
SERVE_REQUESTS = 64
SERVE_BUCKETS = (1, 8, 32)
EMULATE_BATCH = 8
EVAL_BATCH = 64  # = TRAIN_BATCH = SERVE_REQUESTS: one reference program
# Agreement bound, max |out - ref| / max |ref| over a batch of outputs.
# Both sides are float32 on the same chip with the same FFTs; they differ
# only in op order (scan vs per-layer loop, real-DFT matmuls vs FFTs, the
# pencil all-to-all vs a local FFT), whose f32 rounding through up to 31
# hops of 500^2 planes stays near 1e-5.  A bf16 pass anywhere in the
# path rounds values by up to 2^-9 (~2e-3): the detector readout at
# default precision moved the 200^2 loss by 9e-4 on a v5e, which is why
# the readouts run at "highest" (repro.core.diffraction.READOUT_PRECISION).
# 1e-3 admits f32 reordering with two orders of margin, stays far below
# the spacing of the class scores that argmax parity checks, and is
# reached by a bf16 pass wherever its rounding does not average out.
TOL = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


_REFERENCES: dict = {}


def reference_apply(cfg, params, x):
    """Plain float32 reference: the eager engine, matmuls at "highest"."""
    ref_cfg = dataclasses.replace(cfg, engine="eager")
    if ref_cfg not in _REFERENCES:  # one jitted reference per architecture
        _REFERENCES[ref_cfg] = jax.jit(build_model(ref_cfg).apply)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REFERENCES[ref_cfg](params, jnp.asarray(x)))


def compare(name: str, out, ref) -> bool:
    """Max relative error within TOL, argmax parity, all values finite."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
    parity = bool(np.array_equal(out.argmax(-1), ref.argmax(-1)))
    finite = bool(np.all(np.isfinite(out)))
    ok = err <= TOL and parity and finite
    log(f"compare {name}: shape={out.shape} max_rel_err={err:.3e} "
        f"(tol {TOL:g}) argmax_parity={parity} finite={finite} "
        f"-> {'ok' if ok else 'FAIL'}")
    return ok


# ---------------------------------------------------------------- phases
def train_phase(cfg, batch: int, steps_per_call: int, chunks: int):
    """Train from seeded random phases.

    Returns the model, the ``TrainResult`` and the reference loss of the
    initial phases on the first batch (the first loss training reports).
    """
    model = build_model(cfg)
    params0 = model.init(jax.random.PRNGKey(SEED))
    xs, ys = synth_digits(batch * steps_per_call * chunks, seed=SEED)
    t0 = time.perf_counter()
    res = train_classifier(model, params0, batch_iterator(xs, ys, batch,
                                                          seed=1),
                           steps=steps_per_call * chunks, lr=0.1,
                           steps_per_call=steps_per_call)
    log(f"train {cfg.name}: "
        f"{len(res.losses)} steps in {time.perf_counter() - t0:.1f}s "
        f"(compile included), loss first={res.losses[0]:.6f} "
        f"last={res.losses[-1]:.6f}")
    xb, yb = next(batch_iterator(xs, ys, batch, seed=1))
    ref_loss = float(mse_softmax_loss(
        jnp.asarray(reference_apply(cfg, params0, xb)), jnp.asarray(yb),
        cfg.num_classes))
    return model, res, ref_loss


def check_train(cfg, res, ref_loss, params_trained, x_eval) -> bool:
    losses = np.asarray(res.losses)
    finite = bool(np.all(np.isfinite(losses)))
    loss_err = abs(float(losses[0]) - ref_loss) / abs(ref_loss)
    ok = finite and loss_err <= TOL
    log(f"compare {cfg.name} first loss: "
        f"{float(losses[0]):.6f} vs reference {ref_loss:.6f} "
        f"rel_err={loss_err:.3e} (tol {TOL:g}) all_finite={finite} "
        f"-> {'ok' if ok else 'FAIL'}")
    out = cached_apply(cfg)(params_trained, x_eval)
    ref = reference_apply(cfg, params_trained, x_eval)
    return compare(f"{cfg.name} trained logits", out, ref) and ok


def serve_phase(model, params, requests):
    """freeze -> artifact -> one-replica FleetRouter; returns (outs, ok)."""
    from repro.runtime.fleet import FleetRouter
    from repro.runtime.inference import freeze
    from repro.runtime.resilience import save_deployed

    deployed = freeze(model, params)
    with tempfile.TemporaryDirectory() as tmp:
        save_deployed(deployed, tmp)
        t0 = time.perf_counter()
        router = FleetRouter.from_artifact(tmp, replicas=1,
                                           buckets=SERVE_BUCKETS)
        log(f"serve: artifact loaded, {len(SERVE_BUCKETS)} buckets warmed "
            f"in {time.perf_counter() - t0:.1f}s")
        try:
            t0 = time.perf_counter()
            futs = [router.submit(x) for x in requests]
            outs = np.stack([f.result(timeout=600) for f in futs])
            dt = time.perf_counter() - t0
            stats = router.stats()
        finally:
            clean = router.close()
    sup = stats["replicas"]["r0"]["engine"]
    log(f"serve: {len(outs)}/{len(requests)} requests answered in "
        f"{dt:.2f}s; router served={stats['served']} "
        f"retried={stats['retried']} failed={stats['failed']} "
        f"replica_failures={stats['replica_failures']} "
        f"dispatches={stats['dispatches']}; supervisor "
        f"restarts={sup['restarts']} errors={sup['errors']}; "
        f"clean_close={clean}")
    ok = (len(outs) == len(requests) and stats["served"] == len(requests)
          and stats["retried"] == 0 and stats["failed"] == 0
          and stats["replica_failures"] == 0 and sup["restarts"] == 0
          and sup["errors"] == 0 and clean)
    return outs, ok


def emulate_phase(cfg, batch: int):
    """One compile-once forward of a seeded random model; (params, x, out)."""
    model = cached_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    x, _ = synth_digits(batch, seed=SEED + 1)
    t0 = time.perf_counter()
    out = np.asarray(cached_apply(cfg)(params, x))
    log(f"emulate {cfg.name} (n={cfg.n}, depth={cfg.depth}): "
        f"forward of batch {batch} -> "
        f"{out.shape} in {time.perf_counter() - t0:.1f}s (compile included)")
    return params, x, out


def sharded_train_phase(cfg, mesh, batch: int, steps: int) -> bool:
    """Sharded loss/grads vs the single-device step; a few sharded steps."""
    from repro.nn import init_params
    from repro.optim import AdamW
    from repro.runtime import donn_steps as ds

    opt = AdamW(lr=0.05)
    state0 = init_params(ds.donn_state_specs(cfg), jax.random.PRNGKey(SEED))
    xs, ys = synth_digits(batch, seed=SEED)
    batch_np = {"images": xs, "labels": ys}
    model = cached_model(cfg)

    def ref_loss(p, b):
        return mse_softmax_loss(model.apply(p, b["images"]), b["labels"],
                                cfg.num_classes)

    t0 = time.perf_counter()
    l1, g1 = jax.jit(jax.value_and_grad(
        ds.make_donn_sharded_loss(cfg, mesh)))(state0["params"], batch_np)
    l0, g0 = jax.jit(jax.value_and_grad(ref_loss))(state0["params"],
                                                   batch_np)
    loss_err = abs(float(l1) - float(l0)) / abs(float(l0))
    grad_err = max(
        float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)))
    grads_ok = loss_err <= TOL and grad_err <= TOL
    log(f"compare sharded loss/grads {cfg.name} on mesh "
        f"{dict(mesh.shape)}: loss {float(l1):.6f} vs {float(l0):.6f} "
        f"rel_err={loss_err:.3e}, grad max_rel_err={grad_err:.3e} "
        f"(tol {TOL:g}) in {time.perf_counter() - t0:.1f}s "
        f"-> {'ok' if grads_ok else 'FAIL'}")

    # the compiled sharded step: its first loss is the loss of state0 on
    # this batch, so it must match the single-device loss above
    fn, s_sh, b_sh, _ = ds.compile_donn_train_step_sharded(
        cfg, mesh, optimizer=opt, global_batch=batch)
    st = jax.device_put(jax.tree.map(jnp.array, state0), s_sh)
    b_dev = jax.device_put(batch_np, b_sh)
    t0 = time.perf_counter()
    losses = []
    for _ in range(steps):
        st, m = fn(st, b_dev)
        losses.append(float(m["loss"]))
    step_err = abs(losses[0] - float(l0)) / abs(float(l0))
    finite = bool(np.all(np.isfinite(losses)))
    phase0 = st["params"]["phase"]["layer_0"]
    spans = len(phase0.sharding.device_set)
    shard = phase0.sharding.shard_shape(phase0.shape)
    steps_ok = step_err <= TOL and finite and spans == mesh.size
    log(f"sharded train {steps} steps in {time.perf_counter() - t0:.1f}s "
        f"(compile included): losses {losses}, first vs single-device "
        f"rel_err={step_err:.3e}; layer_0 phases {phase0.shape} span "
        f"{spans} devices as {shard} shards "
        f"-> {'ok' if steps_ok else 'FAIL'}")
    return grads_ok and steps_ok


def sharded_serve_phase(cfg, data: int, model_devices: int,
                        batch: int) -> bool:
    """Row-sharded engine vs the single-device engine on one request batch."""
    from repro.runtime.inference import InferenceEngine, freeze

    model = cached_model(cfg)
    deployed = freeze(model, model.init(jax.random.PRNGKey(SEED)))
    x, _ = synth_digits(batch, seed=SEED + 2)
    ref = InferenceEngine(deployed, buckets=(batch,)).infer(x)
    eng = InferenceEngine(deployed, buckets=(batch,), mesh_devices=data,
                          model_devices=model_devices,
                          dp_min_bucket=batch)
    out = eng.infer(x)
    (ex,) = eng._compiled.values()
    spans = len(ex.output_shardings.device_set)
    ok = compare(f"row-sharded serving {cfg.name} "
                 f"({data} data x {model_devices} model)", out, ref)
    log(f"row-sharded serving output spans {spans} devices "
        f"-> {'ok' if spans == data * model_devices else 'FAIL'}")
    return ok and spans == data * model_devices


# ------------------------------------------------------------------ main
def single_chip() -> bool:
    cfg = get_config("donn-mnist-5l")
    x_eval, _ = synth_digits(EVAL_BATCH, seed=SEED + 3)
    clear_emulation_caches()
    model, res, ref_loss = train_phase(cfg, TRAIN_BATCH, STEPS_PER_CALL,
                                       TRAIN_CHUNKS)
    params = res.params
    ok = check_train(cfg, res, ref_loss, params, x_eval)

    clear_emulation_caches()
    requests, _ = synth_digits(SERVE_REQUESTS, seed=SEED + 4)
    outs, serve_ok = serve_phase(model, params, list(requests))
    ok &= serve_ok
    ok &= compare(f"serve {cfg.name} fleet outputs", outs,
                  reference_apply(cfg, params, requests))

    xl = get_config("donn-xl-500")
    clear_emulation_caches()
    params, x, out = emulate_phase(xl, EMULATE_BATCH)
    ok &= compare(f"emulate {xl.name} logits", out,
                  reference_apply(xl, params, x))
    return ok


def four_chips() -> bool:
    from repro.runtime import sharding as shd

    if jax.device_count() < 4:
        raise SystemExit(f"--four-chips needs 4 devices, have "
                         f"{jax.device_count()}")
    cfg = get_config("donn-xl-500")
    mesh = shd.make_mesh_2d(data=2, model=2)
    ok = sharded_train_phase(cfg, mesh, batch=8, steps=3)
    ok &= sharded_serve_phase(cfg, data=2, model_devices=2, batch=8)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train/serve path on a 2x2 "
                         "mesh against its single-device reference")
    args = ap.parse_args(argv)
    use_persistent_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform!r} devices; "
              "this smoke run only runs on the chip", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {device}, jax {jax.__version__}")
    t0 = time.perf_counter()
    ok = four_chips() if args.four_chips else single_chip()
    log(f"all phases {'passed' if ok else 'FAILED'} in "
        f"{time.perf_counter() - t0:.1f}s")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
