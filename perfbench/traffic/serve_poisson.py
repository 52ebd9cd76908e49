"""Open-loop Poisson serving traffic through ``FleetRouter``.

Set-up follows the deployment path: ``freeze`` the seeded model,
``save_deployed`` it, and start ``FleetRouter.from_artifact`` with
``replicas`` replicas and the engine's default buckets.  The window then
submits one ``synth_digits`` image per request on a fixed schedule, never
waiting for answers (independent users), and times each request from when
it was due to when its answer was ready: a generator that falls behind
adds its lateness to the latency instead of hiding it.

The schedule (``schedule``) is a pure function of the mix and the seed.
Its gaps are ``rate_hz * seconds`` exponential draws from the mix's own
``gap_seed``, scaled to fill the window exactly, and the run's seed only
orders them and picks the images: every seed offers the same number of
requests and the same set of gaps.

Mix parameters: ``rate_hz``, ``pool``, ``replicas``, ``gap_seed``.
"""
from __future__ import annotations

import functools
import shutil
import tempfile
import time

import numpy as np

SPANS = ("router.submit", "engine.infer")
WAIT_AFTER_S = 60.0  # how long answers due in the window may still come


def schedule(mix: dict, seconds: float, seed: int) -> tuple:
    """(due times in s from the window's start, pool indices)."""
    n = max(1, int(round(mix["rate_hz"] * seconds)))
    gaps = np.random.default_rng(mix["gap_seed"]).exponential(
        1.0 / mix["rate_hz"], n)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return due, rng.integers(0, mix["pool"], n)


class _Engine:
    """The replica's engine, each call in an ``engine.infer`` span, with a
    fault planted where the variant asks for one."""

    def __init__(self, engine, span, fault):
        self.engine = engine
        self._span = span
        self._fault = fault

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def infer(self, x):
        from perfbench.harness import plant

        with self._span("engine.infer"):
            out = self.engine.infer(x)
        return plant(out, self._fault)


def setup(ctx):
    from repro.core.models import cached_model
    from repro.data import synth_digits
    from repro.runtime.fleet import FleetRouter
    from repro.runtime.inference import freeze
    from repro.runtime.resilience import save_deployed

    from perfbench.harness import fault_of, span

    fault = fault_of(ctx.variant)
    plane_dtype = "float32"
    if ctx.variant == "control":
        plane_dtype = ctx.cell["control"].get("plane_dtype", plane_dtype)
    pool, _ = synth_digits(ctx.mix["pool"], seed=ctx.seed)
    deployed = freeze(cached_model(ctx.cfg), ctx.params,
                      plane_dtype=plane_dtype)
    artifact = tempfile.mkdtemp(prefix="perfbench-artifact-")
    save_deployed(deployed, artifact)
    router = FleetRouter.from_artifact(artifact,
                                       replicas=ctx.mix["replicas"])
    for rep in router.replicas:
        buckets = rep.engine.engine.buckets  # the supervisor's engine's
        rep.engine = _Engine(rep.engine, span, fault)
        for b in buckets:  # run every warmed bucket once
            rep.engine.infer(np.zeros((b,) + pool.shape[1:], np.float32))
    return {"ctx": ctx, "pool": pool, "router": router, "mix": ctx.mix,
            "artifact": artifact}


def _stamp(done, i, fut):
    done[i] = time.perf_counter()


def window(state, seconds, span, tracer):
    from repro.runtime.resilience import DrainingError, OverloadedError

    router, pool = state["router"], state["pool"]
    due, idx = schedule(state["mix"], seconds, state["ctx"].seed)
    n = len(due)
    done = [None] * n
    late = np.zeros(n)
    futs = [None] * n
    shed = 0
    with tracer:
        before = router.stats()
        t0 = time.perf_counter()
        due_abs = t0 + due
        for i in range(n):
            wait = due_abs[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - due_abs[i]
            try:
                with span("router.submit"):
                    fut = router.submit(pool[idx[i]])
            except (OverloadedError, DrainingError):
                shed += 1
                continue
            fut.add_done_callback(functools.partial(_stamp, done, i))
            futs[i] = fut
        t_close = time.perf_counter()
        outputs, failed = {}, shed
        for i, fut in enumerate(futs):
            if fut is None:
                continue
            try:
                outputs[i] = fut.result(
                    timeout=max(t_close + WAIT_AFTER_S - time.perf_counter(),
                                0.0))
            except Exception:  # noqa: BLE001 - a lost answer is a failure
                failed += 1
        after = router.stats()
    # completion is stamped by a callback that may run just after result()
    # returns; wait for the stamps of the answers that came
    for i in outputs:
        while done[i] is None:
            time.sleep(1e-4)
    lat = [(done[i] - due_abs[i]) * 1e3 for i in sorted(outputs)]
    notes = [
        f"generator lateness median {np.median(late) * 1e3:.4f} ms, max "
        f"{np.max(late) * 1e3:.4f} ms over {n} requests at "
        f"{state['mix']['rate_hz']} req/s",
        f"answered {len(outputs)}/{n}, shed {shed}, failed "
        f"{failed - shed}; last answer "
        f"{(max(done[i] for i in outputs) - t_close) * 1e3 if outputs else 0:.3f}"
        f" ms after the window closed",
    ]
    counters = {k: after[k] - before[k]
                for k in ("served", "dispatches", "retried", "failed",
                          "shed")}
    return {"window_s": t_close - t0, "attempted": n, "failed": failed,
            "samples": len(outputs), "calls": counters["dispatches"],
            "frozen": True, "latencies_ms": lat, "counters": counters,
            "outputs": outputs, "idx": idx, "notes": notes}


def release(state):
    from repro.core.models import clear_emulation_caches

    state["router"].close()
    state["router"] = None
    shutil.rmtree(state["artifact"], ignore_errors=True)
    clear_emulation_caches()


def compare(state, res, reference, phases):
    """Worst relative error over every answer (or a seeded sample of
    ``check_requests`` of them), against the reference's logits of the
    same pool images."""
    from perfbench.compare import worst_rel_err

    ctx = state["ctx"]
    answered = sorted(res["outputs"])
    if not answered:
        return {"max_rel_err": float("inf")}
    k = ctx.cell["check_requests"]
    if len(answered) > k:
        rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 3]))
        answered = sorted(rng.choice(answered, k, replace=False).tolist())
    pool_idx = res["idx"][answered]
    uniq, inv = np.unique(pool_idx, return_inverse=True)
    ref = reference.logits(phases, state["pool"][uniq])[inv]
    got = np.stack([res["outputs"][i] for i in answered])
    return {"max_rel_err": worst_rel_err(got, ref)}
