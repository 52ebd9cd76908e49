"""Emulation traffic: ``cached_apply`` back to back over a seeded pool.

A user scoring a test set through the emulator: the pool of ``pool``
``synth_digits`` images is made from the seed and held on the host, and
the window calls the compile-once forward on one batch of ``batch`` rows
after another, fetching each call's logits to the host before the next.
A sample is one image's forward.

Mix parameters: ``batch``, ``pool``.
"""
from __future__ import annotations

import time

import numpy as np

SPANS = ("pool_slice", "cached_apply", "logits_fetch")


def setup(ctx):
    from repro.core.models import cached_apply
    from repro.data import synth_digits

    from perfbench.harness import fault_of, plant

    b = ctx.mix["batch"]
    pool, _ = synth_digits(ctx.mix["pool"], seed=ctx.seed)
    apply = cached_apply(ctx.cfg)
    fault = fault_of(ctx.variant)
    if fault is not None:
        apply = lambda params, x, f=apply: plant(f(params, x), fault)  # noqa: E731
    state = {"ctx": ctx, "pool": pool, "batch": b, "apply": apply,
             "nb": len(pool) // b}
    for i in range(2):  # compile (or load) and run the one shape once more
        np.asarray(apply(ctx.params, pool[i * b:(i + 1) * b]))
    return state


def window(state, seconds, span, tracer):
    ctx, pool, b, apply = (state["ctx"], state["pool"], state["batch"],
                           state["apply"])
    outputs = []
    with tracer:
        t0 = time.perf_counter()
        end = t0 + seconds
        i = 0
        while True:
            j = i % state["nb"]
            with span("pool_slice"):
                xb = pool[j * b:(j + 1) * b]
            with span("cached_apply"):
                dev = apply(ctx.params, xb)
            with span("logits_fetch"):
                logits = np.asarray(dev)
            outputs.append((j, logits))
            i += 1
            if time.perf_counter() >= end:
                break
        t1 = time.perf_counter()
    return {"window_s": t1 - t0, "attempted": i * b, "failed": 0,
            "samples": i * b, "calls": i, "frozen": False,
            "outputs": outputs}


def release(state):
    from repro.core.models import clear_emulation_caches

    state["apply"] = None
    clear_emulation_caches()


def compare(state, res, reference, phases):
    """Worst relative error over a seeded sample of the window's calls,
    the last call always among them."""
    from perfbench.compare import worst_rel_err

    ctx = state["ctx"]
    outs = res["outputs"]
    k = min(ctx.cell["check_calls"], len(outs))
    last = len(outs) - 1
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 1]))
    picks = sorted({last, *rng.choice(last, k - 1, replace=False).tolist()})
    b = state["batch"]
    images = np.concatenate([state["pool"][outs[p][0] * b:(outs[p][0] + 1) * b]
                             for p in picks])
    got = np.concatenate([outs[p][1] for p in picks])
    ref = reference.logits(phases, images)
    return {"max_rel_err": worst_rel_err(got, ref)}
