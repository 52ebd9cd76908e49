"""Training traffic: chunks of Adam steps back to back over a seeded pool.

A researcher training the masks before fabricating them: the window runs
the executable that ``train_classifier(..., steps_per_call=S)`` runs,
``make_train_chunk`` (Adam, the paper's softmax-MSE loss, the state
donated), fed as that loop feeds it: batches of ``batch`` rows cycled over
a pool of ``pool`` seeded ``synth_digits`` images with their labels,
stacked ``steps_per_call`` at a time (``stack_batches``) and put on the
device ``prefetch`` chunks ahead (``device_prefetch``).  The loop is
closed: each chunk's losses are fetched before the next chunk is launched.
A sample is one image's training step.

Set-up warms the chunk on copies of the state, so the window trains from
the seeded masks at optimizer step 0, and keeps an on-device copy of that
state, not donated.  The window's first chunk is the one checked, against
the cell's float64 training reference (the cell's ``reference``) run from
the same state over the same batches: its own losses for its first
``check_steps`` steps, each a function of the updates before it, and each
layer's gradient at its first step, computed by the program's loss
function at the same batch.

Planted faults: "altered_answer" on the chunk's rows of (loss, accuracy),
one a step, and on the per-layer gradients, where they come back;
"half_batch" on each step's images and labels before the chunk runs, so
the chunk trains on the first half of every batch; and this kind's own
"unchanged_state", a chunk whose update leaves the masks as they are (its
Adam at rate 0).

Mix parameters: ``batch``, ``steps_per_call``, ``pool``, ``prefetch``,
``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay``.
"""
from __future__ import annotations

import dataclasses
import pathlib
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPANS = ("next_chunk", "train_chunk", "losses_fetch", "donn.train_dispatch")
ADAM = ("lr", "b1", "b2", "eps", "weight_decay")
OWN_FAULTS = ("unchanged_state",)


def _fault(variant: str):
    """The fault a "fault:<name>" variant plants: the harness's, or one of
    ``OWN_FAULTS``."""
    from perfbench.harness import fault_of

    name = variant.split(":", 1)[1] if variant.startswith("fault:") else None
    return name if name in OWN_FAULTS else fault_of(variant)


def _batches(pool, labels, b):
    """Batches of ``b`` rows cycled over the pool, for ever."""
    nb = len(pool) // b
    i = 0
    while True:
        j = i % nb
        yield pool[j * b:(j + 1) * b], labels[j * b:(j + 1) * b]
        i += 1


def _stack(tree) -> np.ndarray:
    """The (L, n, n) stack of a masks-shaped tree, on the host."""
    layers = tree["phase"]
    return np.stack([np.asarray(layers[f"layer_{i}"])
                     for i in range(len(layers))])


def _half(steps) -> np.ndarray:
    """Stacked batches with the second half of each never computed."""
    from perfbench.harness import plant

    return np.stack([plant(b, "half_batch") for b in np.asarray(steps)])


def setup(ctx):
    import jax
    import jax.numpy as jnp

    from repro.core.models import cached_model
    from repro.core.train_utils import make_loss_fn, make_train_chunk
    from repro.data import synth_digits
    from repro.data.pipeline import device_prefetch, stack_batches
    from repro.optim import AdamW

    mix = ctx.mix
    b, s = mix["batch"], mix["steps_per_call"]
    fault = _fault(ctx.variant)
    opt = AdamW(**{k: mix[k] for k in ADAM})
    # the fault's chunk: the same steps, with an update that moves nothing
    step_opt = dataclasses.replace(opt, lr=0.0) \
        if fault == "unchanged_state" else opt
    model = cached_model(ctx.cfg)
    loss_fn = make_loss_fn(model, ctx.cfg.num_classes)
    pool, labels = synth_digits(mix["pool"], seed=ctx.seed)
    # the chunk donates the state it is given: train on copies, so the
    # harness's own masks stay readable
    params = jax.tree.map(jnp.array, ctx.params)
    state = {
        "ctx": ctx, "batch": b, "steps": s, "fault": fault,
        "chunk_fn": make_train_chunk(model, step_opt, ctx.cfg.num_classes),
        "grad_fn": jax.jit(jax.grad(
            lambda p, x, y: loss_fn(p, x, y, None)[0])),
        "chunks": device_prefetch(
            stack_batches(_batches(pool, labels, b), s),
            size=mix["prefetch"]),
        "params": params, "opt_state": opt.init(params), "step": 0,
        "rng": jax.random.PRNGKey(0),
        # one launch copies the whole state (not donated: new buffers)
        "copy": jax.jit(lambda tree: jax.tree.map(jnp.copy, tree)),
    }
    start = (state["params"], state["opt_state"])
    for _ in range(2):  # compile (or load) and run the one chunk shape
        xs, ys = next(state["chunks"])
        warm = state["copy"](start)
        np.asarray(state["chunk_fn"](*warm, 0, xs, ys, state["rng"])[3])
    # the check's gradient at the batch shape; the state the window starts
    # from, kept
    _stack(state["grad_fn"](params, np.asarray(xs)[0], np.asarray(ys)[0]))
    state["start"] = state["copy"](start)
    return state


def _chunk(state, span):
    """One chunk, as ``train_classifier`` runs it: (its images, its
    labels, its losses and accuracies)."""
    with span("next_chunk"):
        xs, ys = next(state["chunks"])
    xin, yin = ((_half(xs), _half(ys)) if state["fault"] == "half_batch"
                else (xs, ys))
    step0 = state["step"]
    with span("train_chunk"):
        (state["params"], state["opt_state"], state["rng"], losses,
         accs) = state["chunk_fn"](state["params"], state["opt_state"],
                                   step0, xin, yin, state["rng"])
    with span("losses_fetch"):
        losses, accs = np.asarray(losses), np.asarray(accs)
    state["step"] = step0 + int(xs.shape[0])
    return xs, ys, (xin, yin), (losses, accs)


def _deltas(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def window(state, seconds, span, tracer):
    from repro.core.propagation import compile_stats
    from repro.core.train_utils import train_stats

    from perfbench.harness import plant

    c0, t0_stats = compile_stats(), train_stats()
    with tracer:
        t0 = time.perf_counter()
        end = t0 + seconds
        times = [t0]
        first = None
        while True:
            out = _chunk(state, span)
            first = first or out
            times.append(time.perf_counter())
            if times[-1] >= end:
                break
        t1 = times[-1]
    chunks = len(times) - 1
    took = np.diff(times)
    counters = {**_deltas(compile_stats(), c0),
                **_deltas(train_stats(), t0_stats)}
    # what the check reads, fetched after the window: the first chunk's
    # answers as they came back (one row a step: loss, accuracy), and each
    # layer's gradient at its first step
    xs, ys, (xin, yin), (losses, accs) = first
    params, opt_state = state["start"]
    fault = state["fault"]
    answer_fault = fault if fault == "altered_answer" else None
    k = state["ctx"].cell["check_steps"]
    rows = plant(np.stack([losses, accs], axis=-1), answer_fault)
    grads = plant(_stack(state["grad_fn"](params, np.asarray(xin)[0],
                                          np.asarray(yin)[0])),
                  answer_fault)
    steps = chunks * state["steps"]
    return {"window_s": t1 - t0, "attempted": steps * state["batch"],
            "failed": 0, "samples": steps * state["batch"], "calls": chunks,
            "frozen": False, "counters": counters,
            "notes": [f"chunk seconds: median {np.median(took):.4f}, "
                      f"max {took.max():.4f}, over {chunks} chunks"],
            "check": {"losses": rows[:k, 0], "grads": grads,
                      "phases": _stack(params), "mu": _stack(opt_state.mu),
                      "nu": _stack(opt_state.nu), "step": 0,
                      "xs": np.asarray(xs)[:k], "ys": np.asarray(ys)[:k]}}


def release(state):
    from repro.core.models import clear_emulation_caches

    for key in ("chunk_fn", "grad_fn", "copy", "chunks", "params",
                "opt_state", "start"):
        state[key] = None
    clear_emulation_caches()


def compare(state, res, reference, phases):
    """Worst relative error over the checked chunk's answers against the
    cell's float64 training reference, run from the state that chunk
    started from over its batches (Adam replayed between the steps): each
    of its first ``check_steps`` losses, and each layer's gradient at its
    first step."""
    from perfbench import spec
    from perfbench.compare import worst_rel_err

    ctx = state["ctx"]
    c = res["check"]
    ref = spec.load_module(ROOT, "references",
                           ctx.cell["reference"]).Reference(ctx.fields)
    losses, grads = ref.train(c["phases"], c["mu"], c["nu"], c["step"],
                              c["xs"], c["ys"], {k: ctx.mix[k] for k in ADAM})
    depth = len(grads)
    return {"max_rel_err": max(
        worst_rel_err(c["losses"][:, None], losses[:, None]),
        worst_rel_err(c["grads"].reshape(depth, -1),
                      grads.reshape(depth, -1)))}
