"""DONN training utilities (LightRidge `lr.train.utils`).

Loss per the paper (§2.1): L = || softmax(I) - onehot(t) ||_2^2 over the
per-class detector intensities I.  Also: accuracy, detector-noise injection
(Fig. 7 confidence study), and the training drivers used by the examples
and benchmarks:

- ``make_train_step``: the classic one-batch step (params, opt_state,
  step, xb, yb, rng) -> (params, opt_state, loss, acc) — routed through
  the process-wide executable cache when the model/optimizer are
  cache-keyable, so rebuilding a model around the same config stops
  re-tracing an identical training program.
- ``make_train_chunk``: the throughput driver — one jit runs
  ``steps_per_call`` optimizer steps as a ``lax.scan`` over a stacked
  batch chunk with (params, opt_state) *donated*, losses/metrics
  accumulated on device, and exactly one host sync per chunk.  Each launch
  runs in a ``donn.train_dispatch`` host span and is counted by
  ``train_stats()``; inside, the loss and the optimizer update carry the
  ``donn.loss`` and ``donn.optimizer`` stage scopes.
- ``train_classifier(steps_per_call=...)``: epoch loop on top, fed by the
  double-buffered device prefetcher (``repro.data.pipeline``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import AdamW


def mse_softmax_loss(logits: jax.Array, labels: jax.Array, num_classes: int):
    """Paper loss: MSE between softmax(detector intensities) and one-hot."""
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=probs.dtype)
    return jnp.mean(jnp.sum((probs - onehot) ** 2, axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


def add_detector_noise(
    logits_or_intensity: jax.Array, rng: jax.Array, frac: float
) -> jax.Array:
    """Uniform intensity noise bounded by ``frac`` of the max (Fig. 7)."""
    scale = frac * jnp.max(logits_or_intensity, axis=-1, keepdims=True)
    noise = jax.random.uniform(
        rng, logits_or_intensity.shape, logits_or_intensity.dtype, 0.0, 1.0
    )
    return logits_or_intensity + scale * noise


def bce_segmentation_loss(intensity: jax.Array, mask: jax.Array):
    """Per-pixel BCE on normalized intensity (segmentation DONN)."""
    logits = intensity  # already layer-normed in train mode
    return jnp.mean(
        jnp.maximum(logits, 0.0) - logits * mask + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def iou(intensity: jax.Array, mask: jax.Array, thresh: float = 0.0):
    pred = (intensity > thresh).astype(jnp.float32)
    inter = jnp.sum(pred * mask, axis=(-2, -1))
    union = jnp.sum(jnp.maximum(pred, mask), axis=(-2, -1))
    return jnp.mean(inter / jnp.maximum(union, 1.0))


def make_loss_fn(model, num_classes: int, needs_rng: bool = False):
    """(params, xb, yb, rng) -> (loss, logits): the paper's loss on the
    model's forward, the loss itself under the ``loss`` stage scope.  Every
    training step here differentiates this one function."""
    from repro.core import propagation as pp

    def loss_fn(params, xb, yb, rng):
        logits = model.apply(params, xb, rng) if needs_rng else model.apply(
            params, xb
        )
        with pp.stage("loss"):
            return mse_softmax_loss(logits, yb, num_classes), logits

    return loss_fn


def _scoped_update(optimizer, grads, opt_state, params, step):
    """``optimizer.update`` under the ``optimizer`` stage scope."""
    from repro.core import propagation as pp

    with pp.stage("optimizer"):
        return optimizer.update(grads, opt_state, params, step)


# Training chunks launched by ``make_train_chunk``'s functions in this process,
# and the optimizer steps they hold, counted on the host at each launch.
_TRAIN_STATS = {"chunks": 0, "steps": 0}


def train_stats() -> dict:
    """Chunks dispatched by ``make_train_chunk`` functions (``chunks``) and
    the optimizer steps they carried (``steps``), since the process began."""
    return dict(_TRAIN_STATS)


@dataclasses.dataclass
class TrainResult:
    params: Any
    losses: list
    accs: list
    wall_time_s: float
    skipped_steps: int = 0  # guarded steps dropped for non-finite loss/grads
    rollbacks: int = 0      # checkpoint restores triggered by the guard


def optimizer_cache_key(optimizer) -> Optional[tuple]:
    """Hashable identity of an optimizer, or None when not cache-keyable.

    Frozen optimizer dataclasses whose fields are all plain primitives (or
    dtypes) key the executable cache; schedules and other callables fall
    back to per-closure jit (their identity is not value-comparable).
    """
    if not dataclasses.is_dataclass(optimizer):
        return None
    vals = []
    for f in dataclasses.fields(optimizer):
        v = getattr(optimizer, f.name)
        if not isinstance(v, (int, float, str, bool, type(None), type)):
            return None
        vals.append((f.name, v))
    return (type(optimizer).__name__, tuple(vals))


def _train_static_key(tag: str, model, optimizer, *extras) -> Optional[tuple]:
    from repro.core.models import model_cache_key

    mkey = model_cache_key(model)
    okey = optimizer_cache_key(optimizer)
    if mkey is None or okey is None:
        return None
    return (tag, mkey, okey) + tuple(extras)


def make_train_step(model, optimizer, num_classes: int, needs_rng: bool = False):
    """jit'd (params, opt_state, step, batch[, rng]) -> (params, opt, loss, acc).

    Routed through ``repro.core.propagation.cached_executable`` (keyed by
    the model's config statics + optimizer values + input avals) whenever
    the model/optimizer are cache-keyable, so examples and benchmarks that
    rebuild identical models stop re-tracing the same training program.
    """

    loss_fn = make_loss_fn(model, num_classes, needs_rng)

    def step_impl(params, opt_state, step, xb, yb, rng):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, xb, yb, rng
        )
        params, opt_state = _scoped_update(optimizer, grads, opt_state,
                                           params, step)
        return params, opt_state, loss, accuracy(logits, yb)

    skey = _train_static_key("donn_train_step", model, optimizer,
                             num_classes, needs_rng)
    if skey is None:
        return jax.jit(step_impl)
    from repro.core import propagation as pp

    def step_fn(params, opt_state, step, xb, yb, rng):
        args = (params, opt_state, jnp.asarray(step), jnp.asarray(xb),
                jnp.asarray(yb), rng)
        return pp.cached_executable(skey, step_impl, *args)(*args)

    return step_fn


def make_train_chunk(model, optimizer, num_classes: int,
                     needs_rng: bool = False, donate: bool = True,
                     guard: bool = False):
    """Donated multi-step scanned training driver (the throughput engine).

    Returns ``chunk_fn(params, opt_state, step0, xs, ys, rng) -> (params,
    opt_state, rng, losses, accs)`` running one optimizer step per leading
    ``xs``/``ys`` row as a single ``lax.scan`` inside one jit:

    - (params, opt_state) are **donated** — step k+1 updates step k's
      buffers in place instead of re-allocating the whole state;
    - per-step losses/accuracies accumulate on device and come back as
      (S,) arrays — one host sync per chunk instead of per step;
    - the rng chain matches the per-step loop exactly (``rng, sub =
      split(rng)`` before each step), so chunked training is numerically
      identical to ``make_train_step`` iterated S times.

    ``guard=True`` adds **device-side non-finite detection** to every
    step: when the loss or any gradient leaf is non-finite, the update is
    dropped wholesale (params, opt_state and the bias-correction step
    counter stay at their pre-step values — a skipped step is a no-op)
    and the step is flagged.  The chunk then returns two extra metrics,
    ``(..., losses, accs, skipped, params_ok)`` with ``skipped`` an (S,)
    bool array and ``params_ok`` a scalar "all params finite" flag — both
    accumulate on device and ride the existing one-sync-per-chunk
    metrics, adding **zero** host syncs to the hot loop.

    Like ``make_train_step`` it rides the process-wide executable cache
    when the model/optimizer are cache-keyable.
    """

    loss_fn = make_loss_fn(model, num_classes, needs_rng)

    def chunk_impl(params, opt_state, step0, xs, ys, rng):
        def body(carry, batch):
            params, opt_state, step, rng = carry
            xb, yb = batch
            rng, sub = jax.random.split(rng)
            (loss, logits), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, xb, yb, sub)
            if not guard:
                params, opt_state = _scoped_update(
                    optimizer, grads, opt_state, params, step
                )
                return ((params, opt_state, step + 1, rng),
                        (loss, accuracy(logits, yb)))
            ok = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                ok &= jnp.all(jnp.isfinite(g))
            new_params, new_opt = _scoped_update(optimizer, grads,
                                                 opt_state, params, step)
            keep = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), new, old
            )
            # a skipped step is a full no-op: state, optimizer moments AND
            # the bias-correction step counter all stay pre-step
            return ((keep(new_params, params), keep(new_opt, opt_state),
                     jnp.where(ok, step + 1, step), rng),
                    (loss, accuracy(logits, yb), ~ok))

        carry = (params, opt_state, jnp.asarray(step0, jnp.int32), rng)
        (params, opt_state, _, rng), metrics = jax.lax.scan(
            body, carry, (xs, ys)
        )
        if not guard:
            losses, accs = metrics
            return params, opt_state, rng, losses, accs
        losses, accs, skipped = metrics
        params_ok = jnp.array(True)
        for p in jax.tree.leaves(params):
            params_ok &= jnp.all(jnp.isfinite(p))
        return params, opt_state, rng, losses, accs, skipped, params_ok

    from repro.core import propagation as pp

    donate_n = (0, 1) if donate else ()
    skey = _train_static_key("donn_train_chunk", model, optimizer,
                             num_classes, needs_rng, donate, guard)
    jitted = (jax.jit(chunk_impl, donate_argnums=donate_n)
              if skey is None else None)

    def chunk_fn(params, opt_state, step0, xs, ys, rng):
        # input transfer, executable lookup (a compile on a miss) and launch
        with jax.profiler.TraceAnnotation(pp.TRAIN_DISPATCH_SPAN):
            _TRAIN_STATS["chunks"] += 1
            _TRAIN_STATS["steps"] += int(np.shape(xs)[0])
            if jitted is not None:
                return jitted(params, opt_state, step0, xs, ys, rng)
            args = (params, opt_state, jnp.asarray(step0), jnp.asarray(xs),
                    jnp.asarray(ys), rng)
            ex = pp.cached_executable(skey, chunk_impl, *args,
                                      donate_argnums=donate_n)
            return ex(*args)

    return chunk_fn


def train_classifier(
    model,
    params,
    data_iter,
    steps: int,
    lr: float = 0.1,
    num_classes: int = 10,
    needs_rng: bool = False,
    rng: Optional[jax.Array] = None,
    log_every: int = 0,
    steps_per_call: int = 1,
    prefetch: int = 2,
    guard: bool = False,
    ckpt_dir=None,
    ckpt_every: int = 0,
    max_rollbacks: int = 2,
) -> TrainResult:
    """Compact Adam training loop for DONN classifiers (paper uses Adam+MSE).

    ``steps_per_call > 1`` switches to the chunked throughput driver
    (``make_train_chunk``): batches stack into device-resident chunks fed
    through the double-buffered device prefetcher, each chunk runs
    ``steps_per_call`` donated optimizer steps inside one compiled scan,
    and the host syncs once per chunk.  Numerics (losses, rng chain, final
    params) are identical to the per-step path.  ``prefetch`` bounds the
    prefetcher's in-flight chunk count (0 disables it).

    ``guard=True`` (chunked path only) turns on the non-finite guardrails:
    poisoned steps (NaN/inf loss or grads) are skipped device-side as
    exact no-ops and counted in ``TrainResult.skipped_steps``.  With
    ``ckpt_dir`` set, (params, opt_state, rng, step) checkpoint through
    ``repro.checkpoint`` every ``ckpt_every`` steps (plus once at step 0),
    and a chunk that comes back fully skipped or with non-finite params
    **rolls back** to the last good checkpoint and resumes — at most
    ``max_rollbacks`` times (counted in ``TrainResult.rollbacks``);
    beyond that a ``RuntimeError`` surfaces the divergence.
    """
    optimizer = AdamW(lr=lr)
    opt_state = optimizer.init(params)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    losses, accs = [], []
    t0 = time.perf_counter()
    if guard and steps_per_call <= 1:
        raise ValueError("guard=True requires the chunked driver "
                         "(steps_per_call > 1)")
    if steps_per_call <= 1:
        step_fn = make_train_step(model, optimizer, num_classes, needs_rng)
        for i in range(steps):
            xb, yb = next(data_iter)
            rng, sub = jax.random.split(rng)
            params, opt_state, loss, acc = step_fn(
                params, opt_state, jnp.asarray(i), xb, yb, sub
            )
            losses.append(float(loss))
            accs.append(float(acc))
            if log_every and (i % log_every == 0):
                print(f"step {i:4d}  loss {losses[-1]:.4f}  "
                      f"acc {accs[-1]:.3f}")
        return TrainResult(params, losses, accs, time.perf_counter() - t0)

    from repro.data.pipeline import device_prefetch, stack_batches

    # the chunk driver donates its state buffers; copy the caller's params
    # once so their reference stays valid after training
    params = jax.tree.map(jnp.array, params)
    opt_state = jax.tree.map(jnp.array, opt_state)
    chunk_fn = make_train_chunk(model, optimizer, num_classes, needs_rng,
                                guard=guard)
    chunks = stack_batches(data_iter, steps_per_call, total=steps)
    if prefetch:
        chunks = device_prefetch(chunks, size=prefetch)

    skipped_total, rollbacks = 0, 0
    last_good: Optional[int] = None
    # i indexes the data stream / metric lists; opt_step is the optimizer's
    # bias-correction counter — they diverge when guarded steps are skipped
    # (a skipped step consumes a batch but must not advance the optimizer)
    i, opt_step = 0, 0
    if ckpt_dir is not None:
        from repro import checkpoint as ckpt

        def _ckpt_state():
            return {"params": params, "opt": opt_state, "rng": rng,
                    "opt_step": jnp.asarray(opt_step, jnp.int32)}

        # a rollback target must exist before the first chunk can fail
        ckpt.save(ckpt_dir, 0, _ckpt_state(), keep=3)
        last_good = 0
    for xs, ys in chunks:
        out = chunk_fn(params, opt_state, opt_step, xs, ys, rng)
        if guard:
            params, opt_state, rng, closs, cacc, skipped, params_ok = out
            skipped = np.asarray(skipped)  # chunk sync (with the metrics)
            bad_chunk = (not bool(params_ok)) or bool(skipped.all())
            if bad_chunk and last_good is not None:
                if rollbacks >= max_rollbacks:
                    raise RuntimeError(
                        f"training diverged at step {i} and the rollback "
                        f"budget ({max_rollbacks}) is exhausted"
                    )
                state = ckpt.restore(ckpt_dir, last_good, _ckpt_state())
                params = jax.tree.map(jnp.array, state["params"])
                opt_state = jax.tree.map(jnp.array, state["opt"])
                rng = jnp.asarray(state["rng"])
                opt_step = int(state["opt_step"])
                del losses[last_good:], accs[last_good:]  # rolled-back steps
                i = last_good
                rollbacks += 1
                continue
            n_skip = int(skipped.sum())
            skipped_total += n_skip
            opt_step += int(xs.shape[0]) - n_skip
        else:
            params, opt_state, rng, closs, cacc = out
            opt_step += int(xs.shape[0])
        closs, cacc = np.asarray(closs), np.asarray(cacc)  # one sync/chunk
        losses.extend(closs.tolist())
        accs.extend(cacc.tolist())
        if log_every:
            # same lines the per-step path prints, emitted at chunk sync
            for j in range(int(xs.shape[0])):
                if (i + j) % log_every == 0:
                    print(f"step {i + j:4d}  loss {closs[j]:.4f}  "
                          f"acc {cacc[j]:.3f}")
        i += int(xs.shape[0])
        if (last_good is not None and ckpt_every
                and i - last_good >= ckpt_every):
            ckpt.save(ckpt_dir, i, _ckpt_state(), keep=3)
            last_good = i
    return TrainResult(params, losses, accs, time.perf_counter() - t0,
                       skipped_steps=skipped_total, rollbacks=rollbacks)


def evaluate_classifier(model, params, data_iter, batches: int,
                        rng: Optional[jax.Array] = None,
                        noise_frac: float = 0.0) -> float:
    apply = jax.jit(lambda p, x: model.apply(p, x))
    correct, total = 0.0, 0
    rng = rng if rng is not None else jax.random.PRNGKey(1)
    for _ in range(batches):
        xb, yb = next(data_iter)
        logits = apply(params, xb)
        if noise_frac > 0.0:
            rng, sub = jax.random.split(rng)
            logits = add_detector_noise(logits, sub, noise_frac)
        correct += float(jnp.sum(jnp.argmax(logits, -1) == yb))
        total += int(yb.shape[0])
    return correct / max(total, 1)
