"""The float64 training reference, on its own and against the program.

At the size of the registry's ``donn-mnist-5l-smoke`` (64^2 planes, 5
layers, 256-level QAT) on seeded masks, the program's training chunk
must match the reference in its losses over three steps, in each layer's
gradient at the first step, and in the straight-through estimator at
phases beside a level boundary.  The reference's adjoint gradient is held
to central finite differences, and its Adam replay to the program's
``AdamW``, so that it stands on its own.
"""
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from perfbench import spec  # noqa: E402
from perfbench.compare import worst_rel_err  # noqa: E402
from repro.core import build_model  # noqa: E402
from repro.core.config import DONNConfig  # noqa: E402
from repro.core.train_utils import make_loss_fn, make_train_chunk  # noqa: E402
from repro.data import synth_digits  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.optim import AdamW  # noqa: E402

ref_mod = spec.load_module(REPO, "references", "donn_classifier_train")
ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0}
SEED = 2 ** 31 + 61


def smoke():
    cfg = get_config("donn-mnist-5l", smoke=True)
    assert (cfg.n, cfg.depth, cfg.codesign, cfg.device_levels) == \
        (64, 5, "qat", 256)
    return cfg


def fields(cfg: DONNConfig) -> dict:
    import dataclasses

    return dataclasses.asdict(cfg)


def masks(cfg, seed=SEED) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 2 * math.pi,
                       (cfg.depth, cfg.n, cfg.n)).astype(np.float32)


def tree(phases) -> dict:
    return {"phase": {f"layer_{i}": jnp.asarray(p)
                      for i, p in enumerate(phases)}}


def stack(t) -> np.ndarray:
    layers = t["phase"]
    return np.stack([np.asarray(layers[f"layer_{i}"])
                     for i in range(len(layers))])


def batches(steps, b, seed=SEED):
    x, y = synth_digits(steps * b, seed=seed)
    return x.reshape(steps, b, 28, 28), y.reshape(steps, b)


def program_grads(cfg, phases, x, y) -> np.ndarray:
    loss_fn = make_loss_fn(build_model(cfg), cfg.num_classes)
    g = jax.grad(lambda p: loss_fn(p, x, y, None)[0])(tree(phases))
    return stack(g)


def test_chunk_losses_over_three_steps_match_the_reference():
    cfg = smoke()
    opt = AdamW(**ADAM)
    chunk = make_train_chunk(build_model(cfg), opt, cfg.num_classes)
    xs, ys = batches(4, 16)
    phases = masks(cfg)
    params = tree(phases)
    _, _, _, losses, _ = chunk(params, opt.init(params), 0, xs, ys,
                               jax.random.PRNGKey(0))
    zeros = np.zeros_like(phases)
    want, _ = ref_mod.Reference(fields(cfg)).train(
        phases, zeros, zeros, 0, xs[:3], ys[:3], ADAM)
    assert worst_rel_err(np.asarray(losses)[:3, None], want[:, None]) < 1e-5
    # the steps moved the loss: the comparison sees the updates
    assert abs(want[2] - want[0]) > 1e-3 * want[0]


def test_chunk_losses_match_from_a_state_in_mid_training():
    cfg = smoke()
    opt = AdamW(**ADAM)
    chunk = make_train_chunk(build_model(cfg), opt, cfg.num_classes)
    xs, ys = batches(4, 16)
    params = tree(masks(cfg))
    params, state, rng, _, _ = chunk(params, opt.init(params), 0, xs, ys,
                                     jax.random.PRNGKey(0))
    kept = (stack(params), stack(state.mu), stack(state.nu))
    _, _, _, losses, _ = chunk(params, state, 4, xs, ys, rng)
    want, _ = ref_mod.Reference(fields(cfg)).train(*kept, 4, xs[:3], ys[:3],
                                                   ADAM)
    assert worst_rel_err(np.asarray(losses)[:3, None], want[:, None]) < 1e-5


def test_first_step_gradients_match_per_layer():
    cfg = smoke()
    phases = masks(cfg)
    x, y = synth_digits(16, seed=SEED)
    got = program_grads(cfg, phases, x, y)
    loss, want = ref_mod.Reference(fields(cfg)).loss_and_grads(phases, x, y)
    assert want.shape == (cfg.depth, cfg.n, cfg.n)
    assert worst_rel_err(got.reshape(cfg.depth, -1),
                         want.reshape(cfg.depth, -1)) < 1e-4
    assert np.all(np.abs(want).reshape(cfg.depth, -1).max(axis=1) > 0)


@pytest.mark.parametrize("side", [-1, 1])
def test_straight_through_estimator_beside_a_level_boundary(side):
    cfg = smoke()
    step = 2 * math.pi / cfg.device_levels
    rng = np.random.default_rng(SEED)
    levels = rng.integers(0, cfg.device_levels,
                          (cfg.depth, cfg.n, cfg.n)).astype(np.float32)
    # a hundredth of a level from the boundary, on one side or the other
    near = ((levels + np.float32(0.5 + side * 0.01)) * np.float32(step))
    shown = (levels + (side > 0)) % cfg.device_levels * np.float32(step)
    ref = ref_mod.Reference(fields(cfg))
    assert np.allclose(ref.effective_phase(near), shown, atol=1e-5)
    x, y = synth_digits(8, seed=SEED)
    got = program_grads(cfg, near, x, y)
    # the gradient in the mask is the gradient at the level it snaps to,
    # in the program and in the reference
    np.testing.assert_allclose(got, program_grads(cfg, shown, x, y),
                               rtol=0, atol=1e-6 * np.abs(got).max())
    _, want = ref.shown_loss_and_grads(shown.astype(np.float64), x, y)
    assert worst_rel_err(got.reshape(cfg.depth, -1),
                         want.reshape(cfg.depth, -1)) < 1e-4


def test_reference_gradient_matches_central_differences():
    cfg = DONNConfig(n=32, depth=2, distance=0.05, det_size=4,
                     gamma=1.12, codesign="none")
    ref = ref_mod.Reference(fields(cfg))
    rng = np.random.default_rng(3)
    shown = rng.uniform(0, 2 * math.pi, (2, 32, 32))
    x, y = synth_digits(3, seed=5)
    _, grads = ref.shown_loss_and_grads(shown, x, y)
    h = 1e-6
    for i, r, c in [(0, 16, 16), (0, 9, 20), (1, 14, 17), (1, 22, 11)]:
        up, down = shown.copy(), shown.copy()
        up[i, r, c] += h
        down[i, r, c] -= h
        fd = (ref.shown_loss_and_grads(up, x, y)[0]
              - ref.shown_loss_and_grads(down, x, y)[0]) / (2 * h)
        assert fd == pytest.approx(grads[i, r, c], rel=1e-5,
                                   abs=1e-6 * np.abs(grads).max())


def test_adam_replay_matches_the_program_optimizer():
    rng = np.random.default_rng(9)
    p = rng.uniform(0, 6, (2, 8, 8)).astype(np.float32)
    opt = AdamW(**ADAM)
    params = tree(p)
    state = opt.init(params)
    mu = nu = np.zeros_like(p, np.float64)
    want = p.astype(np.float64)
    for step in range(3):
        g = rng.standard_normal(p.shape).astype(np.float32)
        params, state = opt.update(tree(g), state, params, jnp.asarray(step))
        want, mu, nu = ref_mod.adam(want, mu, nu, step, g, **ADAM)
    np.testing.assert_allclose(stack(params), want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(stack(state.nu), nu, rtol=1e-5)
