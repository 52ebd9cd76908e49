"""The packed real-DFT hop: the angular-spectrum hop as real matmuls.

On a CPU the plan keeps the FFT hop (``_packed_hop_applies`` is a TPU
rule), so these tests force the packed path by patching that selector and
compare it with the FFT path on the same plan.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DONNConfig, LayerSpec, build_model
from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.core.models import cached_apply, clear_emulation_caches
from repro.data import synth_digits, synth_rgb_scenes, synth_seg

TINY = dict(name="donn-tiny", n=64, depth=3, distance=0.05, det_size=8,
            gamma=1.12, codesign="qat", device_levels=256)


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


# the forced packed hop, folded (``_folded_hop_applies``) and not, and
# folded with each layer's mix as the one-pass kernel where it takes the
# operands (``_mix_kernel_applies``; interpreted on a CPU)
FOLDS = pytest.mark.parametrize("fold,kernel", [(True, False), (False, False),
                                                (True, True)],
                                ids=["folded", "unfolded", "folded-kernel"])


def _force(m, fold, kernel=False):
    m.setattr(pp, "_packed_hop_applies", lambda n: True)
    m.setattr(pp, "_folded_hop_applies", lambda n: fold)
    if kernel:
        m.setattr(pp, "_mix_kernel_applies", pp._mix_kernel_takes)


def _on_both_paths(monkeypatch, fn, fold, kernel=False):
    """``fn()`` on the FFT path, then with the packed path forced, folded
    if ``fold``, the mix kernel forced if ``kernel``; the plan and
    executable caches are cleared around each."""
    clear_emulation_caches()
    want = fn()
    with monkeypatch.context() as m:
        _force(m, fold, kernel)
        clear_emulation_caches()
        got = fn()
    clear_emulation_caches()
    return got, want


def _close(got, want, rtol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max())


@pytest.mark.parametrize("n", [10, 63, 64])
def test_real_dft_pair_inverts(n):
    g, gi = df.real_dft_matrices(n)
    np.testing.assert_allclose(gi.astype(np.float64) @ g, np.eye(n),
                               atol=1e-5)


@pytest.mark.parametrize("n", [10, 63, 64])
@pytest.mark.parametrize("method", [df.RS, df.FRESNEL])
@pytest.mark.parametrize("band_limit", [True, False])
def test_packed_hop_equals_fft_hop(n, method, band_limit):
    # z long enough that the band limit cuts part of the spectrum
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3], 532e-9,
                              method=method, band_limit=band_limit)
    assert plan._packable
    h = df.transfer_function(plan.grid, 0.3, 532e-9, method, band_limit)
    u = _field((2, 3, n, n))  # batch and channel axes
    want = np.fft.ifft2(np.fft.fft2(u.astype(np.complex128)) * h)
    got = jax.jit(plan._packed_hop)(
        jnp.asarray(u), (jnp.asarray(h.real), jnp.asarray(h.imag)))
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 2e-6


def test_packed_hop_phase_gradients_match_fft_hop():
    n = 32
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.05], 532e-9)
    pair = tuple(jnp.asarray(p) for p in plan._tf_pair())
    pair = (pair[0][0], pair[1][0])
    u = jnp.asarray(_field((2, n, n), seed=1))
    phi = jnp.asarray(np.random.default_rng(2).uniform(0, 2 * np.pi, (n, n)),
                      jnp.float32)

    def loss(hop):
        def f(p):
            v = hop(u * jnp.exp(1j * p.astype(jnp.complex64)), pair)
            return jnp.sum(jnp.abs(v) ** 4)
        return jax.grad(f)(phi)

    fft_hop = lambda v, tf: jnp.fft.ifft2(
        jnp.fft.fft2(v) * jax.lax.complex(*tf))
    _close(loss(plan._packed_hop), loss(fft_hop), rtol=1e-4)


def _tiny_inputs(cfg, b=4):
    plan = pp.plan_from_config(cfg, cfg.gamma)
    rng = np.random.default_rng(3)
    phis = jnp.asarray(rng.uniform(0, 2 * np.pi, (plan.depth, cfg.n, cfg.n)),
                       jnp.float32)
    return phis, jnp.asarray(_field((b, cfg.n, cfg.n), seed=4))


@FOLDS
def test_forced_plan_forward_equals_fft_path(monkeypatch, fold, kernel):
    cfg = DONNConfig(**TINY)
    phis, u = _tiny_inputs(cfg)

    def run():
        before = pp.hop_path_stats()
        plan = pp.plan_from_config(cfg, cfg.gamma)
        out = jax.jit(plan.apply)(phis, u)
        after = pp.hop_path_stats()
        return out, {k: after[k] - before[k] for k in after}

    (got, hops), (want, _) = _on_both_paths(monkeypatch, run, fold, kernel)
    packed = cfg.depth + 1
    assert hops == {"packed": packed, "fft": 0,
                    "folded": packed if fold else 0,
                    "mix_kernel": cfg.depth if kernel else 0}
    _close(got, want)


@pytest.mark.parametrize("plane_dtype", pp.PLANE_DTYPES)
@FOLDS
def test_forced_frozen_apply_equals_fft_path(monkeypatch, fold, kernel,
                                            plane_dtype):
    cfg = DONNConfig(**TINY)
    phis, u = _tiny_inputs(cfg)

    def run():
        plan = pp.plan_from_config(cfg, cfg.gamma)
        frozen = plan.frozen_modulation(phis, plane_dtype)
        return jax.jit(lambda v, f: plan.apply(None, v, frozen=f))(u, frozen)

    _close(*_on_both_paths(monkeypatch, run, fold, kernel))


@FOLDS
def test_forced_masked_ragged_apply_batch_equals_fft_path(monkeypatch, fold,
                                                         kernel):
    # depth-padded candidates: the second is a depth-2 stack whose middle
    # layer is masked to an identity hop
    cfg = DONNConfig(**TINY)
    phis, u = _tiny_inputs(cfg)
    phis_k = jnp.stack([phis, phis[::-1]])
    mask = jnp.asarray([[True, True, True], [True, False, True]])

    def run():
        plan = pp.plan_from_config(cfg, cfg.gamma)
        return jax.jit(lambda p, m: plan.apply_batch(p, u, mask=m))(phis_k,
                                                                    mask)

    got, want = _on_both_paths(monkeypatch, run, fold, kernel)
    _close(got, want)
    assert not np.allclose(np.asarray(got[0]), np.asarray(got[1]))


@FOLDS
def test_forced_apply_batch_with_traced_tfs_equals_fft_path(monkeypatch, fold,
                                                           kernel):
    cfg = DONNConfig(**TINY)
    other = dataclasses.replace(cfg, distance=0.07)
    phis, u = _tiny_inputs(cfg)
    phis_k = jnp.stack([phis, phis[::-1]])

    def run():
        plans = [pp.plan_from_config(c, c.gamma) for c in (cfg, other)]
        tfs = tuple(jnp.asarray(np.stack([p._np[k] for p in plans]))
                    for k in ("hr", "hi"))
        fn = jax.jit(lambda p, t: plans[0].apply_batch(p, u, tfs=t))
        return fn(phis_k, tfs)

    _close(*_on_both_paths(monkeypatch, run, fold, kernel))


@FOLDS
def test_forced_cached_apply_logits_equal_fft_path(monkeypatch, fold, kernel):
    cfg = DONNConfig(**TINY)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    x = jnp.asarray(synth_digits(4, seed=0)[0])
    _close(*_on_both_paths(monkeypatch,
                           lambda: cached_apply(cfg)(params, x), fold,
                           kernel))


@FOLDS
def test_forced_rng_codesign_equals_fft_path(monkeypatch, fold, kernel):
    # stochastic codesign: each layer draws from its own key of the split
    cfg = DONNConfig(**{**TINY, "codesign": "gumbel", "device_levels": 16})
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    x = jnp.asarray(synth_digits(4, seed=0)[0])
    rng = jax.random.PRNGKey(7)
    _close(*_on_both_paths(monkeypatch,
                           lambda: cached_apply(cfg)(params, x, rng), fold,
                           kernel))


@pytest.mark.parametrize("remat", ["none", "layer"])
@FOLDS
def test_forced_qat_phase_gradients_equal_fft_path(monkeypatch, fold, kernel,
                                                    remat):
    cfg = DONNConfig(**{**TINY, "remat": remat})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    x = jnp.asarray(synth_digits(4, seed=1)[0])

    def run():
        loss = lambda p: jnp.sum(model.apply(p, x) ** 2)
        return jax.jit(jax.grad(loss))(params)

    got, want = _on_both_paths(monkeypatch, run, fold, kernel)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, rtol=1e-4)


@FOLDS
def test_forced_multichannel_frozen_serving_equals_fft_path(monkeypatch,
                                                           fold, kernel):
    from repro.runtime.inference import InferenceEngine, freeze

    cfg = DONNConfig(**{**TINY, "channels": 3, "num_classes": 6})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    x = np.asarray(synth_rgb_scenes(2, seed=0)[0])

    def run():
        return InferenceEngine(freeze(model, params), buckets=(2,)).infer(x)

    _close(*_on_both_paths(monkeypatch, run, fold, kernel))


@pytest.mark.parametrize("extra,data", [
    ({"channels": 3, "num_classes": 6}, synth_rgb_scenes),
    ({"segmentation": True, "skip_from": 0, "layer_norm": True}, synth_seg),
    ({"approximation": "fresnel", "tf_dtype": "bfloat16"}, synth_digits),
    ({"layers": (LayerSpec(distance=0.04, size=64),
                 LayerSpec(distance=0.05, size=48, pixel_size=54e-6),
                 LayerSpec(distance=0.05, size=48, pixel_size=54e-6))},
     synth_digits),
], ids=["rgb", "seg-skip", "fresnel-bf16-planes", "segmented"])
@FOLDS
def test_forced_families_equal_fft_path(monkeypatch, extra, data, fold,
                                       kernel):
    cfg = DONNConfig(**{**TINY, **extra})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(data(4, seed=0)[0])
    train = {"train": True} if cfg.segmentation else {}

    def run():
        before = pp.hop_path_stats()
        out = jax.jit(lambda p, v: model.apply(p, v, **train))(params, x)
        after = pp.hop_path_stats()
        return out, {k: after[k] - before[k] for k in after}

    (got, hops), (want, fft_hops) = _on_both_paths(monkeypatch, run, fold,
                                                   kernel)
    assert hops["packed"] > 0 and fft_hops["packed"] == 0
    # a multi-channel (..., C, m, m) field keeps the jnp mix
    kernel_layers = kernel and not cfg.channels > 1
    assert (hops["mix_kernel"] > 0) == kernel_layers
    assert fft_hops["mix_kernel"] == 0
    _close(got, want)


def test_uneven_plane_keeps_the_fft_hop(monkeypatch):
    real_tf = df.transfer_function

    def tilted(grid, z, wavelength, *args, **kw):
        # a linear phase in fx: a shifted beam, odd in frequency
        fx = grid.freqs(kw.get("pad", False))[:, None]
        return real_tf(grid, z, wavelength, *args, **kw) * np.exp(
            1j * 2e-4 * fx).astype(np.complex64)

    monkeypatch.setattr(df, "transfer_function", tilted)
    monkeypatch.setattr(pp, "_packed_hop_applies", lambda n: True)
    pp.clear_tf_cache()
    try:
        plan = pp.PropagationPlan(df.Grid(16, 36e-6), [0.05, 0.05], 532e-9)
        assert not plan._packable
        before = pp.hop_path_stats()
        jax.jit(plan.propagate_final)(jnp.asarray(_field((1, 16, 16))))
        after = pp.hop_path_stats()
    finally:
        pp.clear_tf_cache()
    assert after["packed"] == before["packed"]
    assert after["fft"] == before["fft"] + 1


@pytest.mark.parametrize("extra", [
    {"pad": True},
    {"approximation": "fraunhofer", "band_limit": False, "distance": 2.5},
], ids=["padded", "fraunhofer"])
def test_other_hops_are_not_packable(extra):
    cfg = DONNConfig(**{**TINY, **extra})
    assert not pp.plan_from_config(cfg, cfg.gamma)._packable


@FOLDS
def test_hop_path_stats_counts_each_path(monkeypatch, fold, kernel):
    cfg = DONNConfig(**TINY)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    x = jnp.asarray(synth_digits(2, seed=0)[0])
    hops = cfg.depth + 1  # the scan's layers and the final hop

    def counted():
        before = pp.hop_path_stats()
        np.asarray(cached_apply(cfg)(params, x))
        after = pp.hop_path_stats()
        return {k: after[k] - before[k] for k in after}

    fft, packed = _on_both_paths(monkeypatch, counted, fold, kernel)[::-1]
    assert fft == {"packed": 0, "fft": hops, "folded": 0, "mix_kernel": 0}
    # the kernel mixes each modulated layer, not the final hop
    assert packed == {"packed": hops, "fft": 0,
                      "folded": hops if fold else 0,
                      "mix_kernel": cfg.depth if kernel else 0}
    # a compiled program that runs again traces nothing
    with monkeypatch.context() as m:
        _force(m, fold, kernel)
        first = counted()
        assert counted() == {"packed": 0, "fft": 0, "folded": 0,
                             "mix_kernel": 0}
    clear_emulation_caches()
    assert first == packed


def test_packed_hop_rule_is_tpu_and_measured_sizes_only(monkeypatch):
    if jax.default_backend() != "tpu":
        assert not pp._packed_hop_applies(500)
    monkeypatch.setattr(pp.jax, "default_backend", lambda: "tpu")
    assert [pp._packed_hop_applies(n) for n in (64, 199, 200, 500, 512,
                                                513)] == [
        False, False, True, True, True, False]


def _spectrum(g, x):
    """The complex DFT X[k], k <= n//2, of real x (..., n) from the packed
    real DFT ``g . x``: the cosine rows hold Re X, the sine rows -Im X."""
    n = g.shape[0]
    y = x.astype(np.float64) @ g.T.astype(np.float64)
    k = np.arange(n // 2 + 1)
    sin = np.where((k > 0) & (2 * k < n), y[..., (n - k) % n], 0.0)
    return y[..., k] - 1j * sin


@pytest.mark.parametrize("n", [10, 63, 64, 200])
def test_folded_constants_reproduce_real_dft_matrices(n):
    g, gi = df.real_dft_matrices(n)
    f, fi = df.folded_dft_matrices(n)
    m = (n + 1) // 2
    assert f.shape == fi.shape == (2, m, m)
    rng = np.random.default_rng(n)
    x = rng.standard_normal((5, n))
    fold = df.fold_matrices(n)[0].astype(np.float64)
    a, b = x @ fold[0].T, x @ fold[1].T
    np.testing.assert_array_equal(a[:, 0], x[:, 0] + x[:, -1])
    # a half-sample shift turns G's spectrum into the folded one:
    # exp(-i pi k / n) X[k] = C[k] - i S[k]
    rot = _spectrum(g, x) * np.exp(-1j * np.pi * np.arange(n // 2 + 1) / n)
    c, s = a @ f[0].T.astype(np.float64), b @ f[1].T.astype(np.float64)
    np.testing.assert_allclose(c, rot.real[..., :m], atol=1e-5 * n)
    ks = np.arange(1, min(m, n // 2) + 1)
    np.testing.assert_allclose(s[..., ks - 1], -rot.imag[..., ks],
                               atol=1e-5 * n)
    # and the inverse pair maps a spectrum back as Gi does, in folds
    y = rng.standard_normal((5, n))
    xi = y @ gi.T.astype(np.float64)
    rot = _spectrum(np.linalg.inv(gi.astype(np.float64)), xi) * np.exp(
        -1j * np.pi * np.arange(n // 2 + 1) / n)
    c, s = rot.real[..., :m], np.zeros((5, m))
    s[..., ks - 1] = -rot.imag[..., ks]
    want_a, want_b = xi @ fold[0].T, xi @ fold[1].T
    np.testing.assert_allclose(c @ fi[0].T, want_a, atol=1e-5)
    np.testing.assert_allclose(s @ fi[1].T, want_b, atol=1e-5)


@pytest.mark.parametrize("n", [48, 49])
@pytest.mark.parametrize("method", [df.RS, df.FRESNEL])
@pytest.mark.parametrize("band_limit", [True, False])
def test_forced_folded_scan_equals_fft_path(monkeypatch, n, method,
                                            band_limit):
    # a modulated multi-layer plan over batch and channel axes: the scan
    # carries the folded blocks and each layer mixes them
    rng = np.random.default_rng(n)
    phis = jnp.asarray(rng.uniform(0, 2 * np.pi, (3, 3, n, n)), jnp.float32)
    u = jnp.asarray(_field((2, 3, n, n), seed=5))

    def run():
        plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3] * 4, 532e-9,
                                  method=method, band_limit=band_limit,
                                  gamma=1.1)
        assert plan._packable
        return jax.jit(plan.apply)(phis, u)

    _close(*_on_both_paths(monkeypatch, run, fold=True))


@pytest.mark.parametrize("n", [32, 33])
def test_forced_folded_phase_gradients_match_fft_path(monkeypatch, n):
    rng = np.random.default_rng(n)
    phis = jnp.asarray(rng.uniform(0, 2 * np.pi, (2, 3, n, n)), jnp.float32)
    u = jnp.asarray(_field((2, 3, n, n), seed=6))

    def run():
        plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.05] * 3, 532e-9)
        loss = lambda p, v: jnp.sum(jnp.abs(plan.apply(p, v)) ** 4)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(phis, u)

    got, want = _on_both_paths(monkeypatch, run, fold=True)
    for g, w in zip(got, want):
        _close(g, w, rtol=1e-4)


@pytest.mark.parametrize("n", [9, 63])
def test_quadrants_of_uneven_blocks_at_odd_n(monkeypatch, n):
    # an even plane that is not symmetric under a transpose, so a block
    # meeting the wrong quadrant, or a row offset taken for a column one,
    # shows; at odd n the sine block's padded row meets H[m]
    rng = np.random.default_rng(n)
    half = rng.standard_normal((2, n // 2 + 1, n // 2 + 1))
    k = np.minimum(np.arange(n), n - np.arange(n))
    h = (half[0] + 1j * half[1])[k[:, None], k[None, :]]
    assert df.is_even_plane(h.real) and not np.allclose(h, h.T)
    monkeypatch.setattr(pp, "_folded_hop_applies", lambda n: True)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3], 532e-9)
    u = _field((2, n, n), seed=7)
    want = np.fft.ifft2(np.fft.fft2(u.astype(np.complex128)) * h)
    got = jax.jit(plan._packed_hop)(
        jnp.asarray(u), (jnp.asarray(h.real, jnp.float32),
                         jnp.asarray(h.imag, jnp.float32)))
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 2e-6


@pytest.mark.parametrize("n", [10, 63, 64])
@pytest.mark.parametrize("method", [df.RS, df.FRESNEL])
def test_folded_hop_equals_fft_hop(monkeypatch, n, method):
    monkeypatch.setattr(pp, "_folded_hop_applies", lambda n: True)
    plan = pp.PropagationPlan(df.Grid(n, 36e-6), [0.3], 532e-9,
                              method=method)
    h = df.transfer_function(plan.grid, 0.3, 532e-9, method, True)
    u = _field((2, 3, n, n))
    want = np.fft.ifft2(np.fft.fft2(u.astype(np.complex128)) * h)
    got = jax.jit(plan._packed_hop)(
        jnp.asarray(u), (jnp.asarray(h.real), jnp.asarray(h.imag)))
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 2e-6


def test_folded_rule_is_the_measured_sizes(monkeypatch):
    monkeypatch.setattr(pp.jax, "default_backend", lambda: "tpu")
    assert [pp._packed_hop_applies(n) and pp._folded_hop_applies(n)
            for n in (200, 256, 350, 384, 385, 400, 500, 512)] == [
        False, False, False, False, True, True, True, True]
