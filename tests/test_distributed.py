"""Multi-device tests (run in a subprocess with 8 host-platform devices so
the main pytest process keeps a single device — see the dry-run rules).

The seed LM's sharded asserts and the DONN sharded asserts run in separate
subprocesses, so a failure in one family cannot sink the other's tests."""
import json
import textwrap

import pytest

from conftest import run_subprocess

SUITE = textwrap.dedent("""
    import json, dataclasses
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    results = {}

    # ---------------------------------------------------------- setup
    assert len(jax.devices()) == 8, len(jax.devices())
    from repro.launch.mesh import make_mesh
    from repro.models import get_config, lm
    from repro.runtime import sharding as shd, steps as steps_mod
    from repro.optim import AdamW

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_config("glm4-9b", smoke=True),
                              dtype=jnp.float32, d_model=64, n_layers=2)

    # 1. sharded train step runs; loss matches single-device exactly-ish
    B, S = 4, 32
    batch_specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    opt = AdamW(lr=1e-2)
    fn, s_shard, b_shard, sspecs = steps_mod.compile_train_step(
        cfg, mesh, batch_specs, optimizer=opt)
    state = steps_mod.init_train_state(cfg, jax.random.PRNGKey(0), opt)
    state_sh = jax.device_put(state, s_shard)
    r = np.random.default_rng(0)
    toks = r.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    batch = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
    batch_sh = jax.device_put(batch, b_shard)
    losses_sharded = []
    for i in range(3):
        state_sh, m = fn(state_sh, batch_sh)
        losses_sharded.append(float(m["loss"]))

    # single-device reference
    base = steps_mod.make_train_step(cfg, opt)
    state1 = steps_mod.init_train_state(cfg, jax.random.PRNGKey(0), opt)
    losses_single = []
    for i in range(3):
        state1, m1 = jax.jit(base)(state1, batch)
        losses_single.append(float(m1["loss"]))
    results["dp_tp_matches_single"] = bool(
        np.allclose(losses_sharded, losses_single, rtol=5e-4, atol=5e-4))
    results["losses"] = [losses_sharded, losses_single]

    # 3. compressed psum over a pod axis (shard_map)
    from jax import shard_map
    rr = np.random.default_rng(1)
    from repro.optim.compression import compressed_psum_mean
    mesh_pod = make_mesh((2, 4), ("pod", "data"))
    x = jnp.asarray(rr.normal(size=(2, 256)), jnp.float32)  # per-pod rows
    f = shard_map(lambda v: compressed_psum_mean(v, "pod"),
                  mesh=mesh_pod, in_specs=P("pod", None),
                  out_specs=P("pod", None), check_vma=False)
    got = f(x)
    want = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
    err = float(jnp.max(jnp.abs(got - want)))
    results["compressed_psum_err"] = err
    results["compressed_psum_ok"] = bool(err < np.abs(x).max() / 100)

    # 4. elastic checkpoint: save under mesh A, restore under mesh B
    import tempfile, pathlib
    from repro import checkpoint as ckpt
    d = tempfile.mkdtemp()
    ckpt.save(d, 5, state_sh)
    meshB = make_mesh((4, 2), ("data", "model"))
    s_shardB = shd.tree_shardings(sspecs, meshB)
    restored = ckpt.restore(d, 5, shd.abstract_like(sspecs),
                            shardings=s_shardB)
    ok = True
    for a, b in zip(jax.tree.leaves(state_sh), jax.tree.leaves(restored)):
        ok &= bool(jnp.allclose(jnp.asarray(a, jnp.float32),
                                jnp.asarray(b, jnp.float32)))
    results["elastic_reshard_ok"] = ok

    # 5. decode step under sharding: runs + finite
    fn_d, p_sh, c_sh, cspecs = steps_mod.compile_decode_step(cfg, mesh, 4, 32)
    params = jax.device_put(lm.init(cfg, jax.random.PRNGKey(0)), p_sh)
    cache = jax.device_put(lm.init_cache(cfg, 4, 32), c_sh)
    logits, cache = fn_d(params, cache, jnp.zeros((4, 1), jnp.int32),
                         jnp.int32(0))
    results["sharded_decode_finite"] = bool(
        jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    print("RESULTS:" + json.dumps(results))
""")


def _results(code: str) -> dict:
    proc = run_subprocess(code, device_count=8)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULTS:"):])


@pytest.fixture(scope="module")
def suite_results():
    return _results(SUITE)


# ---------------------------------------------------------------------------
# DONN suite: pencil FFT (standalone, gradients) and the spatially-sharded
# training loss and step against the single-device reference.
# ---------------------------------------------------------------------------
DONN_SUITE = textwrap.dedent("""
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    results = {}

    assert len(jax.devices()) == 8, len(jax.devices())
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding as shd

    # 2. pencil FFT vs fft2 — the supported in-scan entry
    # (local_spectral_pair composed under an explicit shard_map); the
    # standalone pencil_fft2 wrapper is deprecated but works one cycle
    import warnings
    from jax import shard_map
    from repro.runtime.pencil_fft import local_spectral_pair, pencil_fft2
    mesh8 = make_mesh((8,), ("model",))
    rr = np.random.default_rng(1)
    u = jnp.asarray(rr.normal(size=(2, 64, 128))
                    + 1j * rr.normal(size=(2, 64, 128)), jnp.complex64)
    fft2_loc, ifft2_loc = local_spectral_pair("model", 8)
    row_spec = shd.rules_pspec((None, "field_h", None),
                               {"field_h": "model"})
    got = shard_map(fft2_loc, mesh=mesh8, in_specs=row_spec,
                    out_specs=row_spec, check_vma=False)(u)
    want = jnp.fft.fft2(u)
    results["pencil_fft_ok"] = bool(np.allclose(np.asarray(got),
                                                np.asarray(want),
                                                rtol=2e-3, atol=2e-3))
    back = shard_map(ifft2_loc, mesh=mesh8, in_specs=row_spec,
                     out_specs=row_spec, check_vma=False)(got)
    results["pencil_ifft_ok"] = bool(np.allclose(np.asarray(back),
                                                 np.asarray(u),
                                                 rtol=2e-3, atol=2e-3))
    with warnings.catch_warnings(record=True) as wrec:
        warnings.simplefilter("always")
        dep_out = pencil_fft2(u, mesh8)
    results["pencil_fft2_deprecated"] = bool(
        any(issubclass(w.category, DeprecationWarning) for w in wrec)
        and np.allclose(np.asarray(dep_out), np.asarray(want),
                        rtol=2e-3, atol=2e-3))

    # 2b. pencil FFT gradients: value_and_grad of the distributed
    # angular-spectrum hop agrees with the single-device spectral hop
    from repro.runtime.pencil_fft import propagate_tf_distributed
    h = jnp.asarray(rr.normal(size=(64, 128))
                    + 1j * rr.normal(size=(64, 128)), jnp.complex64)

    def loss_dist(v):
        return jnp.sum(jnp.abs(propagate_tf_distributed(v, h, mesh8)) ** 2)

    def loss_ref(v):
        return jnp.sum(jnp.abs(jnp.fft.ifft2(jnp.fft.fft2(v) * h)) ** 2)

    vd, gd = jax.value_and_grad(loss_dist)(u)
    vr, gr = jax.value_and_grad(loss_ref)(u)
    g_scale = float(jnp.max(jnp.abs(gr)))
    results["pencil_grad_val_rel_err"] = abs(float(vd) - float(vr)) / abs(
        float(vr))
    results["pencil_grad_max_rel_err"] = float(
        jnp.max(jnp.abs(gd - gr))) / g_scale
    results["pencil_grad_ok"] = bool(
        results["pencil_grad_val_rel_err"] <= 1e-5
        and results["pencil_grad_max_rel_err"] <= 1e-5)

    # 2c. in-scan usage: the spatially-sharded DONN training loss (pencil
    # FFT inside the fused layer scan, row-sharded planes) matches the
    # single-device step — loss and grads to rtol <= 1e-5, and one
    # compiled spatial train step tracks the reference step
    from repro.core.config import DONNConfig
    from repro.core.models import cached_model
    from repro.core.train_utils import mse_softmax_loss
    from repro.nn import init_params
    from repro.runtime import donn_steps as ds

    cfg_sp = DONNConfig(name="sp", n=64, depth=4, distance=0.05, det_size=8)
    sspecs_sp = ds.donn_state_specs(cfg_sp)
    state_sp = init_params(sspecs_sp, jax.random.PRNGKey(0))
    rsp = np.random.default_rng(3)
    batch_sp = {
        "images": rsp.uniform(0, 1, (8, 28, 28)).astype(np.float32),
        "labels": rsp.integers(0, 10, (8,)).astype(np.int32),
    }
    loss_sp = ds.make_donn_spatial_loss(cfg_sp, mesh8)
    donn = cached_model(cfg_sp)
    loss_1d = lambda p, b: mse_softmax_loss(
        donn.apply(p, b["images"]), b["labels"], cfg_sp.num_classes)
    v1, g1 = jax.jit(jax.value_and_grad(loss_1d))(state_sp["params"],
                                                  batch_sp)
    v2, g2 = jax.jit(jax.value_and_grad(loss_sp))(state_sp["params"],
                                                  batch_sp)
    gmax = max(float(jnp.max(jnp.abs(g)))
               for g in jax.tree.leaves(g1))
    results["spatial_loss_rel_err"] = abs(float(v1) - float(v2)) / abs(
        float(v1))
    results["spatial_grad_max_rel_err"] = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))
    ) / gmax
    results["spatial_loss_grads_ok"] = bool(
        results["spatial_loss_rel_err"] <= 1e-5
        and results["spatial_grad_max_rel_err"] <= 1e-5)

    from repro.optim import AdamW as _AdamW
    fn_sp, s_sh_sp, b_sh_sp, _ = ds.compile_donn_train_step_spatial(
        cfg_sp, mesh8, optimizer=_AdamW(lr=0.05))
    st_sp = jax.device_put(jax.tree.map(jnp.array, state_sp), s_sh_sp)
    b_dev = jax.device_put(batch_sp, b_sh_sp)
    ref_step = jax.jit(ds.make_donn_train_step(cfg_sp, _AdamW(lr=0.05)))
    st_ref = jax.tree.map(jnp.array, state_sp)
    sp_losses, ref_losses = [], []
    for _ in range(2):
        st_sp, m_sp = fn_sp(st_sp, b_dev)
        st_ref, m_ref = ref_step(st_ref, batch_sp)
        sp_losses.append(float(m_sp["loss"]))
        ref_losses.append(float(m_ref["loss"]))
    p_scale = max(float(jnp.max(jnp.abs(p)))
                  for p in jax.tree.leaves(st_ref["params"]))
    results["spatial_step_param_rel_err"] = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(st_sp["params"]),
            jax.tree.leaves(st_ref["params"]))
    ) / p_scale
    # losses track at the grad tolerance; the *param* tolerance is looser
    # because Adam's normalized update amplifies O(1e-6) grad differences
    # to O(lr) wherever the gradient is near zero (sign flips in
    # mh/sqrt(vh)) — inherent to Adam, not to the sharded forward
    results["spatial_step_ok"] = bool(
        np.allclose(sp_losses, ref_losses, rtol=1e-5, atol=1e-7)
        and results["spatial_step_param_rel_err"] <= 2e-3)

    print("RESULTS:" + json.dumps(results))
""")


@pytest.fixture(scope="module")
def donn_suite_results():
    return _results(DONN_SUITE)


def test_dp_tp_matches_single_device(suite_results):
    assert suite_results["dp_tp_matches_single"], suite_results["losses"]


def test_pencil_fft_matches_fft2(donn_suite_results):
    assert donn_suite_results["pencil_fft_ok"]
    assert donn_suite_results["pencil_ifft_ok"]


def test_pencil_fft2_standalone_deprecated_but_working(donn_suite_results):
    assert donn_suite_results["pencil_fft2_deprecated"]


def test_pencil_fft_gradients_match_single_device(donn_suite_results):
    assert donn_suite_results["pencil_grad_ok"], (
        donn_suite_results["pencil_grad_val_rel_err"],
        donn_suite_results["pencil_grad_max_rel_err"],
    )


def test_spatial_train_loss_and_grads_match(donn_suite_results):
    assert donn_suite_results["spatial_loss_grads_ok"], (
        donn_suite_results["spatial_loss_rel_err"],
        donn_suite_results["spatial_grad_max_rel_err"],
    )


def test_spatial_train_step_tracks_reference(donn_suite_results):
    assert donn_suite_results["spatial_step_ok"], donn_suite_results[
        "spatial_step_param_rel_err"]


def test_compressed_psum(suite_results):
    assert suite_results["compressed_psum_ok"], suite_results[
        "compressed_psum_err"]


def test_elastic_checkpoint_reshard(suite_results):
    assert suite_results["elastic_reshard_ok"]


def test_sharded_decode(suite_results):
    assert suite_results["sharded_decode_finite"]


# ---------------------------------------------------------------------------
# Suite 2: the unified 2-D (data, model) mesh — spatial x DP parity for
# every DONN family, the compiled sharded train step, rules-table edge
# cases, and row-sharded frozen serving (ISSUE 10).
# ---------------------------------------------------------------------------
SUITE2 = textwrap.dedent("""
    import json, warnings
    import numpy as np
    import jax, jax.numpy as jnp
    results = {}
    assert len(jax.devices()) == 8, len(jax.devices())

    from repro.core.config import DONNConfig, LayerSpec
    from repro.core.models import cached_model
    from repro.core.train_utils import (
        bce_segmentation_loss, mse_softmax_loss,
    )
    from repro.nn import init_params
    from repro.optim import AdamW
    from repro.runtime import donn_steps as ds
    from repro.runtime import sharding as shd

    mesh = shd.make_mesh_2d(data=2, model=4)
    key = jax.random.PRNGKey(0)

    # ---- 1. spatial x DP parity vs single device, all model families
    def parity(tag, cfg, batch):
        m = cached_model(cfg)
        params = m.init(key)
        loss_fn = ds.make_donn_sharded_loss(cfg, mesh)

        def ref_fn(p, b):
            if cfg.segmentation:
                return bce_segmentation_loss(
                    m.apply(p, b["images"], train=True), b["masks"])
            return mse_softmax_loss(
                m.apply(p, b["images"]), b["labels"], cfg.num_classes)

        l1, g1 = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        l0, g0 = jax.jit(jax.value_and_grad(ref_fn))(params, batch)
        rel_l = abs(float(l1) - float(l0)) / max(abs(float(l0)), 1e-12)
        rel_g = max(
            float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)))
        results[tag] = {"rel_loss": rel_l, "max_rel_grad": rel_g,
                        "ok": bool(rel_l <= 1e-5 and rel_g <= 1e-5)}

    imgs = jax.random.uniform(key, (8, 28, 28))
    labels = jnp.arange(8) % 10
    cfg_cls = DONNConfig(name="cls2d", n=64, depth=4, distance=0.05,
                         det_size=8)
    parity("cls", cfg_cls, {"images": imgs, "labels": labels})
    parity("rgb",
           DONNConfig(name="rgb2d", n=64, depth=2, distance=0.05,
                      det_size=8, channels=3),
           {"images": jax.random.uniform(key, (8, 3, 28, 28)),
            "labels": labels})
    parity("seg",
           DONNConfig(name="seg2d", n=64, depth=3, distance=0.05,
                      segmentation=True, skip_from=0, layer_norm=True),
           {"images": imgs,
            "masks": (jax.random.uniform(key, (8, 64, 64)) > 0.5)
            .astype(jnp.float32)})
    # heterogeneous SegmentedPlan (64 -> 48 grids): one shard_map per
    # segment, the resampling stitches resharded between manual regions
    parity("het",
           DONNConfig(name="het2d", n=64, depth=3, distance=0.05,
                      det_size=8,
                      layers=(LayerSpec(distance=0.05, size=64),
                              LayerSpec(distance=0.05, size=48),
                              LayerSpec(distance=0.05, size=48))),
           {"images": imgs, "labels": labels})

    # ---- 2. compiled sharded train step tracks the reference step
    fn2, s_sh2, b_sh2, _ = ds.compile_donn_train_step_sharded(
        cfg_cls, mesh, optimizer=AdamW(lr=0.05), global_batch=8)
    st0 = init_params(ds.donn_state_specs(cfg_cls), jax.random.PRNGKey(1))
    batch_cls = {"images": np.asarray(imgs, np.float32),
                 "labels": np.asarray(labels, np.int32)}
    st2 = jax.device_put(jax.tree.map(jnp.array, st0), s_sh2)
    b_dev = jax.device_put(batch_cls, b_sh2)
    ref_step = jax.jit(ds.make_donn_train_step(cfg_cls, AdamW(lr=0.05)))
    st_ref = jax.tree.map(jnp.array, st0)
    l2, lref = [], []
    for _ in range(2):
        st2, m2 = fn2(st2, b_dev)
        st_ref, mref = ref_step(st_ref, batch_cls)
        l2.append(float(m2["loss"]))
        lref.append(float(mref["loss"]))
    pscale = max(float(jnp.max(jnp.abs(p)))
                 for p in jax.tree.leaves(st_ref["params"]))
    perr = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(st2["params"]),
            jax.tree.leaves(st_ref["params"]))) / pscale
    # same Adam-amplification caveat as the 1-D spatial step above:
    # losses at grad tolerance, params at 2e-3
    results["sharded_step"] = {
        "losses": [l2, lref], "param_rel_err": perr,
        "ok": bool(np.allclose(l2, lref, rtol=1e-5, atol=1e-7)
                   and perr <= 2e-3)}

    # ---- 3. rules-table edge cases (typed, not silent)
    sp = shd.resolve_pspec((66, 64), ("field_h", "field_w"), mesh,
                           shd.donn_rules())
    results["nondivisible_replicated"] = bool(tuple(sp) == ())
    try:
        shd.check_rules({**shd.donn_rules(), "field_h": "data"})
        results["check_rules_raises"] = False
    except shd.ShardingRulesError:
        results["check_rules_raises"] = True
    try:
        shd.resolve_pspec((8, 64, 64), ("batch", "field_h", "field_w"),
                          mesh, {**shd.DEFAULT_RULES, "batch": "model",
                                 "field_h": "model"})
        results["resolve_collision_raises"] = False
    except shd.ShardingRulesError:
        results["resolve_collision_raises"] = True
    try:
        shd.rules_pspec(("field_h", "field_h"), shd.donn_rules(), mesh)
        results["rules_dup_raises"] = False
    except shd.ShardingRulesError:
        results["rules_dup_raises"] = True

    # ---- 4. frozen-plane row-sharded serving: parity + bit-consistency
    from repro.runtime.inference import freeze, InferenceEngine
    model = cached_model(cfg_cls)
    params = model.init(key)
    dep = freeze(model, params)
    x = np.random.default_rng(7).random((8, 28, 28), np.float32)
    ref = InferenceEngine(dep, buckets=(8,)).infer(x)
    eng = InferenceEngine(dep, buckets=(8,), mesh_devices=2,
                          model_devices=4, dp_min_bucket=8)
    got = eng.infer(x)
    rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    results["serving"] = {
        "rel_err": rel,
        "bit_consistent": bool(np.array_equal(got, eng.infer(x))),
        "ok": bool(rel <= 1e-5)}

    print("RESULTS:" + json.dumps(results))
""")


@pytest.fixture(scope="module")
def suite2_results():
    return _results(SUITE2)


@pytest.mark.parametrize("family", ["cls", "rgb", "seg", "het"])
def test_2d_mesh_parity(suite2_results, family):
    assert suite2_results[family]["ok"], suite2_results[family]


def test_2d_mesh_sharded_train_step_tracks_reference(suite2_results):
    assert suite2_results["sharded_step"]["ok"], suite2_results[
        "sharded_step"]


def test_nondivisible_field_h_drops_to_replicated(suite2_results):
    assert suite2_results["nondivisible_replicated"]


def test_rules_table_collisions_raise_typed_errors(suite2_results):
    assert suite2_results["check_rules_raises"]
    assert suite2_results["resolve_collision_raises"]
    assert suite2_results["rules_dup_raises"]


def test_row_sharded_serving_parity_and_bit_consistency(suite2_results):
    assert suite2_results["serving"]["ok"], suite2_results["serving"]
    assert suite2_results["serving"]["bit_consistent"]
