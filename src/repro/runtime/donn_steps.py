"""pjit train step for the paper's DONN workloads (beyond-paper distribution).

The paper trains on a single GPU (multi-GPU is named as future work, §6);
here DONN training runs on the one 2-D ``(data, model)`` mesh
(``sharding.make_mesh_2d`` + the ``sharding.donn_rules`` logical-axis
table): the batch shards over ``data``, field rows (``field_h``) shard
over ``model`` with the pencil-decomposed FFT inside the fused layer
scan, and both compose — spatial x data-parallel gradients through one
``shard_map`` (``make_donn_sharded_loss`` /
``compile_donn_train_step_sharded``, every model family including
heterogeneous ``SegmentedPlan`` stacks).

Heterogeneous per-layer architectures (``DONNConfig.layers``) ride the
same steps unchanged: the phase params form a *ragged* pytree (one
(n_i, n_i) leaf per layer, shapes varying across segments), and every
state/sharding transform here is a ``jax.tree`` map over ParamSpec
leaves, so per-layer plane sizes need no special casing
(tests/test_hetero.py::TestHeterogeneousForward::test_train_step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.config import DONNConfig
from repro.core.models import cached_model
from repro.core.train_utils import bce_segmentation_loss, mse_softmax_loss
from repro.nn import ParamSpec, is_spec
from repro.optim import AdamW
from repro.optim.adamw import AdamWState
from repro.runtime import sharding as shd

DONN_RULES = {**shd.DEFAULT_RULES, "batch": ("pod", "data", "model")}


def donn_state_specs(cfg: DONNConfig):
    model = cached_model(cfg)
    pspecs = model.param_specs()

    def opt_spec(s):
        return ParamSpec(s.shape, jnp.float32, s.logical_axes, init="zeros")

    return {
        "params": pspecs,
        "mu": jax.tree.map(opt_spec, pspecs, is_leaf=is_spec),
        "nu": jax.tree.map(opt_spec, pspecs, is_leaf=is_spec),
        "step": ParamSpec((), jnp.int32, (), init="zeros"),
    }


def make_donn_train_step(cfg: DONNConfig, optimizer: AdamW):
    model = cached_model(cfg)

    def loss_fn(params, batch):
        if cfg.segmentation:
            inten = model.apply(params, batch["images"], train=True)
            return bce_segmentation_loss(inten, batch["masks"])
        logits = model.apply(params, batch["images"])
        return mse_softmax_loss(logits, batch["labels"], cfg.num_classes)

    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        new_p, new_opt = optimizer.update(
            grads, AdamWState(state["mu"], state["nu"]),
            state["params"], state["step"],
        )
        return (
            {"params": new_p, "mu": new_opt.mu, "nu": new_opt.nu,
             "step": state["step"] + 1},
            {"loss": loss},
        )

    return step


def make_donn_train_chunk(cfg: DONNConfig, optimizer: AdamW = None):
    """Multi-step scanned driver over a stacked batch chunk.

    Returns ``chunk(state, batches) -> (state, {"loss": (S,)})`` running
    one optimizer step per leading row of ``batches`` (every leaf carries
    a leading chunk axis, see ``repro.data.pipeline.stack_batches``) as a
    single ``lax.scan`` — epochs, not forwards, become the unit of
    compiled work.  Covers every ``make_donn_train_step`` workload
    (classification and segmentation, any engine/codesign config).  Wrap
    in ``jax.jit(..., donate_argnums=(0,))`` — or use
    ``compile_donn_train_chunk`` — so the state is donated and per-step
    losses come back as one device-resident (S,) array (one host sync per
    chunk).
    """
    optimizer = optimizer or AdamW(lr=0.01)
    return _chunk_over(make_donn_train_step(cfg, optimizer))


def _chunk_over(step):
    """Lift a ``step(state, batch)`` fn to a scan over a stacked chunk."""

    def chunk(state, batches):
        def body(st, b):
            st, metrics = step(st, b)
            return st, metrics["loss"]

        state, losses = jax.lax.scan(body, state, batches)
        return state, {"loss": losses}

    return chunk


def _batch_shardings(cfg: DONNConfig, mesh, rules, global_batch=None):
    """Per-workload batch shardings (dim 0 over the DP axes)."""
    bs = lambda ndim: shd.batch_sharding(mesh, ndim, rules,
                                         batch_size=global_batch)
    if cfg.segmentation:
        return {"images": bs(3), "masks": bs(3)}
    if cfg.channels > 1:
        return {"images": bs(4), "labels": bs(1)}
    return {"images": bs(3), "labels": bs(1)}


def compile_donn_train_chunk(cfg: DONNConfig, mesh, optimizer=None,
                             donate: bool = True,
                             global_batch: int | None = None):
    """Compiled chunked training: scan ``S`` donated steps per device call.

    The chunked sibling of ``compile_donn_train_step``: batches arrive
    stacked ``(S, B, ...)`` (batch axis data-parallel over the mesh, chunk
    axis unsharded), (params, opt buffers, step) are donated so chunk k+1
    reuses chunk k's state allocations, and the per-step losses return as
    one (S,) array.  Returns ``(fn, state_shardings, batch_shardings,
    state_specs)`` like its sibling.
    """
    from jax.sharding import NamedSharding

    optimizer = optimizer or AdamW(lr=0.01)
    sspecs = donn_state_specs(cfg)
    s_shard = shd.tree_shardings(sspecs, mesh, DONN_RULES)
    b_shard = _batch_shardings(cfg, mesh, DONN_RULES, global_batch)
    # shift the batch sharding right of the leading (unsharded) chunk axis
    b_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, shd.with_leading(s.spec)), b_shard
    )
    chunk = make_donn_train_chunk(cfg, optimizer)

    def run(state, batches):
        # activation constraints (SegmentedPlan stitch carries stay
        # batch-sharded) resolve against this mesh at trace time
        with shd.activation_sharding(mesh, DONN_RULES):
            return chunk(state, batches)

    fn = jax.jit(
        run,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, {"loss": shd.scalar_sharding(mesh)}),
        donate_argnums=(0,) if donate else (),
    )
    return fn, s_shard, b_shard, sspecs


def compile_donn_train_step_shardmap(cfg: DONNConfig, mesh, optimizer=None,
                                     donate: bool = True,
                                     global_batch: int | None = None):
    """Optimized DONN training: shard_map data parallelism.

    GSPMD cannot partition the FFT HLO even over pure batch dims — the
    auto-sharded (pjit) step all-gathers the whole global field for every
    FFT2/iFFT2 (see EXPERIMENTS.md §Perf).  Under shard_map each device
    runs the *entire* optical forward/backward on its local batch shard
    (local FFTs), and only the (tiny, phase-sized) gradients are psum'd —
    the textbook DP layout for a small-parameter model.
    """
    from jax import shard_map

    optimizer = optimizer or AdamW(lr=0.01)
    sspecs = donn_state_specs(cfg)
    s_shard = shd.tree_shardings(sspecs, mesh, {})  # params replicated
    dp_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    if global_batch is not None:  # drop axes until the batch divides
        import math as _math

        while dp_axes and global_batch % _math.prod(
            mesh.shape[a] for a in dp_axes
        ) != 0:
            dp_axes = dp_axes[:-1]
        if not dp_axes:
            raise ValueError(f"batch {global_batch} unshardable on {mesh}")

    # hoisted out of the loss closure: shard_map retraces (and fresh meshes)
    # reuse one cached layer stack instead of rebuilding it per trace
    model = cached_model(cfg)

    def local_step(state, batch):
        def loss_fn(params, b):
            if cfg.segmentation:
                inten = model.apply(params, b["images"], train=True)
                return bce_segmentation_loss(inten, b["masks"])
            logits = model.apply(params, b["images"])
            return mse_softmax_loss(logits, b["labels"], cfg.num_classes)

        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        loss = jax.lax.pmean(loss, dp_axes)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp_axes), grads)
        new_p, new_opt = optimizer.update(
            grads, AdamWState(state["mu"], state["nu"]),
            state["params"], state["step"],
        )
        return (
            {"params": new_p, "mu": new_opt.mu, "nu": new_opt.nu,
             "step": state["step"] + 1},
            {"loss": loss},
        )

    batch_spec = shd.dim0_pspec(dp_axes, 1)
    target = "masks" if cfg.segmentation else "labels"
    b_specs = {"images": batch_spec, target: batch_spec}
    state_specs_sm = jax.tree.map(lambda _: shd.replicated_pspec(), sspecs)
    fn = jax.jit(
        shard_map(
            local_step, mesh=mesh,
            in_specs=(state_specs_sm, b_specs),
            out_specs=(state_specs_sm, {"loss": shd.replicated_pspec()}),
            check_vma=False,
        ),
        donate_argnums=(0,) if donate else (),
    )
    b_shard = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), b_specs
    )
    return fn, s_shard, b_shard, sspecs


def _check_sharded_support(cfg: DONNConfig) -> None:
    """Config gates shared by every spatially-sharded path."""
    resolved = cfg.resolved_layers()
    if cfg.pad or any(l.approximation == "fraunhofer" for l in resolved):
        raise NotImplementedError(
            "spatial sharding needs unpadded angular-spectrum hops"
        )
    if any(l.codesign in ("gumbel", "gumbel_hard") for l in resolved):
        raise NotImplementedError(
            "stochastic codesign draws per-element noise: row shards "
            "would sample different streams than the single-device step"
        )
    if cfg.use_pallas:
        raise NotImplementedError(
            "the fused Pallas kernels operate on full planes"
        )
    if cfg.tf_dtype != "float32":
        raise NotImplementedError(
            "spatial sharding reads the plan's f32 TF planes; the bf16 "
            "storage path would silently diverge from the single-device "
            "reference tolerance"
        )


def _plan_tf_stacks(plan):
    """The plan's baked split TF planes as traced shard_map operands."""
    key_a, key_b = plan._plane_keys
    return jnp.asarray(plan._np[key_a]), jnp.asarray(plan._np[key_b])


def make_donn_sharded_loss(cfg: DONNConfig, mesh, rules=None):
    """Unified spatial x data-parallel loss on the 2-D ``(data, model)`` mesh.

    Returns ``loss_fn(params, batch) -> scalar`` whose optical forward
    runs under ``shard_map`` with the batch sharded over the ``data``
    axis and every plane (field, TF stacks, trainable phases, detector
    masks) row-sharded over the ``model`` axis, each hop of the fused
    layer scan using the pencil-decomposed local FFT
    (``repro.runtime.pencil_fft.local_spectral_pair`` as the plan's
    ``spectral=`` override).  One rules table
    (``sharding.donn_rules``) decides both layouts; either axis may be
    absent from the mesh — batch-only meshes give pure DP, model-only
    meshes the PR-4 spatial layout, and the 2-D mesh composes them
    (spatial x DP gradients: the shard_map transpose psums phase
    cotangents over ``data`` automatically).

    Covers every model family:

    - **classification** (single channel): detector readout psums the
      per-class partial intensities over ``model``;
    - **multi-channel / RGB**: the ``(L, C, N, N)`` phase stack and the
      ``(B, C, N, N)`` field ride the same scan with ``channel``
      replicated (the generalized pencil FFT carries leading dims);
    - **segmentation with optical skip**: the skip hop runs the same
      local spectral pair on its row shard; the intensity map returns
      batch x row sharded, and layer-norm + BCE run outside the
      shard_map in auto (GSPMD) land;
    - **heterogeneous `SegmentedPlan`**: one shard_map per scan segment
      (per-segment specs), the resampling stitches run *between* the
      manual regions where GSPMD reshards them (``constrain`` keeps the
      stitched carry batch-sharded).

    Differentiable: ``jax.value_and_grad`` agrees with the single-device
    loss to rtol <= 1e-5 for all families (tests/test_distributed.py).
    See ``compile_donn_train_step_sharded`` for the compiled step.
    """
    from jax import shard_map
    from repro.core import diffraction as df
    from repro.core import propagation as pp
    from repro.core.laser import data_to_cplex
    from repro.core.train_utils import mse_softmax_loss as _mse

    cfg = cfg.canonical()
    rules = shd.check_rules(dict(rules or shd.donn_rules()))
    _check_sharded_support(cfg)

    model_axis = shd.present_axes(mesh, rules.get("field_h"))
    if model_axis is not None and not isinstance(model_axis, str):
        raise shd.ShardingRulesError(
            f"field_h must map to a single mesh axis for the pencil FFT "
            f"(all_to_all transposes over one named axis), got {model_axis!r}"
        )
    k = int(mesh.shape[model_axis]) if model_axis is not None else 1
    spectral = None
    if k > 1:
        from repro.runtime.pencil_fft import local_spectral_pair

        spectral = local_spectral_pair(model_axis, k)

    model = cached_model(cfg)
    rp = lambda names: shd.rules_pspec(names, rules, mesh)
    plane = rp(("layers", "field_h", "field_w"))  # (L, n/k rows, n) stacks

    def _psum_model(x):
        return jax.lax.psum(x, model_axis) if k > 1 else x

    if cfg.layers is not None:
        # ---- heterogeneous SegmentedPlan: one manual region per scan
        # segment, stitches reshard between them in auto land ----
        if cfg.segmentation or cfg.channels > 1:
            raise NotImplementedError(
                "sharded SegmentedPlan covers the classification family"
            )
        plan = model.plan
        if k > 1:
            for j, seg in enumerate(plan.segments):
                if seg.grid.n % k != 0:
                    raise ValueError(
                        f"segment {j} grid n={seg.grid.n} rows must divide "
                        f"the {k}-way {model_axis!r} axis"
                    )
        seg_tfs = [_plan_tf_stacks(s) for s in plan.segments]
        masks = jnp.asarray(model.detector.masks)
        source = jnp.asarray(model.source)
        in_n, depth = plan.input_grid.n, plan.depth
        u_spec = rp(("batch", "field_h", "field_w"))
        field_axes = ("batch", "field_h", "field_w")

        def make_seg_fn(seg, last):
            def body(phis, a, b, u):
                return seg.forward(phis, u, None, tfs=(a, b),
                                   spectral=spectral, final=last)

            return shard_map(body, mesh=mesh,
                             in_specs=(plane, plane, plane, u_spec),
                             out_specs=u_spec, check_vma=False)

        seg_fns = [make_seg_fn(s, j == len(plan.segments) - 1)
                   for j, s in enumerate(plan.segments)]

        def loss_fn(params, batch):
            with shd.activation_sharding(mesh, rules):
                phis = plan.stack_phases(
                    [params["phase"][f"layer_{i}"] for i in range(depth)]
                )
                with pp.stage("encode"):
                    u = data_to_cplex(batch["images"], in_n) * source
                u = shd.constrain(u, field_axes)
                cur = plan.input_grid
                for j, seg in enumerate(plan.segments):
                    if seg.grid != cur:
                        u = df.resample_field(u, cur, seg.grid)
                        u = shd.constrain(u, field_axes)
                    a, b = seg_tfs[j]
                    u = seg_fns[j](phis[j], a, b, u)
                    cur = seg.grid
                if plan.det_grid != cur:
                    u = df.resample_field(u, cur, plan.det_grid)
                    u = shd.constrain(u, field_axes)
                logits = df.readout(u, masks)
                return _mse(logits, batch["labels"], cfg.num_classes)

        return loss_fn

    # ---- uniform stacks: one manual region around the whole forward ----
    if k > 1 and cfg.n % k != 0:
        raise ValueError(f"n={cfg.n} rows must divide the {k}-way "
                         f"{model_axis!r} axis")

    if cfg.segmentation:
        plan = model.plan
        tf_a, tf_b = _plan_tf_stacks(plan)
        source = jnp.asarray(model.source)
        in_n, depth = model.in_grid.n, plan.depth
        u_spec = rp(("batch", "field_h", "field_w"))
        skip_from = cfg.skip_from
        sqrt2 = jnp.sqrt(2.0).astype(jnp.complex64)
        if skip_from is not None:
            gaps = cfg.gap_distances()
            z_skip = float(sum(gaps[skip_from + 1:]))
            planes = pp.transfer_planes(
                model.layers[skip_from].grid, z_skip, cfg.wavelength,
                cfg.resolved_layers()[skip_from].approximation,
                cfg.band_limit, cfg.pad,
            )
            sk_a = jnp.asarray(planes["hr"])
            sk_b = jnp.asarray(planes["hi"])

            def local_map(phis, a, b, sa, sb, u):
                u1 = plan.forward(phis, u, None, stop=skip_from + 1,
                                  tfs=(a, b), spectral=spectral)
                u2 = plan.forward(phis, u1, None, start=skip_from + 1,
                                  tfs=(a, b), spectral=spectral, final=True)
                sk = plan._hop(u1, (sa, sb), spectral)
                return df.intensity((u2 + sk) / sqrt2)

            row2 = rp(("field_h", "field_w"))
            sharded_map = shard_map(
                local_map, mesh=mesh,
                in_specs=(plane, plane, plane, row2, row2, u_spec),
                out_specs=u_spec, check_vma=False,
            )
            fwd = lambda phis, u0: sharded_map(phis, tf_a, tf_b,
                                               sk_a, sk_b, u0)
        else:

            def local_map(phis, a, b, u):
                u = plan.forward(phis, u, None, tfs=(a, b), spectral=spectral,
                                 final=True)
                return df.intensity(u)

            sharded_map = shard_map(
                local_map, mesh=mesh,
                in_specs=(plane, plane, plane, u_spec),
                out_specs=u_spec, check_vma=False,
            )
            fwd = lambda phis, u0: sharded_map(phis, tf_a, tf_b, u0)

        def loss_fn(params, batch):
            with shd.activation_sharding(mesh, rules):
                phis = plan.stack_phases(
                    [params["phase"][f"layer_{i}"] for i in range(depth)]
                )
                with pp.stage("encode"):
                    u0 = data_to_cplex(batch["images"], in_n) * source
                inten = fwd(phis, u0)
                if cfg.layer_norm:  # train=True semantics (the step's loss)
                    mean = jnp.mean(inten, axis=(-2, -1), keepdims=True)
                    var = jnp.var(inten, axis=(-2, -1), keepdims=True)
                    inten = (inten - mean) * jax.lax.rsqrt(var + 1e-6)
                return bce_segmentation_loss(inten, batch["masks"])

        return loss_fn

    # classification: single channel or multi-channel/RGB
    if cfg.channels > 1:
        host = model.channel_model
        phi_spec = rp(("layers", "channel", "field_h", "field_w"))
        u_spec = rp(("batch", "channel", "field_h", "field_w"))
        readout = functools.partial(df.readout, channel_axis=True)
    else:
        host = model
        phi_spec = plane
        u_spec = rp(("batch", "field_h", "field_w"))
        readout = df.readout
    plan = host.plan
    tf_a, tf_b = _plan_tf_stacks(plan)
    masks = jnp.asarray(host.detector.masks)
    source = jnp.asarray(host.source)
    in_n, depth = host.in_grid.n, plan.depth
    mask_spec = rp(("classes", "field_h", "field_w"))

    def local_logits(phis, a, b, m, u):
        """Per-shard forward core: all plane operands are local row blocks."""
        u = plan.forward(phis, u, None, tfs=(a, b), spectral=spectral,
                         final=True)
        return _psum_model(readout(u, m))

    sharded_logits = shard_map(
        local_logits, mesh=mesh,
        in_specs=(phi_spec, plane, plane, mask_spec, u_spec),
        out_specs=rp(("batch", None)),
        check_vma=False,
    )

    def loss_fn(params, batch):
        with shd.activation_sharding(mesh, rules):
            phis = plan.stack_phases(
                [params["phase"][f"layer_{i}"] for i in range(depth)]
            )
            with pp.stage("encode"):
                u0 = data_to_cplex(batch["images"], in_n) * source
            logits = sharded_logits(phis, tf_a, tf_b, masks, u0)
            return _mse(logits, batch["labels"], cfg.num_classes)

    return loss_fn


def make_donn_spatial_loss(cfg: DONNConfig, mesh, axis: str = "model"):
    """Back-compat spatial-only loss: rows over ``axis``, batch replicated.

    Thin wrapper over :func:`make_donn_sharded_loss` with the batch rule
    disabled — the PR-4 layout.  New code should pass a 2-D mesh and the
    full ``sharding.donn_rules`` table instead.
    """
    rules = {**shd.donn_rules(model=axis), "batch": None, "population": None}
    return make_donn_sharded_loss(cfg, mesh, rules=rules)


def compile_donn_train_step_sharded(cfg: DONNConfig, mesh, rules=None,
                                    optimizer=None, donate: bool = True,
                                    steps_per_call: int = 1,
                                    global_batch: int | None = None):
    """Spatial x data-parallel DONN training on the unified 2-D mesh.

    The train-step compiler over :func:`make_donn_sharded_loss`: state
    (phases + optimizer moments) shards by the same rules table — rows
    over ``model``, replicated over ``data`` (each data shard owns the
    full row block; the shard_map transpose psums the batch-shard
    gradient contributions over ``data``) — and the batch shards over
    the DP axes.  For optical planes too large for one chip (n=1024+
    fields, arXiv:2302.10905-scale scientific workloads) this is the
    only runnable training path: no device ever materializes a full
    plane.  ``steps_per_call > 1`` scans a stacked batch chunk per
    device call (state donated).

    Returns ``(fn, state_shardings, batch_shardings, state_specs)``:
    ``fn(state, batch)`` for ``steps_per_call == 1`` (metrics
    ``{"loss": ()}``), ``fn(state, batches)`` with a leading chunk axis
    and ``{"loss": (S,)}`` otherwise.  Validated against the
    single-device step — loss and grads agree to rtol <= 1e-5 for all
    model families (tests/test_distributed.py).
    """
    from jax.sharding import NamedSharding

    optimizer = optimizer or AdamW(lr=0.01)
    rules = shd.check_rules(dict(rules or shd.donn_rules()))
    loss_fn = make_donn_sharded_loss(cfg, mesh, rules=rules)

    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        new_p, new_opt = optimizer.update(
            grads, AdamWState(state["mu"], state["nu"]),
            state["params"], state["step"],
        )
        return (
            {"params": new_p, "mu": new_opt.mu, "nu": new_opt.nu,
             "step": state["step"] + 1},
            {"loss": loss},
        )

    if steps_per_call > 1:
        step = _chunk_over(step)

    sspecs = donn_state_specs(cfg)
    s_shard = shd.tree_shardings(sspecs, mesh, rules)
    b_shard = _batch_shardings(cfg, mesh, rules, global_batch)
    if steps_per_call > 1:
        b_shard = jax.tree.map(
            lambda s: NamedSharding(mesh, shd.with_leading(s.spec)), b_shard
        )
    fn = jax.jit(
        step,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, {"loss": shd.scalar_sharding(mesh)}),
        donate_argnums=(0,) if donate else (),
    )
    return fn, s_shard, b_shard, sspecs


def compile_donn_train_step_spatial(cfg: DONNConfig, mesh, axis: str = "model",
                                    optimizer=None, donate: bool = True,
                                    steps_per_call: int = 1):
    """Back-compat spatial-only compiled step (batch replicated).

    Delegates to :func:`compile_donn_train_step_sharded` with the batch
    rule disabled — the PR-4 single-axis layout.  New code should build
    a ``make_mesh_2d`` mesh and call the sharded compiler directly.
    """
    rules = {**shd.donn_rules(model=axis), "batch": None, "population": None}
    return compile_donn_train_step_sharded(
        cfg, mesh, rules=rules, optimizer=optimizer, donate=donate,
        steps_per_call=steps_per_call,
    )


def compile_donn_train_step(cfg: DONNConfig, mesh, optimizer=None,
                            donate: bool = True,
                            global_batch: int | None = None):
    optimizer = optimizer or AdamW(lr=0.01)
    sspecs = donn_state_specs(cfg)
    s_shard = shd.tree_shardings(sspecs, mesh, DONN_RULES)
    b_shard = _batch_shardings(cfg, mesh, DONN_RULES, global_batch)
    step = make_donn_train_step(cfg, optimizer)

    def run(state, batch):
        with shd.activation_sharding(mesh, DONN_RULES):
            return step(state, batch)

    fn = jax.jit(
        run,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, {"loss": shd.scalar_sharding(mesh)}),
        donate_argnums=(0,) if donate else (),
    )
    return fn, s_shard, b_shard, sspecs
