"""Deployment inference engine: frozen DONNs served fast (LightRidge pillar 3).

PRs 1-4 optimized training, emulation and DSE; a *deployed* model still
paid the full training-path forward on every request — per-call codesign
quantization (a 256-level argmin/softmax per layer for realistic nonlinear
devices), per-call ``exp(j theta)``, phase-stack construction, a fresh jit
dispatch per request, and no batching across requests.  All of that is
statically known at deploy time (the SLM is programmed / the mask is
printed once — cf. the hybrid reconfigurable DONNs of arXiv 2411.05748 and
the physics-aware discrete codesign of arXiv 2209.14252), so this module
folds it out of the hot path entirely:

1.  **Frozen artifact** — ``freeze(model, params)`` resolves the codesign
    device response once (``codesign.deployed_phase``) and precomputes the
    ``gamma * exp(j theta)`` modulation planes per layer
    (``PropagationPlan.frozen_modulation``), as cartesian split planes.
    Per-request work shrinks to the hops plus one complex multiply per
    layer, via the
    ``forward(frozen=...)`` fast path — bit-identical to the training-path
    forward at eval (tests/test_inference.py).
2.  **Bucketed AOT executables** — one compiled program per batch bucket,
    riding ``cached_executable`` with the request buffer donated.
    ``warmup(buckets=...)`` pays every compile at deploy time, so the
    first request is served from a warm executable.
3.  **Micro-batching** — ``MicroBatcher`` queues single requests and
    launches on batch-full-or-deadline, padding the queued set to the
    nearest bucket (``repro.data.pipeline.bucket_for`` / ``pad_batch``).
4.  **Multi-device dispatch** — buckets at least ``dp_min_bucket`` wide
    run data-parallel over the host mesh via ``shard_map`` on the batch
    axis (each device runs the whole optical forward on its batch shard;
    a DONN's phases are tiny, so pure DP is the right layout).
5.  **Row-sharded (model-parallel) serving** — ``model_devices=k`` puts
    the engine on the canonical 2-D ``(data, model)`` mesh
    (``sharding.make_mesh_2d`` + the ``donn_rules`` table): frozen
    modulation stacks, TF planes and detector masks shard their field
    rows over ``model`` and every hop runs the in-scan pencil FFT
    (``pencil_fft.local_spectral_pair``), so planes too large for one
    chip serve through the same bucketed executables; composes with the
    batch-axis DP above on one mesh.

Measured in ``benchmarks/bench_inference_throughput.py``; served by
``repro.launch.serve_donn``.
"""
from __future__ import annotations

import threading
import time
import warnings
from concurrent.futures import Future
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.core.laser import data_to_cplex, data_to_real
from repro.data.pipeline import bucket_for, pad_batch
from repro.runtime import sharding as shd
from repro.runtime.resilience import DeadlineExceededError, OverloadedError

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)


# --------------------------------------------------------------------------
# Frozen deployment artifact
# --------------------------------------------------------------------------
class DeployedDONN:
    """A trained DONN frozen for serving.

    Holds the propagation plan, the precomputed modulation planes and the
    (config-static) detector geometry — everything ``forward`` needs, and
    nothing of the training machinery (params pytree, codesign rng,
    quantizers).  Build with ``freeze(model, params)``.
    """

    def __init__(self, cfg, family: str, plan, frozen, source, in_n: int,
                 detector=None, skip_from=None, skip_hop=None,
                 out_grid=None, rfft_first: bool = False):
        self.cfg = cfg
        self.family = family  # "cls" | "multi" | "seg"
        self.plan = plan
        self.frozen = frozen
        self.source = jnp.asarray(source)
        self.in_n = in_n
        self.detector = detector
        self.skip_from = skip_from
        self.skip_hop = skip_hop
        self.out_grid = out_grid
        self.heterogeneous = cfg.is_heterogeneous()
        # storage precision of the modulation planes (derived, so restored
        # artifacts report it without trusting their metadata)
        self.plane_dtype = pp.frozen_plane_dtype(
            frozen[0] if self.heterogeneous else frozen
        )
        self.rfft_first = bool(rfft_first)
        if self.rfft_first:
            if self.heterogeneous:
                raise ValueError(
                    "rfft_first covers uniform plans (the segmented first "
                    "hop is a follow-on)"
                )
            if not plan.rfft_first_supported():
                raise ValueError(
                    "rfft_first needs an unpadded non-fraunhofer plan"
                )
            if plan.depth < 1:
                raise ValueError("rfft_first needs at least one layer")
            if not np.allclose(np.asarray(self.source).imag, 0.0):
                raise ValueError(
                    "rfft_first needs a real source field (amplitude-"
                    "encoded inputs keep the entry field real)"
                )
            # half-spectrum TF planes build (and evenness-check) eagerly
            plan._rfft_half()

    # --- the deployment forward (bit-identical to model.apply at eval) ---
    def forward(self, x: jax.Array, frozen=None) -> jax.Array:
        """Batched frozen forward: images -> logits / intensity maps.

        ``frozen`` optionally overrides the artifact's modulation planes —
        the ``InferenceEngine`` passes them as *traced inputs* so every
        deployment of one architecture shares a single compiled program
        (same statics, different trained params).
        """
        frozen = self.frozen if frozen is None else frozen
        if self.rfft_first:
            # real-to-complex entry: amplitude-encoded data through a real
            # source keeps the field real, so layer 0 runs as half-spectrum
            # rFFTs (plan.first_layer_real); the scan continues at layer 1
            with pp.stage("encode"):
                xr = data_to_real(x, self.in_n) * self.source.real
            u = self.plan.first_layer_real(xr, frozen)
            start = 1
        else:
            with pp.stage("encode"):
                u = data_to_cplex(x, self.in_n) * self.source
            start = 0
        if self.family == "seg":
            plan = self.plan
            if self.skip_from is None:
                u = plan.forward(None, u, start=start, frozen=frozen,
                                 final=True)
                skip_u = None
            else:
                u = plan.forward(None, u, start=start,
                                 stop=self.skip_from + 1, frozen=frozen)
                skip_u = u
                u = plan.forward(None, u, start=self.skip_from + 1,
                                 frozen=frozen, final=True)
            if skip_u is not None:
                sk = self.skip_hop.propagate(skip_u)
                sk = df.resample_field(sk, self.skip_hop.grid, self.out_grid)
                u = (u + sk) / jnp.sqrt(2.0).astype(jnp.complex64)
            return df.intensity(u)  # eval path: no train-time layer norm
        u = self.plan.forward(None, u, start=start, frozen=frozen,
                              final=True)
        if self.family == "multi":
            from repro.core.models import channel_readout

            return channel_readout(u, self.detector.masks)
        return self.detector(u)

    def static_key(self) -> tuple:
        """Executable-cache identity: config statics only.

        The trained modulation planes enter compiled programs as traced
        inputs, so deployments of the same architecture with different
        params share executables (and can never read each other's baked
        constants).  ``rfft_first`` changes the program *structure* (the
        entry hop), so it is part of the identity; plane storage dtypes
        already differ in the frozen-input avals.
        """
        from repro.core.models import config_static_key

        return ("deployed_donn", self.family, config_static_key(self.cfg),
                self.rfft_first)


def deployed_from_model(model, frozen, source=None,
                        rfft_first: bool = False) -> DeployedDONN:
    """Assemble a ``DeployedDONN`` around a built model + ready-made planes.

    The structural half of ``freeze``: plan, detector, grids and skip
    wiring come from the model; the modulation planes are supplied by the
    caller (``freeze`` computes them from trained params;
    ``runtime.resilience.load_deployed`` restores them from a serialized
    artifact without touching params or codesign at all).  ``source``
    optionally overrides the model's laser field (artifacts persist the
    resolved field so non-default lasers survive the round-trip).
    """
    from repro.core import models as md

    if isinstance(model, md.MultiChannelDONN):
        cm = model.channel_model
        return DeployedDONN(
            model.cfg, "multi", cm.plan, frozen,
            cm.source if source is None else source, cm.in_grid.n,
            detector=cm.detector, rfft_first=rfft_first,
        )
    if isinstance(model, md.SegmentationDONN):
        return DeployedDONN(
            model.cfg, "seg", model.plan, frozen,
            model.source if source is None else source, model.in_grid.n,
            skip_from=model.skip_from,
            skip_hop=getattr(model, "skip_hop", None), out_grid=model.grid,
            rfft_first=rfft_first,
        )
    if not isinstance(model, md.DONN):
        raise TypeError(f"cannot freeze {type(model).__name__}")
    return DeployedDONN(
        model.cfg, "cls", model.plan, frozen,
        model.source if source is None else source, model.in_grid.n,
        detector=model.detector, rfft_first=rfft_first,
    )


def freeze(model, params, plane_dtype: str = "float32",
           rfft_first: bool = False) -> DeployedDONN:
    """Fold a trained model + params into a serving artifact.

    Covers all three model families (classify / RGB multi-channel /
    segmentation incl. the optical skip), uniform and heterogeneous
    (segmented-plan) stacks, every codesign mode (stochastic modes resolve
    to their deterministic eval form, see ``codesign.deployed_phase``).

    ``plane_dtype`` selects the storage precision of the frozen modulation
    planes (``"float32"`` bit-identical | ``"bfloat16"`` | ``"int8"``,
    both with f32 accumulation — accuracy deltas measured per family in
    BENCH_inference_throughput).  ``rfft_first`` opts the serving forward
    into the half-spectrum real-to-complex first hop (uniform unpadded
    non-fraunhofer plans with a real source; raises otherwise).
    """
    from repro.core import models as md

    if isinstance(model, md.MultiChannelDONN):
        cm = model.channel_model
        phis = cm.plan.stack_phases(
            params["phase"][f"layer_{i}"] for i in range(len(cm.layers))
        )
        frozen = cm.plan.frozen_modulation(phis, plane_dtype)
    elif isinstance(model, md.SegmentationDONN) or isinstance(model, md.DONN):
        if isinstance(model, md.DONN):
            phis = model.stacked_phases(params)
        else:
            phis = model.plan.stack_phases(
                params["phase"][f"layer_{i}"]
                for i in range(len(model.layers))
            )
        frozen = model.plan.frozen_modulation(phis, plane_dtype)
    else:
        raise TypeError(f"cannot freeze {type(model).__name__}")
    return deployed_from_model(model, frozen, rfft_first=rfft_first)


# --------------------------------------------------------------------------
# Bucketed, donated, (optionally) data-parallel serving engine
# --------------------------------------------------------------------------
class InferenceEngine:
    """Shape-bucketed AOT serving around a ``DeployedDONN``.

    - one compiled executable per batch bucket (``cached_executable``:
      deployments sharing architecture statics + bucket share programs);
    - the padded request buffer is **donated** (requests are always padded
      into a fresh buffer first — ``pad_batch`` — so donation can never
      alias a live caller array);
    - ``warmup()`` pays every bucket's compile at deploy time;
    - buckets of at least ``dp_min_bucket`` rows dispatch data-parallel
      over ``mesh_devices`` devices via ``shard_map`` on the batch axis;
    - ``model_devices=k`` row-shards the frozen planes / TF stacks /
      detector masks over the ``model`` axis of the 2-D ``(data, model)``
      mesh and runs pencil-FFT hops — frozen stacks too large for one
      chip serve without replicating any plane (classify family, unpadded
      angular-spectrum plans).
    """

    def __init__(self, deployed: DeployedDONN,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 donate: bool = True, mesh_devices: Optional[int] = None,
                 dp_min_bucket: int = 8,
                 model_devices: Optional[int] = None):
        self.deployed = deployed
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError("buckets must be positive ints")
        self.donate = donate
        self.dp_min_bucket = int(dp_min_bucket)
        self.ndev = int(mesh_devices) if mesh_devices else 1
        self.mp = int(model_devices) if model_devices else 1
        if self.ndev < 1 or self.mp < 1:
            raise ValueError("mesh_devices/model_devices must be >= 1")
        if self.ndev * self.mp > jax.device_count():
            raise ValueError(
                f"mesh needs {self.ndev * self.mp} devices ({self.ndev} "
                f"data x {self.mp} model), have {jax.device_count()}"
            )
        if (self.ndev > 1 or self.mp > 1) and deployed.heterogeneous:
            raise NotImplementedError(
                "multi-device dispatch covers uniform plans (segmented "
                "frozen planes are a ragged pytree; flatten is a follow-on)"
            )
        if self.mp > 1:
            cfg = deployed.cfg
            if deployed.family != "cls":
                raise NotImplementedError(
                    "row-sharded serving covers the classify family; RGB "
                    "and segmentation row-shard on the training path only "
                    "for now (donn_steps.make_donn_sharded_loss)"
                )
            if deployed.rfft_first:
                raise NotImplementedError(
                    "rfft_first's half-spectrum entry hop is not row-"
                    "shardable; freeze with rfft_first=False to serve "
                    "model-parallel"
                )
            if cfg.pad or any(l.approximation == "fraunhofer"
                              for l in cfg.resolved_layers()):
                raise NotImplementedError(
                    "row-sharded serving needs unpadded angular-spectrum "
                    "hops (the spectral-override contract, plan._hop)"
                )
            n = deployed.plan.grid.n
            if n % self.mp:
                raise ValueError(
                    f"field rows n={n} not divisible by "
                    f"model_devices={self.mp}"
                )
        self._mesh = None
        self._rules = None
        self._x_sharding = None
        if self.ndev > 1 or self.mp > 1:
            from jax.sharding import NamedSharding

            self._mesh = shd.make_mesh_2d(data=self.ndev, model=self.mp)
            self._rules = shd.donn_rules()
            if self.ndev > 1:
                self._x_sharding = NamedSharding(
                    self._mesh, shd.dim0_pspec("data", self._x_ndim())
                )
        # hot-path pin: {(input shape, dtype): compiled} — infer() does a
        # plain dict lookup; cached_executable stays the cross-engine
        # sharing layer behind it (first build per shape goes through it)
        self._compiled: dict = {}
        self.stats = {"requests": 0, "batches": 0, "padded_rows": 0}

    # --- shapes ---
    def _x_ndim(self) -> int:
        return 4 if self.deployed.family == "multi" else 3

    def _example(self, bucket: int) -> np.ndarray:
        n = self.deployed.cfg.input_size
        shape = ((bucket, self.deployed.cfg.channels, n, n)
                 if self.deployed.family == "multi" else (bucket, n, n))
        return np.zeros(shape, np.float32)

    def _dp(self, bucket: int) -> bool:
        return (self.ndev > 1 and bucket >= self.dp_min_bucket
                and bucket % self.ndev == 0)

    # --- compiled program per bucket ---
    def _executable(self, xp: jax.Array):
        pin_key = (tuple(xp.shape), jnp.result_type(xp).name)
        pinned = self._compiled.get(pin_key)
        if pinned is not None:
            return pinned
        bucket = xp.shape[0]
        dp = self._dp(bucket)
        dep = self.deployed

        def fwd(x, frozen):
            return dep.forward(x, frozen=frozen)

        if self.mp > 1:
            from jax import shard_map
            from repro.runtime.donn_steps import _plan_tf_stacks
            from repro.runtime.pencil_fft import local_spectral_pair

            # Row-sharded serving: the frozen modulation stacks, the TF
            # planes and the detector masks all shard field rows over
            # "model"; every hop of the frozen scan runs the in-scan
            # pencil FFT and the per-class partial readout psums over
            # "model".  Composes with batch DP over "data" on the same
            # mesh (u0 is built in auto land so GSPMD places the entry
            # encode; tf/mask stacks are config statics, closed over like
            # the baked plan constants they replace).
            mesh, rules, mp = self._mesh, self._rules, self.mp
            plan = dep.plan
            spectral = local_spectral_pair("model", mp)
            tf_a, tf_b = _plan_tf_stacks(plan)
            masks = jnp.asarray(dep.detector.masks)
            bax = "batch" if dp else None
            u_spec = shd.rules_pspec((bax, "field_h", "field_w"),
                                     rules, mesh)
            tf_spec = shd.rules_pspec(("layers", "field_h", "field_w"),
                                      rules, mesh)
            m_spec = shd.rules_pspec(("classes", "field_h", "field_w"),
                                     rules, mesh)
            frozen_specs = jax.tree.map(
                lambda a: shd.operand_pspec(
                    jnp.shape(a), ("layers", "field_h", "field_w"),
                    mesh, rules,
                ),
                tuple(dep.frozen),
            )
            out_spec = shd.rules_pspec((bax, None), rules, mesh)

            def local_logits(u, a, b, m, fz):
                u = plan.forward(None, u, tfs=(a, b), spectral=spectral,
                                 frozen=fz, final=True)
                part = df.readout(u, m)
                return jax.lax.psum(part, "model")

            sharded = shard_map(
                local_logits, mesh=mesh,
                in_specs=(u_spec, tf_spec, tf_spec, m_spec, frozen_specs),
                out_specs=out_spec, check_vma=False,
            )

            def run(x, frozen):
                with pp.stage("encode"):
                    u = data_to_cplex(x, dep.in_n) * dep.source
                return sharded(u, tf_a, tf_b, masks, tuple(frozen))

            fn = run
        elif dp:
            from jax import shard_map

            mesh = self._mesh
            x_spec = shd.dim0_pspec("data", self._x_ndim())
            # frozen planes replicate; the batch axis shards.  Every device
            # runs the full optical forward on its local rows — pure DP,
            # zero cross-device collectives in the hot loop.  The spec tree
            # mirrors the frozen tuple (2 leaves f32/bf16 storage, 4 with
            # int8 quantized planes + their per-layer scales).
            frozen_specs = jax.tree.map(
                lambda a: shd.replicated_pspec(jnp.ndim(a)),
                tuple(dep.frozen),
            )
            out_spec = shd.dim0_pspec(
                "data", 3 if dep.family == "seg" else 2
            )

            def run(x, frozen):
                return shard_map(
                    fwd, mesh=mesh, in_specs=(x_spec, frozen_specs),
                    out_specs=out_spec, check_vma=False,
                )(x, frozen)

            fn = run
        else:
            fn = fwd
        key = dep.static_key() + (
            "dp", self.ndev if dp else 1, "mp", self.mp, self.donate
        )
        with warnings.catch_warnings():
            # donation only pays when an output aval matches the request
            # buffer (e.g. full-res segmentation maps); elsewhere it just
            # releases the buffer early — silence XLA's per-compile nag
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            ex = pp.cached_executable(
                key, fn, xp, dep.frozen,
                donate_argnums=(0,) if self.donate else (),
            )
        self._compiled[pin_key] = ex
        return ex

    def _place(self, xp: np.ndarray) -> jax.Array:
        if self._dp(xp.shape[0]):
            return jax.device_put(xp, self._x_sharding)
        return jnp.asarray(xp)

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """AOT-compile (and cache) every bucket's executable now.

        Deploy-time cost instead of first-request latency.  Returns
        {bucket: compile_seconds}.
        """
        out = {}
        for b in (self.buckets if buckets is None else buckets):
            xp = self._place(self._example(b))
            t0 = time.perf_counter()
            self._executable(xp)
            out[b] = time.perf_counter() - t0
        return out

    def infer(self, x) -> np.ndarray:
        """Serve one request batch: pad to bucket, run, slice.

        ``x``: (B, h, w) images ((B, C, h, w) for the RGB family), any B.
        Batches wider than the largest bucket chunk through it.  Returns
        the (B, ...) outputs as numpy (the host sync is the response).
        """
        x = np.asarray(x)
        if x.ndim == self._x_ndim() - 1:
            x = x[None]
        b_max = self.buckets[-1]
        outs = []
        for lo in range(0, x.shape[0], b_max):
            chunk = x[lo: lo + b_max]
            bucket = bucket_for(chunk.shape[0], self.buckets)
            xp = self._place(pad_batch(chunk, bucket))
            ex = self._executable(xp)
            out = ex(xp, self.deployed.frozen)
            outs.append(np.asarray(out)[: chunk.shape[0]])
            self.stats["batches"] += 1
            self.stats["requests"] += int(chunk.shape[0])
            self.stats["padded_rows"] += bucket - int(chunk.shape[0])
        return np.concatenate(outs, axis=0)


def expected_request_shape(deployed: DeployedDONN) -> tuple:
    """Per-request input shape a deployment serves ((C,n,n) for RGB)."""
    cfg = deployed.cfg
    n = cfg.input_size
    if deployed.family == "multi":
        return (cfg.channels, n, n)
    return (n, n)


def validate_request(deployed: DeployedDONN, x: np.ndarray) -> None:
    """Admission-time request validation shared by every dispatcher.

    Raises ``TypeError``/``ValueError`` on a request that could poison a
    batch (wrong dtype kind / per-request shape) — the door check both
    ``MicroBatcher.submit`` and ``runtime.fleet.FleetRouter.submit`` run.
    """
    if not (np.issubdtype(x.dtype, np.floating)
            or np.issubdtype(x.dtype, np.integer)
            or np.issubdtype(x.dtype, np.bool_)):
        raise TypeError(
            f"request dtype {x.dtype} is not castable to float32"
        )
    exp = expected_request_shape(deployed)
    if x.shape != exp:
        raise ValueError(
            f"request shape {x.shape} != expected per-request shape "
            f"{exp} for the {deployed.family!r} family"
        )


class _Request:
    """One queued inference request (slots: this sits on the hot path)."""

    __slots__ = ("x", "future", "t_arrival", "deadline")

    def __init__(self, x, future, t_arrival, deadline):
        self.x = x
        self.future = future
        self.t_arrival = t_arrival
        self.deadline = deadline  # absolute perf_counter time, or None


class MicroBatcher:
    """Batch-full-or-deadline request dispatcher over an ``InferenceEngine``.

    ``submit(x)`` enqueues one request (a single image / image stack) and
    returns a ``concurrent.futures.Future``; a background worker drains
    the queue whenever the largest bucket fills or the oldest queued
    request has waited ``max_wait_ms``, pads the group to the nearest
    bucket and serves it as one device call.

    Hardened for real traffic (``repro.runtime.resilience``):

    - **bounded admission** — at most ``max_queue`` requests wait; beyond
      that ``submit`` sheds with ``OverloadedError`` instead of growing
      the queue (and the tail latency) without bound;
    - **per-request deadlines** — ``submit(x, timeout_ms=...)`` fails the
      future with ``DeadlineExceededError`` once the deadline passes
      undispatched, instead of waiting forever behind a stall;
    - **submit-time validation** — shape/dtype mismatches are rejected at
      the door (``ValueError``/``TypeError``) before they can poison a
      batch (``validate=False`` restores trust-the-caller behavior);
    - **group bisection** — a group that fails to serve is split in half
      and retried, so one poison request fails only its own future while
      the rest of the group still gets results;
    - **accounted shutdown** — ``close()`` returns True for a clean drain;
      on an unclean join it fails every unresolved future and returns
      False instead of silently stranding callers.
    """

    def __init__(self, engine: InferenceEngine, max_wait_ms: float = 2.0,
                 max_queue: Optional[int] = 1024, validate: bool = True):
        self.engine = engine
        self.max_wait_s = max_wait_ms / 1e3
        self.max_queue = None if not max_queue else int(max_queue)
        self.validate = validate
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._pending: list = []  # [_Request]
        self._inflight: list = []  # group currently being served
        self._closed = False
        self.stats = {"submitted": 0, "served": 0, "shed": 0, "expired": 0,
                      "failed": 0}
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # --- admission ---
    def _expected_shape(self) -> tuple:
        return expected_request_shape(self.engine.deployed)

    def _validate(self, x: np.ndarray):
        validate_request(self.engine.deployed, x)

    def submit(self, x, timeout_ms: Optional[float] = None) -> Future:
        """Enqueue one request; returns a Future resolving to its output.

        Raises ``OverloadedError`` when the admission queue is full (load
        shedding — the caller should back off / retry elsewhere) and
        ``ValueError``/``TypeError`` on malformed requests when
        ``validate`` is on.  With ``timeout_ms`` set, the future fails
        with ``DeadlineExceededError`` if still undispatched then.
        """
        x = np.asarray(x)
        if self.validate:
            self._validate(x)
        now = time.perf_counter()
        deadline = None if timeout_ms is None else now + timeout_ms / 1e3
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise RuntimeError("MicroBatcher is closed")
            if (self.max_queue is not None
                    and len(self._pending) >= self.max_queue):
                self.stats["shed"] += 1
                raise OverloadedError(
                    f"admission queue full ({self.max_queue} pending)"
                )
            self._pending.append(_Request(x, fut, now, deadline))
            self.stats["submitted"] += 1
            self._cv.notify()
        return fut

    # --- dispatch ---
    def _split_expired(self, now: float) -> list:
        """Pop expired requests off the queue (caller holds the lock)."""
        expired = [r for r in self._pending
                   if r.deadline is not None and now >= r.deadline]
        if expired:
            self._pending = [r for r in self._pending if r not in expired]
        return expired

    def _take(self) -> tuple:
        """Block until work is ready: (group_to_serve, expired_requests).

        Both empty means the batcher is closed and drained.
        """
        b_max = self.engine.buckets[-1]
        with self._cv:
            while True:
                now = time.perf_counter()
                expired = self._split_expired(now)
                if expired:
                    return [], expired
                if self._closed and not self._pending:
                    return [], []
                if self._pending:
                    if len(self._pending) >= b_max or self._closed:
                        break
                    timeout = self.max_wait_s - (now - self._pending[0].t_arrival)
                    dls = [r.deadline for r in self._pending
                           if r.deadline is not None]
                    if dls:
                        timeout = min(timeout, min(dls) - now)
                    if timeout <= 0:
                        break
                    self._cv.wait(timeout=timeout)
                else:
                    self._cv.wait(timeout=0.1)
            group = self._pending[:b_max]
            del self._pending[:len(group)]
            self._inflight = group
            return group, []

    def _serve(self, group: list):
        """Serve a group; on failure bisect so only poison requests fail."""
        try:
            # the stack is inside the try: a malformed request (e.g. a
            # mismatched image shape with validate off) must fail, not
            # kill the worker and hang every later submit
            xs = np.stack([r.x for r in group])
            outs = self.engine.infer(xs)
        except Exception as e:  # noqa: BLE001 - propagate to callers
            if len(group) == 1:
                if not group[0].future.done():
                    group[0].future.set_exception(e)
                self.stats["failed"] += 1
                return
            mid = len(group) // 2
            self._serve(group[:mid])
            self._serve(group[mid:])
            return
        for r, out in zip(group, outs):
            if not r.future.done():
                r.future.set_result(out)
            self.stats["served"] += 1

    def _run(self):
        while True:
            group, expired = self._take()
            for r in expired:
                if not r.future.done():
                    r.future.set_exception(DeadlineExceededError(
                        "request deadline expired before dispatch"
                    ))
                self.stats["expired"] += 1
            if not group and not expired:
                return
            if group:
                self._serve(group)
                with self._cv:
                    self._inflight = []

    def close(self, timeout: float = 30.0) -> bool:
        """Drain the queue and stop the worker.

        Returns True on a clean drain.  If the worker fails to join
        within ``timeout`` seconds (e.g. wedged inside a device call),
        every unresolved pending/in-flight future is failed with a
        ``RuntimeError`` so no caller blocks forever, and False is
        returned — callers that care must check it.
        """
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._worker.join(timeout=timeout)
        if not self._worker.is_alive():
            return True
        with self._cv:
            stranded = self._pending + self._inflight
            self._pending = []
        err = RuntimeError(
            f"MicroBatcher shutdown unclean: worker did not join within "
            f"{timeout}s; {len(stranded)} request(s) abandoned"
        )
        for r in stranded:
            if not r.future.done():
                r.future.set_exception(err)
        return False
