"""Fused scan-based propagation engine (the LightRidge hot path, Fig. 9).

The eager model forward is a per-layer Python loop: every layer re-uploads
its transfer function, traces its own FFT2 / complex-multiply / iFFT2 /
phase-modulation chain, and ``MultiChannelDONN`` runs its channels as
separate unbatched stacks.  This module replaces that loop with a
*propagation plan* and a compile-once emulation runtime on top of it:

1.  **TF cache** — transfer functions are precomputed once per geometry and
    cached process-wide (LRU), keyed by ``(grid, z, wavelength, method,
    band_limit, pad)``.  They are stored as split real/imag float32 planes
    ``(hr, hi)``; band-limit masks and evanescent decay fold into them.
2.  **Stacked scan** — all layer TFs and phase maps stack into ``(L, N,
    N)`` tensors and the forward becomes a single ``jax.lax.scan`` whose
    body is traced once: FFT2 -> spectral multiply -> iFFT2 -> phase
    modulation.  The scan carries an ``unroll`` knob
    (``DONNConfig.scan_unroll``; default from ``default_scan_unroll``) that
    claws back XLA:CPU's while-loop overhead in steady state, and TF planes
    may be stored bf16 with f32 accumulation (``DONNConfig.tf_dtype``).
3.  **Batched channels and candidates** — multi-channel inputs keep their
    channel axis and propagate as one ``(..., C, N, N)`` tensor through
    shared kernels with ``(L, C, N, N)`` phase stacks
    (``repro.core.models.MultiChannelDONN``).  The same machinery batches
    *candidates*: ``PropagationPlan.apply_batch`` vmaps a ``(K, L, N, N)``
    (or ``(K, L, C, N, N)``) stack of K phase configurations through one
    shared compiled forward, and ``forward``/``apply`` accept externally
    supplied transfer planes (``tfs=...``) so per-candidate *geometries*
    ride the same executable as traced inputs instead of baked constants
    (``repro.core.models.emulate_batch``, the DSE verification path).
4.  **Plan and executable caches** — ``plan_from_config`` memoizes
    ``PropagationPlan`` instances per geometry tuple and
    ``cached_executable`` memoizes AOT-compiled programs keyed by
    ``(statics, input shapes/dtypes)``; ``plan_cache_stats()`` /
    ``clear_plan_cache()`` mirror the TF-cache API.  Repeated emulation
    (DSE verification sweeps, sensitivity analysis, codesign loops) stops
    paying trace+compile per candidate.

5.  **Segmented plans for heterogeneous stacks** — configs with per-layer
    ``LayerSpec`` overrides (mixed plane sizes, pixel sizes, approximation
    methods, codesign devices) compile to a ``SegmentedPlan``: maximal
    runs of fusable layers each become one scan segment, stitched by
    eager hops with field resampling at grid boundaries.  Uniform configs
    keep the single-segment ``PropagationPlan`` (identical HLO and cache
    keys as before).

6.  **Stage scopes** — every stage of the forward (``STAGES``: encode,
    masks, fft, tf_mul, ifft, modulate, readout, stitch) runs under one
    ``jax.named_scope`` named ``donn.<stage>``, written where the stage's
    work is, so every path (train, emulate, DSE, serve) carries it; the
    training steps add ``loss`` (softmax and MSE) and ``optimizer`` (the
    parameter update).  Scopes are metadata only:
    ``stage_map()`` reads them back from each cached executable's HLO,
    which is how a profiler trace's device ops are joined to stages.
    ``compile_stats()`` counts the process's backend compiles and
    persistent-cache loads; the host spans ``donn.compile``,
    ``donn.dispatch`` and ``donn.train_dispatch`` (``TraceAnnotation``s,
    which do nothing while no profiler runs) mark compiles, forward
    launches and training-chunk launches.

7.  **Packed real-DFT hop** — on a TPU, at the plane sizes where it
    measured faster, the plain hop runs as four real matmul stages on the
    MXU instead of XLA's FFTs (``_packed_hop``); it needs every transfer
    plane even in each frequency axis, checked when the plan is built.
    From N = 385 (``_folded_hop_applies``) it runs folded into even and
    odd halves, each stage contracting half the plane (``_folded_hop``,
    built on ``diffraction.folded_dft_matrices``):
    ``forward`` folds a field once and scans the folded blocks, each
    layer's modulation mixing them (``diffraction.modulate_folded``; on a
    TPU one Pallas pass, ``repro.kernels.fold_mix``).
    ``hop_path_stats()`` counts the hops traced on each path.

The eager path remains available via ``DONNConfig(engine="eager")`` and
must agree with the plan path to rtol <= 1e-5
(tests/test_propagation_plan.py, tests/test_hetero.py).
"""
from __future__ import annotations

import re
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codesign as cd
from repro.core import diffraction as df
from repro.core import physics
from repro.core.cache import lru_get, lru_put
from repro.runtime import sharding as shd

# --------------------------------------------------------------------------
# Process-wide caches (TF planes, plans, executables)
# --------------------------------------------------------------------------
# All three are bounded LRU maps (repro.core.cache): lookups reinsert the
# hit entry at the back, eviction pops the front — a DSE sweep alternating
# more geometries than the bound can hold no longer evicts its own hot
# entries (the old FIFO did).
_TF_CACHE: dict = {}
_TF_CACHE_MAX = 512
_TF_STATS = {"hits": 0, "misses": 0}

_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 64
_PLAN_STATS = {"hits": 0, "misses": 0}

_EXEC_CACHE: dict = {}
_EXEC_CACHE_MAX = 64
_EXEC_STATS = {"hits": 0, "misses": 0}


# shared bounded-LRU implementation (repro.core.cache)
_cache_get = lru_get
_cache_put = lru_put


# --------------------------------------------------------------------------
# Stages of the forward (named scopes) and host spans
# --------------------------------------------------------------------------
# No JAX primitive names a scope "donn.*" (JAX's own FFT adds
# "jit(fft)/fft" to the path), so the innermost "donn.<stage>" component of
# a compiled op's op_name is the stage that op belongs to.
STAGE_PREFIX = "donn."
STAGES = ("encode", "masks", "fft", "tf_mul", "ifft", "modulate",
          "readout", "stitch", "loss", "optimizer")
COMPILE_SPAN = STAGE_PREFIX + "compile"
DISPATCH_SPAN = STAGE_PREFIX + "dispatch"
TRAIN_DISPATCH_SPAN = STAGE_PREFIX + "train_dispatch"


def stage(name: str):
    """The named scope of one stage of the forward (metadata only)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r} (expected one of {STAGES})")
    return jax.named_scope(STAGE_PREFIX + name)


# Process-wide compile counter, fed by JAX's monitoring events.  JAX records
# the backend-compile duration around every program build, a persistent-
# cache load included, and a cache-hit event for each load.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_STATS = {"builds": 0, "cache_loads": 0}


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _COMPILE_STATS["cache_loads"] += 1


def _on_duration(event: str, _duration: float, **_) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _COMPILE_STATS["builds"] += 1


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_stats() -> dict:
    """Programs this process compiled with XLA (``compiles``) and loaded
    from the persistent compilation cache (``cache_loads``), every jit in
    the process counted, not only the plan's."""
    loads = _COMPILE_STATS["cache_loads"]
    return {"compiles": _COMPILE_STATS["builds"] - loads,
            "cache_loads": loads}


# Hops traced into programs, by path: "packed" (the real-DFT matmul hop)
# or "fft" (jnp.fft); "folded" counts the packed hops that run folded
# (``diffraction.folded_dft_matrices``), "mix_kernel" the modulated folded
# layers whose mix runs as the Pallas kernel (``repro.kernels.fold_mix``).
# A scan adds its length once per trace.
_HOP_STATS = {"packed": 0, "fft": 0, "folded": 0, "mix_kernel": 0}


def hop_path_stats() -> dict:
    """Hops traced so far, by path (``packed``, ``fft``): how often the
    packed real-DFT hop engages; ``folded`` counts the packed hops that
    run folded (``_folded_hop_applies``), ``mix_kernel`` the modulated
    folded layers whose mix traced the one-pass kernel
    (``_mix_kernel_applies``).  Each trace of a program counts its hops
    once, every layer of a scan included; running a compiled program
    counts nothing."""
    return dict(_HOP_STATS)


# Plane sizes over which the packed hop was measured against XLA's FFT hop
# on a TPU v5e, at batch 64: 1.77x faster at 200, 2.04x at 350, 2.07x at 500
# and 2.31x at 512 (PERF.md §6, PR 14).  Outside them it is unmeasured.
_PACKED_HOP_N = (200, 512)


def _packed_hop_applies(n: int) -> bool:
    """Whether an n x n hop that may take the packed path takes it: on a
    TPU, at the plane sizes where it measured faster than the FFT."""
    lo, hi = _PACKED_HOP_N
    return jax.default_backend() == "tpu" and lo <= n <= hi


# Smallest plane the packed hop runs folded: from here (N > 3 * 128) the
# folded stages pad to half the unfolded stages' lane tiles.  Per image-hop
# of the plan's forward at batch 64 on a TPU v5e, unfolded -> folded:
# 27.26 -> 30.14 us at N = 300, 31.50 -> 34.67 at 350, 31.35 -> 39.70 at
# 384, 57.39 -> 41.46 at 392, 58.64 -> 41.54 at 400, 76.93 -> 60.15 at
# 500, 79.25 -> 60.50 at 512.  At 200 and 256 the forward gains too (6.94
# -> 4.31, 8.89 -> 5.33), but the N = 200 training cell ran 23% slower
# folded (PERF.md §6).
_FOLDED_HOP_MIN_N = 385


def _folded_hop_applies(n: int) -> bool:
    """Whether a packed n x n hop runs folded."""
    return n >= _FOLDED_HOP_MIN_N


def _mix_kernel_takes(x: jax.Array, mod: jax.Array) -> bool:
    """Whether the one-pass mix kernel (``repro.kernels.fold_mix``) takes
    these operands: float32 blocks of the plain (4, 2, B, m, m) field and
    one (4, 2, m, m) modulation, in a program the compiler does not
    partition over several devices (``sharding.partitioned_by_compiler``;
    inside a ``shard_map`` each device runs the kernel on its shard).  Any
    other rank, such as a multi-channel (..., C, m, m) field, keeps the
    jnp mix."""
    return (x.ndim == 5 and x.shape[:2] == (4, 2)
            and mod.shape == x.shape[:2] + x.shape[-2:]
            and x.dtype == mod.dtype == jnp.float32
            and not shd.partitioned_by_compiler())


def _mix_kernel_applies(x: jax.Array, mod: jax.Array) -> bool:
    """Whether a folded layer's mix (``diffraction.modulate_folded``) runs
    as the one-pass kernel: on a TPU, where the kernel takes the
    operands."""
    return jax.default_backend() == "tpu" and _mix_kernel_takes(x, mod)


def _modulate_folded(x: jax.Array, mod: jax.Array, layers: int) -> jax.Array:
    """One folded layer's mix, as the kernel where ``_mix_kernel_applies``
    (Pallas is imported here, on first use), else as jnp.  ``layers`` is
    how many layers this trace stands for in ``hop_path_stats``."""
    if not _mix_kernel_applies(x, mod):
        return df.modulate_folded(x, mod)
    from repro.kernels import fold_mix

    _HOP_STATS["mix_kernel"] += layers
    return fold_mix.modulate_folded(x, mod)


def _quadrants(h: jax.Array, m: int, ndim: int) -> jax.Array:
    """The quadrants of a natural-order plane (..., n, n) that meet the
    folded hop's blocks, stacked in ``diffraction.BLOCKS`` order and
    broadcast to ``ndim`` axes (4, ..., m, m): a cosine block at
    frequencies 0 .. m-1, a sine block at 1 .. m, per axis."""
    q = jnp.stack([h[..., r:r + m, t:t + m] for r, t in df.BLOCKS])
    return q.reshape((4,) + (1,) * (ndim - q.ndim) + q.shape[1:])


def tf_cache_key(grid: df.Grid, z: float, wavelength: float, method: str,
                 band_limit: bool, pad: bool) -> tuple:
    return (grid.n, float(grid.pixel_size), float(z), float(wavelength),
            method, bool(band_limit), bool(pad))


def tf_cache_stats() -> dict:
    return dict(_TF_STATS)


def clear_tf_cache() -> None:
    _TF_CACHE.clear()
    _TF_STATS["hits"] = 0
    _TF_STATS["misses"] = 0


def plan_cache_stats() -> dict:
    """Plan + executable cache counters (mirrors ``tf_cache_stats``)."""
    return {
        "hits": _PLAN_STATS["hits"],
        "misses": _PLAN_STATS["misses"],
        "size": len(_PLAN_CACHE),
        "exec_hits": _EXEC_STATS["hits"],
        "exec_misses": _EXEC_STATS["misses"],
        "exec_size": len(_EXEC_CACHE),
    }


def clear_plan_cache() -> None:
    """Drop all cached plans and compiled executables, reset counters."""
    _PLAN_CACHE.clear()
    _EXEC_CACHE.clear()
    for s in (_PLAN_STATS, _EXEC_STATS):
        s["hits"] = 0
        s["misses"] = 0


def transfer_planes(grid: df.Grid, z: float, wavelength: float,
                    method: str = df.RS, band_limit: bool = True,
                    pad: bool = False) -> dict:
    """Cached split-plane transfer function for one propagation gap.

    Returns {"hr", "hi"} float32 numpy arrays on the (possibly padded)
    grid; for ``method="fraunhofer"`` the planes describe the far-field
    quadratic output factor instead (it carries the 1/(lambda z) scaling).
    """
    key = tf_cache_key(grid, z, wavelength, method, band_limit, pad)
    hit = _cache_get(_TF_CACHE, key, _TF_STATS)
    if hit is not None:
        return hit
    if method == df.FRAUNHOFER:
        h = df.fraunhofer_quad(grid, z, wavelength)
    else:
        h = df.transfer_function(grid, z, wavelength, method, band_limit,
                                 pad=pad)
    entry = {
        "hr": np.ascontiguousarray(h.real.astype(np.float32)),
        "hi": np.ascontiguousarray(h.imag.astype(np.float32)),
    }
    _cache_put(_TF_CACHE, key, entry, _TF_CACHE_MAX)
    return entry


def cached_transfer_function(grid: df.Grid, z: float, wavelength: float,
                             method: str = df.RS, band_limit: bool = True,
                             pad: bool = False) -> np.ndarray:
    """Complex64 view of the cached transfer function (eager-path layers)."""
    p = transfer_planes(grid, z, wavelength, method, band_limit, pad)
    return p["hr"] + 1j * p["hi"]


# --------------------------------------------------------------------------
# Executable cache (AOT compile-once layer)
# --------------------------------------------------------------------------
def _aval_key(args) -> tuple:
    leaves, treedef = jax.tree.flatten(args)
    return (treedef,) + tuple(
        (np.shape(leaf), jnp.result_type(leaf).name,
         bool(getattr(leaf, "weak_type", False)))
        for leaf in leaves
    )


def cached_executable(static_key: tuple, fn: Callable, *args,
                      donate_argnums: tuple = ()):
    """AOT-compiled ``fn`` for the shapes/dtypes of ``args``.

    Keyed by ``(static_key, donation, input avals)`` — the compile-once
    layer above the TF/plan caches.  Repeated emulations with identical
    statics and input shapes reuse one XLA executable instead of re-tracing
    a fresh closure (what every ``build_model``+``jit(apply)`` cycle used
    to pay).  ``donate_argnums`` compiles the executable with those
    positional inputs donated (the chunked training drivers donate params
    and optimizer state so step k+1 reuses step k's buffers in place).
    """
    donate_argnums = tuple(donate_argnums)
    key = (static_key, donate_argnums, _aval_key(args))
    compiled = _cache_get(_EXEC_CACHE, key, _EXEC_STATS)
    if compiled is None:
        with jax.profiler.TraceAnnotation(COMPILE_SPAN):
            compiled = jax.jit(
                fn, donate_argnums=donate_argnums
            ).lower(*args).compile()
        _cache_put(_EXEC_CACHE, key, compiled, _EXEC_CACHE_MAX)
    return compiled


_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
# "%fusion.12 = f32[8,8]{1,0} fusion(%a), kind=kLoop, ..., metadata={...}"
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?(?:^|\s)"
                        r"([a-z][\w\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE_IN_OP_NAME = re.compile(re.escape(STAGE_PREFIX) + r"(\w+)")
# ops that only hold other ops: their own time is loop control, not a stage
_CONTAINER_OPS = frozenset({"while", "conditional", "call"})


def hlo_stage_map(hlo_text: str) -> dict:
    """{(module name, instruction name): stage} of one compiled module's
    text, from the innermost ``donn.<stage>`` scope of each instruction's
    op_name.  Containers (while, conditional, call) and instructions
    without a stage are left out."""
    lines = hlo_text.splitlines()
    head = _HLO_MODULE.match(lines[0]) if lines else None
    if head is None:
        raise ValueError("not the text of an HLO module")
    module, out = head.group(1), {}
    for line in lines:
        instr = _HLO_INSTR.match(line)
        op_name = _HLO_OP_NAME.search(line)
        if instr is None or op_name is None:
            continue
        if instr.group(2) in _CONTAINER_OPS:
            continue
        stages = [s for s in _STAGE_IN_OP_NAME.findall(op_name.group(1))
                  if s in STAGES]
        if stages:
            out[(module, instr.group(1))] = stages[-1]
    return out


def stage_map() -> dict:
    """{(module name, instruction name): stage} over every executable in
    the executable cache, built from their HLO text when called."""
    out = {}
    for compiled in list(_EXEC_CACHE.values()):
        out.update(hlo_stage_map(compiled.as_text()))
    return out


# --------------------------------------------------------------------------
# Frozen-plane storage dtypes (deployment serving path)
# --------------------------------------------------------------------------
PLANE_DTYPES = ("float32", "bfloat16", "int8")


def quantize_frozen_planes(pair, plane_dtype: str = "float32") -> tuple:
    """Reduce a frozen modulation plane pair to its storage dtype.

    The ``tf_dtype`` idea generalized to the serving path: planes are
    *stored* small and every consumer accumulates in f32
    (``dequant_frozen_layer`` inside the scan body).

    - ``"float32"``  -> the pair unchanged (bit-identical fast path);
    - ``"bfloat16"`` -> the same 2-tuple cast to bf16 storage;
    - ``"int8"``     -> a 4-tuple ``(qa, qb, sa, sb)``: symmetric per-layer
      linear quantization ``q = round(x / s)`` with f32 scales
      ``s = max|x| / 127`` kept per layer (shape ``(L, 1, 1[, 1])``), so
      each modulation plane dequantizes independently.
    """
    if plane_dtype not in PLANE_DTYPES:
        raise ValueError(
            f"unknown plane_dtype {plane_dtype!r} (expected one of "
            f"{PLANE_DTYPES})"
        )
    if plane_dtype == "float32":
        return tuple(pair)
    if plane_dtype == "bfloat16":
        return tuple(jnp.asarray(p).astype(jnp.bfloat16) for p in pair)
    qs, ss = [], []
    for p in pair:
        p = jnp.asarray(p, jnp.float32)
        red = tuple(range(1, p.ndim))
        s = jnp.max(jnp.abs(p), axis=red, keepdims=True) / 127.0
        s = jnp.maximum(s, jnp.float32(1e-12))
        qs.append(jnp.round(p / s).astype(jnp.int8))
        ss.append(s)
    return (qs[0], qs[1], ss[0], ss[1])


@stage("masks")
def dequant_frozen_layer(leaves) -> tuple:
    """One layer's frozen-plane leaves -> f32 ``(a, b)`` (f32 accumulation).

    ``leaves`` is one scan step's slice of the frozen tuple: ``(a, b)``
    for float32/bfloat16 storage, ``(qa, qb, sa, sb)`` for int8.
    """
    if len(leaves) == 2:
        a, b = leaves
        return a.astype(jnp.float32), b.astype(jnp.float32)
    qa, qb, sa, sb = leaves
    return qa.astype(jnp.float32) * sa, qb.astype(jnp.float32) * sb


def frozen_plane_dtype(frozen) -> str:
    """Storage dtype of a frozen pair/4-tuple (inverse of quantization)."""
    frozen = tuple(frozen)
    if len(frozen) == 4:
        return "int8"
    return "bfloat16" if frozen[0].dtype == jnp.bfloat16 else "float32"


# --------------------------------------------------------------------------
# Scan tuning
# --------------------------------------------------------------------------
def default_scan_unroll(depth: int) -> int:
    """Scan unroll heuristic (measured on XLA:CPU, BENCH_propagation_plan).

    The rolled while-loop form costs ~4-15% steady-state vs the eager
    unrolled HLO; unrolling by 8 recovers it (best of the depth-16 sweep,
    ~1.06x vs eager, ahead of both the rolled loop and full unroll) while
    the body is still traced once, so first-call stays ahead of eager too.
    Shallower stacks unroll fully; deeper stacks keep the cap so compile
    time stays bounded — the plan/executable caches make that first
    compile a one-time cost per (statics, shapes) anyway.
    """
    return min(depth, 8)


# --------------------------------------------------------------------------
# Propagation plan
# --------------------------------------------------------------------------
class PropagationPlan:
    """Stacked, scan-based forward pipeline for a diffractive stack.

    Covers ``depth`` modulated layers (gap i then phase plane i) plus the
    final free-space hop to the detector plane.  ``forward`` runs a slice
    of the modulated layers as one ``jax.lax.scan``; ``propagate_final``
    runs the last hop.  Phase stacks may be ``(L, N, N)`` (single channel)
    or ``(L, C, N, N)`` (multi-channel; fields keep their channel axis).

    Transfer planes default to the plan's baked constants, but ``forward``
    / ``propagate_final`` / ``apply`` also accept an external plane pair
    (``tfs``) with the same ``(depth+1, ...)`` layout, possibly traced —
    that is how ``apply_batch`` and the DSE ``emulate_batch`` path push
    per-candidate geometries through one shared executable.
    """

    def __init__(
        self,
        grid: df.Grid,
        gaps,  # depth+1 propagation distances (last = hop to detector)
        wavelength: float,
        method: str = df.RS,
        band_limit: bool = True,
        pad: bool = False,
        gamma: float = 1.0,
        device: Optional[cd.DeviceSpec] = None,
        codesign_mode: str = "none",
        unroll: Optional[int] = None,
        tf_dtype: str = "float32",
        final_hop: bool = True,
        remat: str = "none",
    ):
        """``final_hop=False`` builds an *inner segment* of a heterogeneous
        stack: every gap is a modulated layer's gap and ``propagate_final``
        is unavailable (the next segment owns the following hop).

        ``remat`` threads a ``jax.checkpoint`` policy into the scan:
        ``"layer"`` checkpoints the scan body (the backward pass recomputes
        each layer's FFT chain from its carry instead of storing it),
        ``"segment"`` checkpoints the whole scan region.  Both trade
        recompute for activation memory — the knob that keeps deep or
        large-plane *training* from OOMing."""
        if method not in df.METHODS:
            raise ValueError(f"unknown method {method!r}")
        if tf_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown tf_dtype {tf_dtype!r}")
        if remat not in ("none", "layer", "segment"):
            raise ValueError(f"unknown remat {remat!r}")
        self.grid = grid
        self.gaps = tuple(float(g) for g in gaps)
        self.final_hop = final_hop
        self.depth = len(self.gaps) - 1 if final_hop else len(self.gaps)
        self.wavelength = wavelength
        self.method = method
        self.band_limit = band_limit
        self.pad = pad and method != df.FRAUNHOFER
        self.gamma = float(gamma)
        self.device = device
        self.codesign_mode = codesign_mode
        self.unroll = unroll
        self.tf_dtype = tf_dtype
        self.remat = remat
        planes = [
            transfer_planes(grid, z, wavelength, method, band_limit, self.pad)
            for z in self.gaps
        ]
        # the plain hop may run as the packed real-DFT hop (``_packed_hop``),
        # which needs every plane even in each axis
        self._packable = (method != df.FRAUNHOFER and not self.pad
                          and all(df.is_even_plane(p[k]) for p in planes
                                  for k in ("hr", "hi")))
        # stacked numpy constants; uploaded lazily (imports stay device-free)
        self._np = {k: np.stack([p[k] for p in planes]) for k in ("hr", "hi")}
        self._jax: dict = {}

    # --- constants ---
    def _const(self, name: str) -> jax.Array:
        arr = self._jax.get(name)
        if arr is None:
            arr = jnp.asarray(self._np[name])
            if self.tf_dtype != "float32":
                # storage dtype only: every consumer upcasts to f32 before
                # the complex multiply (f32 accumulation)
                arr = arr.astype(self.tf_dtype)
            # under a jit trace jnp.asarray yields a Tracer — caching it
            # across traces would leak; cache only concrete device arrays
            if not isinstance(arr, jax.core.Tracer):
                self._jax[name] = arr
        return arr

    def _tf_pair(self) -> tuple:
        """Full (depth+1, N, N) split-plane stacks (baked constants)."""
        return self._const("hr"), self._const("hi")

    # --- elementwise sites ---
    @stage("tf_mul")
    def _spectral_mul(self, s: jax.Array, pair) -> jax.Array:
        """Multiply a spectrum (or far-field plane) by one layer's TF pair."""
        a, b = pair
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        return s * jax.lax.complex(a, b)  # (hr, hi)

    @stage("modulate")
    def _modulate(self, u: jax.Array, phi: jax.Array) -> jax.Array:
        """gamma * u * exp(j phi); phi (N, N) or per-channel (C, N, N)."""
        return u * (self.gamma * jnp.exp(1j * phi.astype(jnp.complex64)))

    @stage("modulate")
    def _modulate_frozen(self, u: jax.Array, pair) -> jax.Array:
        """Modulate by one layer's *precomputed* modulation plane pair.

        The deployment fast path: codesign response and ``gamma * exp(j
        theta)`` were folded once at freeze time (``frozen_modulation``),
        so per-request work is a single complex multiply.  Numerics are
        bit-identical to ``_modulate`` on the codesign-resolved phase (same
        operand values).
        """
        a, b = pair
        return u * jax.lax.complex(a, b)  # (mr, mi) = gamma * exp(j phi)

    def frozen_modulation(self, phis: jax.Array,
                          plane_dtype: str = "float32") -> tuple:
        """Deploy-time fold: device response + ``gamma*exp(j phi)`` once.

        ``phis`` is the trained (L, ...) phase stack.  The codesign device
        response is resolved rng-free (``codesign.deployed_phase`` — the
        statically-known state the fabricated hardware holds) and the
        modulation ``gamma * exp(j phi_eff)`` is precomputed into a split
        plane pair ``(mr, mi)``.  Feed the result to
        ``forward``/``apply`` via ``frozen=`` — the per-request hot path
        then skips phase-stack construction, quantization and codesign rng
        entirely (bit-identical to the training-path forward at eval,
        tests/test_inference.py).

        ``plane_dtype`` selects the *storage* precision of the folded
        planes (``quantize_frozen_planes``): ``"float32"`` is bit-identical
        to the historical pair, ``"bfloat16"``/``"int8"`` shrink the
        serving artifact 2x/4x with f32 accumulation in the scan body
        (accuracy deltas measured in BENCH_inference_throughput).
        """

        @stage("masks")
        def fold(p):
            eff = self._codesign_stack(p, None)
            m = self.gamma * jnp.exp(1j * eff.astype(jnp.complex64))
            return m.real, m.imag

        a, b = jax.jit(fold)(jnp.asarray(phis))
        return quantize_frozen_planes((a, b), plane_dtype)

    def _hop(self, u: jax.Array, pair, spectral=None,
             hops: int = 1, folded: bool = False) -> jax.Array:
        """One free-space gap with a prepared TF plane pair.

        ``spectral`` optionally overrides the (fft2, ifft2) pair — the hook
        distributed spectral hops use: ``repro.runtime.pencil_fft.
        local_spectral_pair`` runs the pencil-decomposed local FFT *inside*
        the scan body when fields (and TF planes) are row-sharded under an
        enclosing ``shard_map``.  Without it, a plain hop of a plan whose
        planes are even runs as ``_packed_hop`` where
        ``_packed_hop_applies``.  ``hops`` is how many hops this trace
        stands for in ``hop_path_stats`` (a scan body's: the scan length).
        ``folded``: ``u`` is the packed hop's folded blocks
        (``diffraction.fold_field``), and the hop returns them
        (``_folded_hop``).
        """
        packed = self._packs(spectral)
        _HOP_STATS["packed" if packed else "fft"] += hops
        if packed and _folded_hop_applies(self.grid.n):
            _HOP_STATS["folded"] += hops
        if folded:
            return self._folded_hop(u, pair)
        if packed:
            return self._packed_hop(u, pair)
        if spectral is not None:
            if self.method == df.FRAUNHOFER or self.pad:
                raise NotImplementedError(
                    "spectral-hop overrides support unpadded angular-"
                    "spectrum methods only (no fraunhofer, no pad)"
                )
            fft2, ifft2 = spectral
        else:
            fft2, ifft2 = jnp.fft.fft2, jnp.fft.ifft2
        if self.method == df.FRAUNHOFER:
            with stage("fft"):
                spec = jnp.fft.fftshift(fft2(u), axes=(-2, -1))
            return self._spectral_mul(spec, pair)
        n = self.grid.n
        with stage("fft"):
            spec = fft2(df.pad_field(u, n) if self.pad else u)
        spec = self._spectral_mul(spec, pair)
        with stage("ifft"):
            out = ifft2(spec)
            return df.crop_field(out, n) if self.pad else out

    def _packs(self, spectral=None) -> bool:
        """Whether this plan's plain hop runs as ``_packed_hop``."""
        return (spectral is None and self._packable
                and _packed_hop_applies(self.grid.n))

    def _folds(self, spectral=None) -> bool:
        """Whether this plan's plain hop runs as ``_folded_hop``."""
        return self._packs(spectral) and _folded_hop_applies(self.grid.n)

    def _packed_hop(self, u: jax.Array, pair) -> jax.Array:
        """The plain hop of natural-order field(s) as real-DFT matmuls:
        ``_folded_hop`` between a fold and an unfold where
        ``_folded_hop_applies``, else ``Gi (H o (G u G^T)) Gi^T`` on the
        real and imaginary parts stacked as one real operand, with the
        natural-order ``(hr, hi)`` pair as the packed spectrum's
        multiplier (``diffraction.real_dft_matrices``)."""
        n = self.grid.n
        if _folded_hop_applies(n):
            with stage("fft"):
                x = df.fold_field(u)
            x = self._folded_hop(x, pair)
            with stage("ifft"):
                return df.unfold_field(x, n)
        g, gi = df.real_dft_matrices(n)
        with stage("fft"):
            x = df.real_dft_2d(jnp.stack([u.real, u.imag]), g)
        with stage("tf_mul"):
            hr, hi = (p.astype(jnp.float32) for p in pair)
            xr, xi = x[0], x[1]
            x = jnp.stack([hr * xr - hi * xi, hr * xi + hi * xr])
        with stage("ifft"):
            y = df.real_dft_2d(x, gi)
            return jax.lax.complex(y[0], y[1])

    def _folded_hop(self, x: jax.Array, pair) -> jax.Array:
        """The plain hop as folded real-DFT matmuls on a field's four folded
        blocks (``diffraction.fold_field``), in and out: each block's
        forward stages, its quadrant of the natural-order ``(hr, hi)``
        pair, its inverse stages (``diffraction.folded_dft_matrices``)."""
        f, fi = df.folded_dft_matrices(self.grid.n)
        with stage("fft"):
            x = df.folded_dft_2d(x, f)
        with stage("tf_mul"):
            xr, xi = x[:, 0], x[:, 1]
            hr, hi = (_quadrants(p.astype(jnp.float32), f.shape[-1],
                                 xr.ndim) for p in pair)
            x = jnp.stack([hr * xr - hi * xi, hr * xi + hi * xr], axis=1)
        with stage("ifft"):
            return df.folded_dft_2d(x, fi, inverse=True)

    # --- codesign ---
    @stage("masks")
    def _codesign_stack(self, phis: jax.Array, rngs) -> jax.Array:
        """Per-layer hardware quantization on a stacked phase tensor.

        Matches the eager path: layer i uses key rngs[i]; in the multi-
        channel layout every channel of a layer shares that layer's key
        (the eager reference passes one rng into each channel's stack).
        """
        if self.device is None or self.codesign_mode == "none":
            return phis

        def per_layer(phi, rng):
            fn = lambda p: cd.apply_codesign(p, self.device,
                                             self.codesign_mode, rng)
            if phi.ndim > 2:  # (C, N, N): share the layer key across channels
                return jax.vmap(fn)(phi)
            return fn(phi)

        if rngs is None:
            return jax.vmap(lambda p: per_layer(p, None))(phis)
        return jax.vmap(per_layer)(phis, rngs)

    # --- forward ---
    def _scan_unroll(self, length: int) -> int:
        unroll = (self.unroll if self.unroll is not None
                  else default_scan_unroll(self.depth))
        return max(1, min(int(unroll), max(length, 1)))

    # --- phase-stack assembly (uniform: one stack; see SegmentedPlan) ---
    @property
    def segment_slices(self) -> tuple:
        """Global layer-index ranges of each fused scan segment."""
        return ((0, self.depth),)

    @stage("masks")
    def stack_phases(self, phases) -> jax.Array:
        """Per-layer phase arrays -> the (L, ...) stack ``forward`` scans."""
        return jnp.stack(list(phases))

    def forward(self, phis: jax.Array, u: jax.Array, rngs=None,
                start: int = 0, stop: Optional[int] = None,
                tfs=None, mask=None, pre=None, spectral=None,
                frozen=None, final: bool = False) -> jax.Array:
        """Scan layers [start, stop) over the field u.

        phis: full (L, ...) phase stack (codesign is applied to the whole
        stack so per-layer rng alignment is independent of the slice);
        rngs: optional (L, key) stack from ``jax.random.split``;
        tfs: optional external split-plane pair, each (depth+1, ...) —
        defaults to the plan's baked constants;
        mask: optional (L,) bool vector — masked-out layers are identity
        hops (the carry passes through untouched), which is how depth-
        padded candidate stacks emulate shallower architectures through
        one shared scan (``repro.core.models.emulate_batch``);
        pre: optional callable applied to the initial carry *inside* this
        forward (``SegmentedPlan`` folds boundary stitch resamples into the
        adjacent segment this way, so the stitch fuses with the segment's
        first hop instead of running as a detached einsum);
        spectral: optional (fft2, ifft2) override for every hop in the
        scan body — the distributed pencil-FFT path
        (``repro.runtime.pencil_fft.local_spectral_pair``);
        frozen: optional precomputed (L, ...) modulation plane pair from
        ``frozen_modulation`` — the deployment fast path.  With it the
        scan skips phase-stack codesign (quantization, rng) entirely and
        each layer is one hop plus one complex multiply; ``phis``/``rngs``/
        ``mask`` are ignored (pass None);
        final: also run the last free-space hop (``propagate_final``), on
        the scan's carry as it is (``stop`` must be the depth).

        Where the plain hop runs folded (``_folds``) the field is folded
        once here, the scan carries its folded blocks, each layer
        modulating them (``diffraction.modulate_folded``), and it is
        unfolded once at the end, after the final hop if ``final``.

        The plan's ``remat`` policy wraps the body (``"layer"``) or the
        whole scan (``"segment"``) in ``jax.checkpoint``.
        """
        stop = self.depth if stop is None else stop
        if final:
            self._check_final()
            if stop != self.depth:
                raise ValueError("the final hop follows the last layer")
        if pre is not None:
            u = pre(u)
        a, b = self._tf_pair() if tfs is None else tfs
        fold = self._folds(spectral)
        hops = stop - start
        if frozen is not None:
            mask = None
            mods = tuple(f[start:stop] for f in frozen)
        else:
            mods = (self._codesign_stack(phis, rngs)[start:stop],)
        if fold:
            with stage("masks"):
                mods = (df.fold_modulation(
                    jax.lax.complex(*dequant_frozen_layer(mods))
                    if frozen is not None
                    else self.gamma * jnp.exp(
                        1j * mods[0].astype(jnp.complex64))),)
            with stage("fft"):
                u = df.fold_field(u)
        xs = (a[start:stop], b[start:stop]) + mods
        if mask is not None:
            xs = xs + (mask[start:stop],)

        def body(carry, layer_xs):
            """One modulated layer: hop, then the layer's trainable phase,
            frozen modulation pair or folded modulation."""
            tf, mod = layer_xs[:2], layer_xs[2:2 + len(mods)]
            if fold:
                new = self._hop(carry, tf, hops=hops, folded=True)
                with stage("modulate"):
                    new = _modulate_folded(new, mod[0], hops)
            elif frozen is not None:
                new = self._modulate_frozen(
                    self._hop(carry, tf, spectral, hops=hops),
                    dequant_frozen_layer(mod))
            else:
                new = self._modulate(
                    self._hop(carry, tf, spectral, hops=hops), mod[0])
            if mask is not None:
                new = jnp.where(layer_xs[-1], new, carry)
            return new, None

        if self.remat == "layer":
            body = jax.checkpoint(body)

        def run(u0, xs_):
            out, _ = jax.lax.scan(body, u0, xs_,
                                  unroll=self._scan_unroll(hops))
            return out

        if self.remat == "segment":
            run = jax.checkpoint(run)
        u = run(u, xs)
        if final:
            u = self._hop(u, (a[self.depth], b[self.depth]), spectral,
                          folded=fold)
        if fold:
            with stage("ifft"):
                u = df.unfold_field(u, self.grid.n)
        return u

    def propagate_final(self, u: jax.Array, tfs=None,
                        spectral=None) -> jax.Array:
        """The last free-space hop (layer plane -> detector, no modulation)."""
        self._check_final()
        a, b = self._tf_pair() if tfs is None else tfs
        return self._hop(u, (a[self.depth], b[self.depth]), spectral)

    def _check_final(self) -> None:
        if not self.final_hop:
            raise ValueError(
                "this plan is an inner segment (final_hop=False); the next "
                "segment owns the following hop"
            )

    # --- real-to-complex first hop -------------------------------------
    def rfft_first_supported(self) -> bool:
        """Whether the half-spectrum first hop applies to this plan.

        Needs the plain fft2/ifft2 hop structure (no fraunhofer, no pad)
        and an even transfer function ``H(-f) = H(f)`` — true for every
        angular-spectrum TF here since they are functions of ``fx^2 +
        fy^2`` on the symmetric ``fftfreq`` grid (verified numerically at
        first use; ``first_layer_real`` raises otherwise).
        """
        return self.method != df.FRAUNHOFER and not self.pad

    def _rfft_half(self) -> tuple:
        """Cached half-spectrum cartesian TF planes for gap 0.

        A real input field has a conjugate-symmetric spectrum, and the TF
        is even, so hop 0 needs only the ``(N, N//2 + 1)`` rfft2 half
        grid: ``ifft2(U.H) = irfft2(U_half.Hr_half) + j irfft2(U_half.
        Hi_half)`` (each product is conjugate-symmetric because Hr/Hi are
        real and even).  1 rfft2 + 2 irfft2 ~ 1.5 full complex FFTs for
        the most common entry hop (intensity/amplitude encoded data).
        """
        cached = self._jax.get("_rhalf")
        if cached is not None:
            return cached
        if not self.rfft_first_supported():
            raise ValueError(
                "rfft first hop needs an unpadded non-fraunhofer plan"
            )
        p = transfer_planes(self.grid, self.gaps[0], self.wavelength,
                            self.method, self.band_limit, self.pad)
        half = self.grid.n // 2 + 1
        if not (df.is_even_plane(p["hr"]) and df.is_even_plane(p["hi"])):
            raise ValueError(
                "transfer function is not even in frequency; the "
                "half-spectrum first hop does not apply"
            )
        pair = (jnp.asarray(p["hr"][..., :half]),
                jnp.asarray(p["hi"][..., :half]))
        self._jax["_rhalf"] = pair
        return pair

    def first_layer_real(self, x: jax.Array, frozen) -> jax.Array:
        """Layer 0 (hop + frozen modulation) for a *real* input field.

        ``x`` is the real field amplitude (imag exactly zero — intensity/
        amplitude-encoded data through a real source); ``frozen`` the full
        frozen tuple from ``frozen_modulation``.  Continue with
        ``forward(None, u, start=1, frozen=frozen)``.
        """
        hr, hi = self._rfft_half()
        _HOP_STATS["fft"] += 1
        with stage("fft"):
            s = jnp.fft.rfft2(x)
        with stage("tf_mul"):
            sr, si = s * hr, s * hi
        n = (self.grid.n, self.grid.n)
        with stage("ifft"):
            u = jax.lax.complex(jnp.fft.irfft2(sr, s=n),
                                jnp.fft.irfft2(si, s=n))
        mod = dequant_frozen_layer(tuple(f[0] for f in tuple(frozen)))
        return self._modulate_frozen(u, mod)

    def apply(self, phis: jax.Array, u: jax.Array, rng=None,
              tfs=None, mask=None, spectral=None, frozen=None) -> jax.Array:
        """Full stack: scan all layers then the final hop.

        rng is a single key (split into per-layer keys here, mirroring the
        eager model) or None.  ``frozen`` takes a precomputed modulation
        plane pair (``frozen_modulation``) — the deployment fast path; rng
        and phis are then unused.
        """
        rngs = (jax.random.split(rng, self.depth)
                if rng is not None and frozen is None else None)
        return self.forward(phis, u, rngs, tfs=tfs, mask=mask,
                            spectral=spectral, frozen=frozen, final=True)

    def apply_batch(self, phis: jax.Array, u: jax.Array, rng=None,
                    tfs=None, per_candidate_inputs: bool = False,
                    mask=None) -> jax.Array:
        """Vmapped multi-candidate forward: K phase configs, one program.

        phis: (K, L, N, N) or (K, L, C, N, N) stack of K candidate phase
        configurations; u: one shared input field broadcast to every
        candidate, or a per-candidate (K, ...) stack when
        ``per_candidate_inputs``; tfs: optional per-candidate plane pair
        with leading K axis (each (K, depth+1, ...)) — the DSE path where
        candidate *geometries* differ but ride one compiled forward;
        rng: one key, split across candidates; mask: optional (K, L) bool
        layer-validity matrix for depth-padded (ragged-depth) candidate
        sets.  Returns the stacked (K, ...) detector-plane fields.
        """
        inp = {"phis": phis, "u": u}
        axes = {"phis": 0, "u": 0 if per_candidate_inputs else None}
        if rng is not None:
            inp["rng"] = jax.random.split(rng, phis.shape[0])
            axes["rng"] = 0
        if tfs is not None:
            inp["tfs"] = tuple(tfs)
            axes["tfs"] = (0, 0)
        if mask is not None:
            inp["mask"] = mask
            axes["mask"] = 0

        def one(d):
            return self.apply(d["phis"], d["u"], d.get("rng"),
                              tfs=d.get("tfs"), mask=d.get("mask"))

        return jax.vmap(one, in_axes=(axes,))(inp)


# --------------------------------------------------------------------------
# Segmented plan (heterogeneous per-layer architectures)
# --------------------------------------------------------------------------
def segment_layers(resolved_layers) -> tuple:
    """Group resolved ``LayerSpec``s into maximal fusable runs.

    Consecutive layers sharing (size, pixel_size, approximation, codesign
    device) compile into one fused ``lax.scan`` segment; a boundary is cut
    wherever any of those change.  Returns ``((start, stop), ...)`` global
    layer-index slices.
    """
    def seg_key(s):
        return (s.size, s.pixel_size, s.approximation, s.codesign,
                s.device_levels, s.response_gamma)

    slices, start = [], 0
    for i in range(1, len(resolved_layers)):
        if seg_key(resolved_layers[i]) != seg_key(resolved_layers[i - 1]):
            slices.append((start, i))
            start = i
    slices.append((start, len(resolved_layers)))
    return tuple(slices)


class SegmentedPlan:
    """Scan-based forward for a *heterogeneous* diffractive stack.

    Maximal runs of layers sharing (plane size, pixel size, approximation,
    codesign device) each compile to one fused ``lax.scan`` segment —
    exactly the uniform ``PropagationPlan`` machinery — with eager stitch
    hops between segments: when adjacent segments live on different grids
    the field is resampled (bilinear over physical coordinates, exact
    crop/pad for equal pixel sizes) at the boundary.  A uniform model is a
    single segment and never takes this path (``plan_from_config`` keeps
    returning the plain ``PropagationPlan`` for it), so the homogeneous
    HLO/perf is untouched.

    Phase stacks are *pytrees*: one ``(L_k, ...)`` stack per segment
    (``stack_phases`` assembles them from per-layer arrays; shapes are
    ragged across segments when plane sizes differ).
    """

    def __init__(self, cfg, gamma: float = 1.0):
        cfg = cfg.canonical()
        if cfg.layers is None:
            raise ValueError("SegmentedPlan needs a heterogeneous config; "
                             "use PropagationPlan for uniform stacks")
        specs = cfg.resolved_layers()
        self.cfg = cfg
        self.gamma = float(gamma)
        self.depth = len(specs)
        self.slices = segment_layers(specs)
        self.det_grid = df.Grid(cfg.n, cfg.pixel_size)
        self.segments = []
        for k, (lo, hi) in enumerate(self.slices):
            s0 = specs[lo]
            last = k == len(self.slices) - 1
            gaps = [specs[i].distance for i in range(lo, hi)]
            if last:
                gaps.append(cfg.gap_distances()[-1])
            self.segments.append(PropagationPlan(
                df.Grid(s0.size, s0.pixel_size),
                gaps,
                cfg.wavelength,
                method=s0.approximation,
                band_limit=cfg.band_limit,
                pad=cfg.pad,
                gamma=gamma,
                device=cd.device_for_layer(s0.codesign, s0.device_levels,
                                           s0.response_gamma),
                codesign_mode=s0.codesign,
                unroll=cfg.scan_unroll,
                tf_dtype=cfg.tf_dtype,
                final_hop=last,
                remat=cfg.remat,
            ))
        self.input_grid = self.segments[0].grid
        self.layer_grids = tuple(df.Grid(s.size, s.pixel_size) for s in specs)

    # --- phase-stack assembly ---
    @property
    def segment_slices(self) -> tuple:
        return self.slices

    @stage("masks")
    def stack_phases(self, phases) -> tuple:
        """Per-layer phase arrays -> per-segment stacks (ragged pytree)."""
        phases = list(phases)
        if len(phases) != self.depth:
            raise ValueError(f"expected {self.depth} phase maps, "
                             f"got {len(phases)}")
        return tuple(
            jnp.stack(phases[lo:hi]) for lo, hi in self.slices
        )

    def frozen_modulation(self, phis, plane_dtype: str = "float32") -> tuple:
        """Per-segment deploy-time fold (see ``PropagationPlan``'s).

        ``phis`` is the per-segment pytree from ``stack_phases``; returns
        one modulation plane tuple per segment, in segment order — the
        ``frozen=`` input of this plan's ``forward``/``apply``.
        ``plane_dtype`` applies to every segment (int8 scales stay
        per-layer within each segment).
        """
        return tuple(
            seg.frozen_modulation(p, plane_dtype)
            for seg, p in zip(self.segments, phis)
        )

    # --- forward ---
    def forward(self, phis, u: jax.Array, rngs=None, start: int = 0,
                stop: Optional[int] = None, tfs=None,
                frozen=None, final: bool = False) -> jax.Array:
        """Run global layers [start, stop); ``phis`` is the per-segment
        pytree from ``stack_phases``.  The incoming field must live on the
        grid of layer ``start - 1`` (the input grid when start == 0); the
        returned field lives on the grid of layer ``stop - 1``, or with
        ``final`` on the detector grid, after ``propagate_final``'s hop
        (which the last segment's forward runs) and stitch.
        ``frozen`` takes the per-segment pair tuple from this plan's
        ``frozen_modulation`` (deployment fast path; phis/rngs unused)."""
        if tfs is not None:
            raise NotImplementedError(
                "external transfer planes are a uniform-plan feature "
                "(batched DSE); segmented plans bake their constants"
            )
        stop = self.depth if stop is None else stop
        if final and stop != self.depth:
            raise ValueError("the final hop follows the last layer")
        cur_grid = (self.layer_grids[start - 1] if start > 0
                    else self.input_grid)
        for k, (lo, hi) in enumerate(self.slices):
            a, b = max(lo, start), min(hi, stop)
            last = final and k == len(self.slices) - 1
            if a >= b and not last:
                continue
            seg = self.segments[k]
            stitch = None
            if seg.grid != cur_grid:
                # boundary stitch folded into the adjacent segment: the
                # resample runs inside ``seg.forward`` (split real/imag
                # matmuls, exact slicing at equal pitch) so it fuses with
                # the segment's first hop instead of sitting between scans
                src = cur_grid
                stitch = lambda v, s=src, g=seg.grid: df.resample_field(
                    v, s, g)
            if frozen is not None:
                u = seg.forward(None, u, start=a - lo, stop=b - lo,
                                pre=stitch, frozen=frozen[k], final=last)
            else:
                seg_rngs = rngs[lo:hi] if rngs is not None else None
                u = seg.forward(phis[k], u, seg_rngs, start=a - lo,
                                stop=b - lo, pre=stitch, final=last)
            cur_grid = seg.grid
        if final:
            u = df.resample_field(u, cur_grid, self.det_grid)
        return u

    def propagate_final(self, u: jax.Array, tfs=None) -> jax.Array:
        """Last free-space hop (on the last layer's grid), then the stitch
        onto the detector grid if it differs."""
        if tfs is not None:
            raise NotImplementedError("segmented plans bake their constants")
        u = self.segments[-1].propagate_final(u)
        return df.resample_field(u, self.segments[-1].grid, self.det_grid)

    def apply(self, phis, u: jax.Array, rng=None, tfs=None,
              frozen=None) -> jax.Array:
        rngs = (jax.random.split(rng, self.depth)
                if rng is not None and frozen is None else None)
        return self.forward(phis, u, rngs, tfs=tfs, frozen=frozen,
                            final=True)


def device_spec_from_config(cfg) -> Optional[cd.DeviceSpec]:
    """The (frozen, hashable) codesign device a config describes, or None."""
    return cd.device_for_layer(cfg.codesign, cfg.device_levels,
                               cfg.response_gamma)


def plan_cache_key(cfg, gamma: float) -> tuple:
    """Geometry tuple identifying one plan build.

    Configs are canonicalized first, so a uniform architecture spelled via
    ``layers`` hits the *identical* cache entry as the scalar spelling;
    genuinely heterogeneous configs key on the fully-resolved per-layer
    tuple instead.
    """
    cfg = cfg.canonical()
    if cfg.layers is not None:
        per_layer = tuple(
            (l.size, float(l.pixel_size), float(l.distance), l.approximation,
             l.codesign, l.device_levels, float(l.response_gamma))
            for l in cfg.layers
        )
        return ("seg", per_layer, cfg.n, float(cfg.pixel_size),
                float(cfg.distance), float(cfg.wavelength),
                bool(cfg.band_limit), bool(cfg.pad), float(gamma),
                cfg.scan_unroll, cfg.tf_dtype, cfg.remat)
    dev = device_spec_from_config(cfg)
    return (cfg.n, float(cfg.pixel_size), cfg.gap_distances(),
            float(cfg.wavelength), cfg.approximation, bool(cfg.band_limit),
            bool(cfg.pad), float(gamma), dev, cfg.codesign,
            cfg.scan_unroll, cfg.tf_dtype, cfg.remat)


def plan_from_config(cfg, gamma: float):
    """Build (or fetch) the plan for a config — memoized per geometry tuple.

    Uniform configs get the fused single-scan ``PropagationPlan``;
    heterogeneous configs (``cfg.layers`` surviving canonicalization) get a
    ``SegmentedPlan``.  Plans are immutable once built (stacked numpy
    constants + lazily uploaded device arrays), so every model/step/
    benchmark sharing a geometry shares one plan instead of rebuilding and
    re-uploading it.
    """
    key = plan_cache_key(cfg, gamma)
    plan = _cache_get(_PLAN_CACHE, key, _PLAN_STATS)
    if plan is not None:
        return plan
    # validate once per plan-cache miss: physically invalid geometry
    # raises a structured PhysicsValidationError naming the criterion
    # before any TF plane is built (soft regime violations warn)
    physics.check_config(cfg)
    cfg = cfg.canonical()
    if cfg.layers is not None:
        plan = SegmentedPlan(cfg, gamma)
    else:
        dev = device_spec_from_config(cfg)
        plan = PropagationPlan(
            df.Grid(cfg.n, cfg.pixel_size),
            cfg.gap_distances(),
            cfg.wavelength,
            method=cfg.approximation,
            band_limit=cfg.band_limit,
            pad=cfg.pad,
            gamma=gamma,
            device=dev,
            codesign_mode=cfg.codesign,
            unroll=cfg.scan_unroll,
            tf_dtype=cfg.tf_dtype,
            remat=cfg.remat,
        )
    _cache_put(_PLAN_CACHE, key, plan, _PLAN_CACHE_MAX)
    return plan
