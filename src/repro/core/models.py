"""DONN model containers (LightRidge `lr.models`).

- ``DONN``: sequential stack of diffractive layers + detector (classification).
- ``MultiChannelDONN``: the paper's RGB architecture (Fig. 12) — parallel
  optical channels whose output intensities merge on one detector.
- ``SegmentationDONN``: the paper's image-segmentation architecture (Fig. 13)
  with *optical skip connection* (complex-field beam-splitter sum) and
  train-time layer normalization.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codesign as cd
from repro.core import diffraction as df
from repro.core import propagation as pp
from repro.core.cache import lru_get, lru_put
from repro.core.config import DONNConfig
from repro.core.laser import Laser, data_to_cplex
from repro.core.layers import Detector, DiffractiveLayer
from repro.core.propagation import plan_from_config
from repro.nn import ParamSpec, init_params


def channel_readout(u: jax.Array, masks) -> jax.Array:
    """Multi-channel detector accumulation, shared by every path.

    (..., C, n, n) per-channel output fields -> (..., num_classes): the
    incoherent channel sum pooled over the per-class detector regions, as
    one jnp contraction.  One definition serves training
    (``MultiChannelDONN.apply``, both engines), batched DSE emulation
    (``emulate_batch``) and the deployment engine
    (``repro.runtime.inference``), so the contraction cannot drift between
    them.
    """
    return df.readout(u, jnp.asarray(masks), channel_axis=True)


def _build_layers(cfg: DONNConfig, gamma: float):
    """Eager per-layer stack from the (possibly heterogeneous) config.

    Each layer owns its *own* grid / approximation / codesign device
    (resolved from ``cfg.layers`` or the uniform scalars); the final
    free-space hop to the detector runs on the last layer's grid.
    """
    specs = cfg.resolved_layers()
    layers = []
    for s in specs:
        layers.append(
            DiffractiveLayer(
                df.Grid(s.size, s.pixel_size),
                s.distance,
                cfg.wavelength,
                method=s.approximation,
                band_limit=cfg.band_limit,
                pad=cfg.pad,
                device=cd.device_for_layer(s.codesign, s.device_levels,
                                           s.response_gamma),
                codesign_mode=s.codesign,
                gamma=gamma,
            )
        )
    # final free-space hop: last layer -> detector plane (no modulation)
    final = DiffractiveLayer(
        layers[-1].grid,
        cfg.gap_distances()[-1],
        cfg.wavelength,
        method=specs[-1].approximation,
        band_limit=cfg.band_limit,
        pad=cfg.pad,
        gamma=1.0,
    )
    return layers, final


class DONN:
    """Sequential DONN classifier."""

    def __init__(self, cfg: DONNConfig, laser: Optional[Laser] = None):
        if cfg.channels != 1:
            raise ValueError("use MultiChannelDONN for channels > 1")
        self.cfg = cfg
        self.grid = df.Grid(cfg.n, cfg.pixel_size)  # detector/system grid
        self.laser = laser or Laser(wavelength=cfg.wavelength)
        self.gamma = 1.0 if cfg.gamma is None else float(cfg.gamma)
        self.layers, self.final = _build_layers(cfg, self.gamma)
        self.in_grid = self.layers[0].grid  # source plane (first layer size)
        self._plan = None  # built on first scan-path use
        self.detector = Detector(
            self.grid,
            cfg.num_classes,
            cfg.det_size,
            cfg.detector_layout,
        )
        self.source = self.laser.field(self.in_grid)  # (n, n) complex64 const

    @property
    def plan(self):
        if self._plan is None:
            self._plan = plan_from_config(self.cfg, self.gamma)
        return self._plan

    # --- params ---
    def param_specs(self):
        return {
            "phase": {
                f"layer_{i}": layer.param_spec()
                for i, layer in enumerate(self.layers)
            }
        }

    def init(self, key: jax.Array):
        return init_params(self.param_specs(), key)

    # --- forward ---
    @pp.stage("encode")
    def encode(self, x: jax.Array) -> jax.Array:
        u = data_to_cplex(x, self.in_grid.n)
        return u * jnp.asarray(self.source)

    def fields(self, params, x, rng: Optional[jax.Array] = None):
        """All intermediate fields (lr.model.prop_view)."""
        u = self.encode(x)
        out = [u]
        rngs = (
            jax.random.split(rng, len(self.layers)) if rng is not None else
            [None] * len(self.layers)
        )
        cur = self.in_grid
        for i, layer in enumerate(self.layers):
            u = df.resample_field(u, cur, layer.grid)  # no-op on equal grids
            u = layer(params["phase"][f"layer_{i}"], u, rngs[i])
            cur = layer.grid
            out.append(u)
        u = self.final.propagate(u)
        u = df.resample_field(u, self.final.grid, self.grid)
        out.append(u)
        return out

    def stacked_phases(self, params):
        """Phase stack in the plan's layout: one (L, N, N) array for
        uniform stacks, a per-segment pytree for heterogeneous ones."""
        return self.plan.stack_phases(
            params["phase"][f"layer_{i}"] for i in range(len(self.layers))
        )

    def apply(self, params, x, rng: Optional[jax.Array] = None) -> jax.Array:
        """Images (..., h, w) -> per-class detector intensities (..., C)."""
        if self.cfg.engine == "eager":
            u = self.fields(params, x, rng)[-1]
        else:
            u = self.plan.apply(self.stacked_phases(params), self.encode(x),
                                rng)
        return self.detector(u)

    def prop_view(self, params, x, rng=None):
        return [df.intensity(u) for u in self.fields(params, x, rng)]


class MultiChannelDONN:
    """Multi-channel (RGB) DONN (paper Fig. 12).

    ``channels`` parallel optical stacks; each encodes one input channel; all
    output beams project onto a single shared detector where intensities add.
    """

    def __init__(self, cfg: DONNConfig, laser: Optional[Laser] = None):
        self.cfg = cfg
        sub = DONNConfig(**{**cfg.__dict__, "channels": 1})
        self.channel_model = DONN(sub, laser)

    def param_specs(self):
        spec = self.channel_model.param_specs()["phase"]
        c = self.cfg.channels
        return {
            "phase": {
                name: ParamSpec(
                    (c,) + s.shape,
                    s.dtype,
                    ("channel",) + s.logical_axes,
                    init=s.init,
                )
                for name, s in spec.items()
            }
        }

    def init(self, key):
        return init_params(self.param_specs(), key)

    def apply(self, params, x, rng: Optional[jax.Array] = None) -> jax.Array:
        """x: (..., C, h, w) multi-channel images -> (..., num_classes)."""
        cm = self.channel_model
        if self.cfg.engine == "eager":
            def one_channel(phases, xc):
                p = {"phase": phases}
                u = cm.fields(p, xc, rng)[-1]
                return u

            # vmap over the channel axis of both params and inputs
            u = jax.vmap(one_channel, in_axes=(0, -3), out_axes=-3)(
                params["phase"], x
            )  # (..., C, n, n) per-channel output fields
            return channel_readout(u, cm.detector.masks)
        # batched plan path: all channels propagate as one (..., C, N, N)
        # tensor through shared kernels (the TFs are channel-independent;
        # the (L, C, N, N) phase stack rides the scan — per segment for
        # heterogeneous stacks).
        phis = cm.plan.stack_phases(
            params["phase"][f"layer_{i}"] for i in range(len(cm.layers))
        )
        u = cm.plan.apply(phis, cm.encode(x), rng)
        return channel_readout(u, cm.detector.masks)


class SegmentationDONN:
    """All-optical image segmentation DONN (paper Fig. 13a).

    Optical skip connection: the field exiting layer ``skip_from`` is split
    off, propagated directly to the detector plane, and coherently recombined
    (beam-splitter sum, 1/sqrt(2) each) with the main path.  LayerNorm on the
    output intensity is applied only during training.
    """

    def __init__(self, cfg: DONNConfig, laser: Optional[Laser] = None):
        self.cfg = cfg
        self.grid = df.Grid(cfg.n, cfg.pixel_size)  # detector/system grid
        self.laser = laser or Laser(wavelength=cfg.wavelength)
        self.gamma = 1.0 if cfg.gamma is None else float(cfg.gamma)
        self.layers, self.final = _build_layers(cfg, self.gamma)
        self.in_grid = self.layers[0].grid
        self._plan = None  # built on first scan-path use
        self.skip_from = cfg.skip_from
        if self.skip_from is not None:
            # skip hop covers the remaining distance to the detector plane,
            # computed on the skip plane's own grid
            gaps = cfg.gap_distances()
            z_skip = float(sum(gaps[self.skip_from + 1 :]))
            skip_grid = self.layers[self.skip_from].grid
            self.skip_hop = DiffractiveLayer(
                skip_grid,
                z_skip,
                cfg.wavelength,
                method=cfg.resolved_layers()[self.skip_from].approximation,
                band_limit=cfg.band_limit,
                pad=cfg.pad,
            )
        self.source = self.laser.field(self.in_grid)

    @property
    def plan(self):
        if self._plan is None:
            self._plan = plan_from_config(self.cfg, self.gamma)
        return self._plan

    def param_specs(self):
        return {
            "phase": {
                f"layer_{i}": layer.param_spec()
                for i, layer in enumerate(self.layers)
            }
        }

    def init(self, key):
        return init_params(self.param_specs(), key)

    def apply(
        self, params, x, rng: Optional[jax.Array] = None, train: bool = False
    ) -> jax.Array:
        """Images (..., h, w) -> per-pixel intensity map (..., n, n)."""
        with pp.stage("encode"):
            u = data_to_cplex(x, self.in_grid.n) * jnp.asarray(self.source)
        skip_u = None
        if self.cfg.engine == "eager":
            rngs = (
                jax.random.split(rng, len(self.layers)) if rng is not None
                else [None] * len(self.layers)
            )
            cur = self.in_grid
            for i, layer in enumerate(self.layers):
                u = df.resample_field(u, cur, layer.grid)
                u = layer(params["phase"][f"layer_{i}"], u, rngs[i])
                cur = layer.grid
                if self.skip_from is not None and i == self.skip_from:
                    skip_u = u
            u = self.final.propagate(u)
            u = df.resample_field(u, self.final.grid, self.grid)
        else:
            phis = self.plan.stack_phases(
                params["phase"][f"layer_{i}"]
                for i in range(len(self.layers))
            )
            rngs = (
                jax.random.split(rng, len(self.layers)) if rng is not None
                else None
            )
            if self.skip_from is None:
                u = self.plan.forward(phis, u, rngs, final=True)
            else:
                u = self.plan.forward(phis, u, rngs,
                                      stop=self.skip_from + 1)
                skip_u = u
                u = self.plan.forward(phis, u, rngs,
                                      start=self.skip_from + 1, final=True)
        if skip_u is not None:
            # beam-splitter recombination on the detector grid
            sk = self.skip_hop.propagate(skip_u)
            sk = df.resample_field(sk, self.skip_hop.grid, self.grid)
            u = (u + sk) / jnp.sqrt(2.0).astype(jnp.complex64)
        inten = df.intensity(u)
        if train and self.cfg.layer_norm:
            mean = jnp.mean(inten, axis=(-2, -1), keepdims=True)
            var = jnp.var(inten, axis=(-2, -1), keepdims=True)
            inten = (inten - mean) * jax.lax.rsqrt(var + 1e-6)
        return inten


def build_model(cfg: DONNConfig, laser: Optional[Laser] = None):
    """Factory used by the DSL and configs."""
    if cfg.segmentation:
        return SegmentationDONN(cfg, laser)
    if cfg.channels > 1:
        return MultiChannelDONN(cfg, laser)
    return DONN(cfg, laser)


# --------------------------------------------------------------------------
# Compile-once emulation runtime
# --------------------------------------------------------------------------
_MODEL_CACHE: dict = {}
_MODEL_CACHE_MAX = 64
_MODEL_STATS = {"hits": 0, "misses": 0}

# geometry knobs free to vary across one emulate_batch candidate set; every
# other config field is an architecture static shared by the batch.  depth
# rides along via depth-padded + masked candidate stacks.
_GEOMETRY_FIELDS = ("name", "wavelength", "pixel_size", "distance",
                    "distances", "depth")


def config_static_key(cfg: DONNConfig) -> tuple:
    """Hashable config key (canonicalized, drops the cosmetic name).

    ``name`` never reaches the compiled program, so configs identical up
    to it share models and executables — a DSE sweep naming its candidates
    uniquely still compiles once per geometry.  The key is built on the
    *canonical* config (``DONNConfig.canonical``): uniform architectures
    spelled via ``layers`` collapse onto the scalar spelling, ``distance``
    / ``distances`` normalize through ``gap_distances()``, and surviving
    heterogeneous ``layers`` flatten to hashable per-layer tuples.
    """
    cfg = cfg.canonical()
    d = dataclasses.asdict(cfg)
    d.pop("name")
    d["distances"] = cfg.gap_distances()
    d["distance"] = 0.0  # folded into the normalized distances
    if d["layers"] is not None:
        d["layers"] = tuple(
            tuple(sorted(l.items())) for l in d["layers"]
        )
    return tuple(sorted(d.items()))


def _shared_statics_key(cfg: DONNConfig) -> tuple:
    d = dict(config_static_key(cfg))
    for f in _GEOMETRY_FIELDS:
        d.pop(f, None)
    return tuple(sorted(d.items()))


def clear_emulation_caches() -> None:
    """Clear the model + batched-input memos and the plan/exec caches."""
    _MODEL_CACHE.clear()
    _MODEL_STATS.update(hits=0, misses=0)
    _BATCH_INPUT_CACHE.clear()
    _BATCH_INPUT_STATS.update(hits=0, misses=0)
    pp.clear_plan_cache()


def model_cache_key(model) -> Optional[tuple]:
    """Executable-cache identity of a model, or None when not keyable.

    A model may share cached (training) executables iff its numerics are a
    pure function of its config: it exposes ``cfg`` and was built with the
    default laser (``Laser`` is a frozen dataclass, so default-equivalent
    explicit lasers compare equal).  Custom-profile models return None and
    fall back to per-closure jit.  Used by the train-step factories in
    ``repro.core.train_utils``.
    """
    cfg = getattr(model, "cfg", None)
    if cfg is None:
        return None
    inner = getattr(model, "channel_model", model)  # MultiChannelDONN
    if getattr(inner, "laser", None) != Laser(wavelength=cfg.wavelength):
        return None
    return config_static_key(cfg)


def cached_model(cfg: DONNConfig, laser: Optional[Laser] = None):
    """Memoized ``build_model`` (default laser only).

    DSE sweeps, retraced train-step factories and repeated benchmarks reuse
    one layer stack + detector per config instead of rebuilding them.
    Models are stateless w.r.t. params, so sharing is safe.
    """
    if laser is not None:
        return build_model(cfg, laser)
    key = config_static_key(cfg)
    model = lru_get(_MODEL_CACHE, key, _MODEL_STATS)
    if model is None:
        model = build_model(cfg)
        lru_put(_MODEL_CACHE, key, model, _MODEL_CACHE_MAX)
    return model


def cached_apply(cfg: DONNConfig):
    """Compile-once ``model.apply``: f(params, x, rng=None).

    Backed by the process-wide executable cache — keyed by config statics
    plus input shapes/dtypes — so repeated emulations of one geometry pay
    trace+compile exactly once per shape, however many times the model is
    (re)built around it.
    """
    model = cached_model(cfg)
    skey = ("donn_apply", config_static_key(cfg))

    def run(params, x, rng=None):
        # input transfer, executable lookup (a compile on a miss) and launch
        with jax.profiler.TraceAnnotation(pp.DISPATCH_SPAN):
            x = jnp.asarray(x)
            if rng is None:
                ex = pp.cached_executable(
                    skey + ("norng",), lambda p, xx: model.apply(p, xx),
                    params, x,
                )
                return ex(params, x)
            ex = pp.cached_executable(
                skey + ("rng",), lambda p, xx, r: model.apply(p, xx, r),
                params, x, rng,
            )
            return ex(params, x, rng)

    return run


@pp.stage("masks")
def _stack_phases(params, depth: int, pad_to: Optional[int] = None) -> jax.Array:
    """(L, ...) phase stack; zero-padded along L to ``pad_to`` if given."""
    phis = jnp.stack(
        [params["phase"][f"layer_{i}"] for i in range(depth)]
    )
    if pad_to is not None and pad_to > depth:
        phis = jnp.pad(
            phis, [(0, pad_to - depth)] + [(0, 0)] * (phis.ndim - 1)
        )
    return phis


def _pad_planes(planes: np.ndarray, depth: int, pad_to: int) -> np.ndarray:
    """Pad a (depth+1, ...) TF-plane stack to (pad_to+1, ...).

    Rows [0, depth) are the real layer gaps, row ``depth`` the final hop.
    Dummy rows (copies of the final-hop plane — any finite plane works,
    the layer mask makes them identity hops) are inserted *between* the
    layer gaps and the final hop so the shared scan-plus-final program
    reads every candidate's final plane at the same index ``pad_to``.
    """
    if depth == pad_to:
        return planes
    dummy = np.repeat(planes[depth:depth + 1], pad_to - depth, axis=0)
    return np.concatenate([planes[:depth], dummy, planes[depth:]], axis=0)


# candidate-set geometry -> stacked device inputs (TF planes, sources, skip
# planes).  They are deterministic in the geometry tuple, so warm
# emulate_batch calls skip the per-candidate host rebuild + re-upload.
_BATCH_INPUT_CACHE: dict = {}
_BATCH_INPUT_CACHE_MAX = 32
_BATCH_INPUT_STATS = {"hits": 0, "misses": 0}


def _batched_inputs(cfgs, base, gamma: float, template, has_skip: bool):
    """Stacked (K, ...) transfer planes, sources and skip planes (memoized).

    Candidates of unequal depth are padded to the deepest one
    (``template.depth``): dummy gap planes fill the tail of each TF stack
    (masked to identity hops by the caller's layer mask) and every
    candidate's final hop lands at the shared index ``template.depth``.
    """
    key = ("emulate_inputs",
           tuple(pp.plan_cache_key(c, gamma) for c in cfgs),
           base.skip_from if has_skip else None)
    hit = lru_get(_BATCH_INPUT_CACHE, key, _BATCH_INPUT_STATS)
    if hit is not None:
        return hit
    plans = [pp.plan_from_config(c, gamma) for c in cfgs]
    L = template.depth
    tf_a = jnp.asarray(
        np.stack([_pad_planes(p._np["hr"], p.depth, L) for p in plans])
    )
    tf_b = jnp.asarray(
        np.stack([_pad_planes(p._np["hi"], p.depth, L) for p in plans])
    )
    if base.tf_dtype != "float32":
        tf_a = tf_a.astype(base.tf_dtype)
        tf_b = tf_b.astype(base.tf_dtype)
    sources = jnp.asarray(np.stack([
        Laser(wavelength=c.wavelength).field(df.Grid(c.n, c.pixel_size))
        for c in cfgs
    ]))
    skip_pair = None
    if has_skip:
        # skip hop covers the remaining distance to the detector plane,
        # per candidate geometry
        def _skip_planes(c):
            gaps = c.gap_distances()
            z = float(sum(gaps[base.skip_from + 1:]))
            return pp.transfer_planes(
                df.Grid(c.n, c.pixel_size), z, c.wavelength,
                method=base.approximation, band_limit=base.band_limit,
                pad=template.pad,
            )
        sk = [_skip_planes(c) for c in cfgs]
        skip_pair = (jnp.asarray(np.stack([p["hr"] for p in sk])),
                     jnp.asarray(np.stack([p["hi"] for p in sk])))
    entry = (tf_a, tf_b, sources, skip_pair)
    lru_put(_BATCH_INPUT_CACHE, key, entry, _BATCH_INPUT_CACHE_MAX)
    return entry


def emulate_batch(cfgs: Sequence[DONNConfig], params, x, rng=None,
                  train: bool = False) -> jax.Array:
    """Emulate K candidate DONN configs in one compiled, vmapped forward.

    The DSE verification primitive: all cfgs must share architecture
    statics (n, channels, detector geometry, engine flags), while
    per-candidate *geometry* — wavelength, pixel_size, distance(s), and
    **depth** — is free.  Per-candidate transfer planes and source fields
    enter the compiled program as traced inputs (not baked constants), so
    every candidate set with the same statics and shapes reuses one cached
    executable: K emulations cost one trace+compile plus one device call,
    instead of K sequential ``build_model`` + ``jit(apply)`` cycles.

    Ragged-depth candidate sets are depth-padded to the deepest candidate
    and masked: padded layers are identity hops inside the shared scan, so
    a 2-layer and a 5-layer architecture score in the *same* device call
    (per-candidate params required; with rng-driven codesign the per-layer
    key split uses the padded depth, so stochastic modes are deterministic
    but not bitwise-aligned with a sequential per-depth emulation).

    params: one pytree shared by every candidate, or a sequence of K
    pytrees (required when depths differ).  x: one shared input batch.
    rng: one key, split across candidates (candidate i sees
    ``jax.random.split(rng, K)[i]``).

    Returns the stacked (K, ...) outputs of ``build_model(cfg).apply`` per
    candidate: per-class intensities for classifiers, intensity maps for
    segmentation (``train=True`` applies the train-time layer norm).
    """
    cfgs = [c.canonical() for c in cfgs]
    if not cfgs:
        raise ValueError("emulate_batch needs at least one candidate")
    for c in cfgs:
        if c.layers is not None:
            raise ValueError(
                "emulate_batch candidates must be per-candidate-uniform "
                f"stacks; {c.name!r} has heterogeneous per-layer specs "
                "(cfg.layers), which cannot share one vmapped scan yet"
            )
    base = cfgs[0]
    skey = _shared_statics_key(base)
    for c in cfgs[1:]:
        if _shared_statics_key(c) != skey:
            raise ValueError(
                "emulate_batch candidates must share all non-geometry "
                "statics (n, channels, detector, engine flags); "
                f"{c.name!r} differs from {base.name!r}"
            )
    K = len(cfgs)
    n = base.n
    gamma = 1.0 if base.gamma is None else float(base.gamma)
    depths = [c.depth for c in cfgs]
    mixed_depth = len(set(depths)) > 1
    # the template plan supplies the shared scan program; its depth is the
    # padded depth every candidate rides (shallower ones mask their tail)
    template = pp.plan_from_config(cfgs[int(np.argmax(depths))], gamma)
    has_skip = base.segmentation and base.skip_from is not None
    if has_skip and base.skip_from >= min(depths):
        raise ValueError(
            f"skip_from={base.skip_from} must precede the shallowest "
            f"candidate (min depth {min(depths)})"
        )
    tf_a, tf_b, sources, skip_pair = _batched_inputs(
        cfgs, base, gamma, template, has_skip
    )
    if isinstance(params, (list, tuple)):
        if len(params) != K:
            raise ValueError(f"got {len(params)} params for {K} candidates")
        phis = jnp.stack([
            _stack_phases(p, c.depth, pad_to=template.depth)
            for p, c in zip(params, cfgs)
        ])
    else:
        if mixed_depth:
            raise ValueError(
                "mixed-depth candidate sets need per-candidate params "
                "(one pytree per depth); got a single shared pytree"
            )
        one = _stack_phases(params, base.depth)
        phis = jnp.broadcast_to(one[None], (K,) + one.shape)
    x = jnp.asarray(x)

    family = ("seg" if base.segmentation
              else "multi" if base.channels > 1 else "cls")
    use_rng = rng is not None
    if family == "cls":
        det = cached_model(base).detector
    elif family == "multi":
        det = cached_model(base).channel_model.detector
    else:
        det = None

    # one dict pytree in, so jit/vmap handle the optional inputs natively
    # (no positional-argument protocol to keep in sync)
    inputs = {"tf_a": tf_a, "tf_b": tf_b, "src": sources, "phis": phis,
              "x": x}
    if use_rng:
        inputs["rngs"] = jax.random.split(rng, K)
    if has_skip:
        inputs["skip_a"], inputs["skip_b"] = skip_pair
    if mixed_depth:
        # (K, L_max) layer-validity mask: padded tail layers become
        # identity hops inside the shared scan
        inputs["mask"] = jnp.asarray(
            np.arange(template.depth)[None, :] < np.asarray(depths)[:, None]
        )

    def fn(inp):
        with pp.stage("encode"):
            u0 = data_to_cplex(inp["x"], n)  # shared encoded input batch

        def candidate(a, b, src, p, r=None, sa=None, sb=None, m=None):
            with pp.stage("encode"):
                u = u0 * src
            tfs = (a, b)
            if family == "seg":
                rngs_l = (jax.random.split(r, template.depth)
                          if r is not None else None)
                if has_skip:
                    u = template.forward(p, u, rngs_l,
                                         stop=base.skip_from + 1, tfs=tfs,
                                         mask=m)
                    skip_u = u
                    u = template.forward(p, u, rngs_l,
                                         start=base.skip_from + 1, tfs=tfs,
                                         mask=m, final=True)
                    u = (u + template._hop(skip_u, (sa, sb))) / jnp.sqrt(
                        2.0
                    ).astype(jnp.complex64)
                else:
                    u = template.forward(p, u, rngs_l, tfs=tfs, mask=m,
                                         final=True)
                inten = df.intensity(u)
                if train and base.layer_norm:
                    mean = jnp.mean(inten, axis=(-2, -1), keepdims=True)
                    var = jnp.var(inten, axis=(-2, -1), keepdims=True)
                    inten = (inten - mean) * jax.lax.rsqrt(var + 1e-6)
                return inten
            u = template.apply(p, u, r, tfs=tfs, mask=m)
            if family == "multi":
                return channel_readout(u, det.masks)
            return det(u)

        per_cand = {k: v for k, v in inp.items() if k != "x"}

        def one(c):
            return candidate(c["tf_a"], c["tf_b"], c["src"], c["phis"],
                             c.get("rngs"), c.get("skip_a"), c.get("skip_b"),
                             c.get("mask"))

        return jax.vmap(one)(per_cand)

    static_key = ("emulate_batch", family, skey, use_rng, bool(train),
                  mixed_depth)
    ex = pp.cached_executable(static_key, fn, inputs)
    return ex(inputs)
