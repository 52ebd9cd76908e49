"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Parameters and activations carry *logical* axis names (see nn.ParamSpec);
rules map logical names to mesh axes.  ``resolve_pspec`` drops a mapping
when the dim is not divisible by the mesh-axis extent (e.g. kv_heads=2 on a
16-way model axis -> replicated), which keeps one rule set valid across all
10 architectures.

Default layout (DESIGN.md §8):
  batch      -> (pod, data)   data parallel
  embed      -> data          FSDP: params + optimizer states sharded
  vocab/heads/kv_heads/mlp/expert -> model   TP / EP
  field_w    -> model         DONN spatial model-parallel (pencil FFT)
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn import ParamSpec, is_spec

class ShardingRulesError(ValueError):
    """A rules table maps conflicting logical axes onto one mesh axis.

    Raised (rather than silently picking a winner) when ``batch`` and
    ``field_h`` — the two axes that define the 2-D ``(data, model)``
    layout — claim the same mesh axis: sharding the batch and the field
    rows over one axis would make every device see a *different* row
    block of a *different* batch shard, which is never the intended
    layout and produces silently wrong psums.
    """


DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": "model",  # Megatron-style sequence parallelism: the residual
    #                  stream between layers shards S over the TP axis, so
    #                  saved layer-boundary activations are 1/TP the size;
    #                  GSPMD inserts the AG/RS pair around each block.
    "embed": ("data", "pod"),  # FSDP + ZeRO-across-pods: parameters and
    #                  optimizer moments shard over data AND pod axes
    #                  (32-way on the 512-chip mesh) — cross-pod traffic is
    #                  the per-layer gather, compressible (optim.compression)
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head": "model",  # head_dim fallback: shards KV caches when kv_heads
    #                   is not divisible by the model axis (GQA/MQA archs)
    "mlp": "model",
    "expert": "model",
    "channel": None,
    "layers": None,
    "field_h": None,
    "field_w": "model",
    "population": ("pod", "data"),  # DSE candidate stacks: generations of
    #                  K candidates shard over the DP axes, composing with
    #                  field_h -> model (population x spatial on one mesh)
    "classes": None,
}


def donn_rules(*, data="data", model="model") -> dict:
    """THE unified DONN rules table for the 2-D ``(data, model)`` mesh.

    One table consumed by training (``donn_steps.make_donn_sharded_loss``),
    serving (``InferenceEngine(model_devices=...)``) and DSE stacks:

      batch / population -> (pod, data)   data parallel
      field_h            -> model         spatial rows (pencil FFT)
      field_w / channel  -> replicated    (W is the locally-full FFT axis)

    Validated by :func:`check_rules` — ``batch`` and ``field_h`` on the
    same mesh axis raise :class:`ShardingRulesError`.
    """
    return check_rules({
        **DEFAULT_RULES,
        "batch": ("pod", data),
        "population": ("pod", data),
        "field_h": model,
        "field_w": None,
    })


def check_rules(rules: Mapping[str, Any]) -> Mapping[str, Any]:
    """Typed validation of a rules table: batch/field_h must not collide."""
    def flat(v):
        return () if v is None else ((v,) if isinstance(v, str) else tuple(v))

    overlap = set(flat(rules.get("batch"))) & set(flat(rules.get("field_h")))
    if overlap:
        raise ShardingRulesError(
            f"'batch' and 'field_h' both map onto mesh axis "
            f"{sorted(overlap)[0]!r}: the data and spatial layouts would "
            f"alias — give each its own mesh axis (see make_mesh_2d)"
        )
    return rules


def make_mesh_2d(data: int = 1, model: int = 1, *, devices=None) -> Mesh:
    """The canonical 2-D ``(data, model)`` mesh every DONN consumer uses.

    ``data`` x ``model`` devices (defaults: 1x1, valid on a single host
    device): batch/population shard over ``data``, field rows over
    ``model`` (pencil FFT).  Replaces the ad-hoc per-call-site
    ``compat.make_mesh`` constructions — one entry point, one axis-name
    spelling, paired with the :func:`donn_rules` table.
    """
    devs = list(devices if devices is not None else jax.devices())
    need = int(data) * int(model)
    if need > len(devs):
        raise ValueError(
            f"make_mesh_2d needs {need} devices "
            f"({data} data x {model} model), have {len(devs)}"
        )
    arr = np.asarray(devs[:need], dtype=object).reshape(int(data), int(model))
    return Mesh(arr, ("data", "model"))


def spatial_rules(axis: str = "model") -> dict:
    """Row-sharded DONN spatial layout (pencil FFT inside the scan body).

    The in-scan distributed spectral hop keeps fields, TF planes and
    trainable phases sharded along H (``field_h``) over one mesh axis —
    ``repro.runtime.pencil_fft.local_spectral_pair`` transposes to/from
    the W-sharded layout internally per FFT.  ``field_w`` replicates (it
    is the locally-full axis between transposes).
    """
    return {**DEFAULT_RULES, "field_h": axis, "field_w": None}


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        if a not in mesh.shape:
            return 0  # axis not present in this mesh -> unmappable
        size *= mesh.shape[a]
    return size


def _present(mesh: Mesh, axes):
    """Filter an axis (or tuple) down to axes present in the mesh."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if axes in mesh.shape else None
    kept = tuple(a for a in axes if a in mesh.shape)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def present_axes(mesh: Mesh, axes):
    """Public form of :func:`_present` (rule axes filtered to the mesh)."""
    return _present(mesh, axes)


def _flat_axes(axes) -> tuple:
    return () if axes is None else (
        (axes,) if isinstance(axes, str) else tuple(axes)
    )


def _check_batch_field_collision(logical_axes, mesh, rules) -> None:
    """Typed error when batch and field_h resolve onto one mesh axis."""
    names = [n for n in logical_axes if n]
    if "batch" not in names or "field_h" not in names:
        return
    b = set(_flat_axes(_present(mesh, rules.get("batch"))))
    h = set(_flat_axes(_present(mesh, rules.get("field_h"))))
    if b & h:
        raise ShardingRulesError(
            f"'batch' and 'field_h' both resolve to mesh axis "
            f"{sorted(b & h)[0]!r} on {tuple(mesh.shape)}: refusing to "
            f"silently pick a winner — fix the rules table (donn_rules "
            f"gives batch->data, field_h->model)"
        )


def rules_pspec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Mapping[str, Any]] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Logical axis names -> PartitionSpec through the rules table.

    The shard_map companion of :func:`resolve_pspec`: manual-region
    in/out specs must divide exactly (shard_map checks shapes itself),
    so there is no shape/divisibility fallback here — but duplicate mesh
    -axis use across dims raises :class:`ShardingRulesError` instead of
    silently mis-sharding.  With ``mesh`` given, rule axes absent from
    the mesh drop to replicated (so one spec spelling serves 1-D and
    2-D meshes).
    """
    rules = rules or DEFAULT_RULES
    out, used = [], set()
    for name in logical_axes:
        axes = rules.get(name) if name else None
        if mesh is not None:
            axes = _present(mesh, axes)
        flat = _flat_axes(axes)
        dup = sorted(set(flat) & used)
        if dup:
            raise ShardingRulesError(
                f"mesh axis {dup[0]!r} claimed by more than one logical "
                f"axis in {tuple(logical_axes)}"
            )
        used.update(flat)
        out.append(axes if flat else None)
    return P(*out)


def dim0_pspec(axes, ndim: int) -> P:
    """PartitionSpec sharding dim 0 over ``axes``, rest replicated."""
    if not _flat_axes(axes):
        return P(*([None] * ndim))
    return P(axes, *([None] * (ndim - 1)))


def replicated_pspec(ndim: int = 0) -> P:
    return P(*([None] * ndim))


def with_leading(spec: P, lead: int = 1) -> P:
    """Shift a spec right of ``lead`` unsharded leading axes (chunk dims)."""
    return P(*((None,) * lead + tuple(spec)))


def resolve_pspec(
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Mapping[str, Any]] = None,
) -> P:
    """Map logical axes to mesh axes; drop non-divisible or duplicate uses.

    A mesh axis is consumed at most once per array (first dim wins), so
    fallback rules — e.g. kv_heads and head both mapping to "model" — give
    "shard whichever dim divides, preferring the earlier one".  The one
    pair that does NOT silently fall back is ``batch``/``field_h``: both
    resolving to one mesh axis is a rules-table bug (the 2-D layouts
    alias) and raises :class:`ShardingRulesError`.  A ``field_h`` dim not
    divisible by the model-axis extent cleanly drops to replicated like
    every other dim.
    """
    rules = rules or DEFAULT_RULES
    _check_batch_field_collision(logical_axes, mesh, rules)
    out = []
    used: set = set()
    for dim, name in zip(shape, logical_axes):
        axes = _present(mesh, rules.get(name)) if name else None
        if axes is not None:
            flat = (axes,) if isinstance(axes, str) else tuple(axes)
            if any(a in used for a in flat):
                axes = None
        size = _axis_size(mesh, axes) if axes else 1
        if axes is None or size <= 1 or dim % size != 0:
            out.append(None)  # replicate: unmapped, non-divisible, or dup
        else:
            out.append(axes)
            used.update((axes,) if isinstance(axes, str) else axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def operand_pspec(
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Mapping[str, Any]] = None,
) -> P:
    """:func:`resolve_pspec` without the trailing-None trim.

    shard_map operand specs must be full rank, but still want the
    divisibility fallback (e.g. the (L, 1, 1) int8 plane scales riding a
    row-sharded frozen stack replicate instead of erroring).
    """
    spec = tuple(resolve_pspec(shape, logical_axes, mesh, rules))
    return P(*(spec + (None,) * (len(tuple(shape)) - len(spec))))


def spec_sharding(spec: ParamSpec, mesh: Mesh, rules=None) -> NamedSharding:
    axes = spec.logical_axes or (None,) * len(spec.shape)
    return NamedSharding(mesh, resolve_pspec(spec.shape, axes, mesh, rules))


def tree_shardings(specs, mesh: Mesh, rules=None):
    return jax.tree.map(
        lambda s: spec_sharding(s, mesh, rules), specs, is_leaf=is_spec
    )


def tree_pspecs(specs, mesh: Mesh, rules=None):
    return jax.tree.map(
        lambda s: resolve_pspec(
            s.shape, s.logical_axes or (None,) * len(s.shape), mesh, rules
        ),
        specs,
        is_leaf=is_spec,
    )


def batch_sharding(mesh: Mesh, ndim: int, rules=None,
                   batch_size: Optional[int] = None) -> NamedSharding:
    """Shard dim 0 (global batch) over the DP axes; rest replicated.

    If ``batch_size`` is given, axes are dropped (right-to-left) until the
    remaining product divides it (e.g. global_batch=1 -> replicated).
    """
    rules = rules or DEFAULT_RULES
    axes = _present(mesh, rules.get("batch"))
    if axes is None:
        return NamedSharding(mesh, P(*([None] * ndim)))
    flat = (axes,) if isinstance(axes, str) else tuple(axes)
    if batch_size is not None:
        while flat and batch_size % _axis_size(mesh, flat) != 0:
            flat = flat[:-1]
    if not flat:
        return NamedSharding(mesh, P(*([None] * ndim)))
    axes = flat if len(flat) > 1 else flat[0]
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def scalar_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ----------------------------------------------------------------------
# Activation sharding constraints.  Model code calls ``constrain(x, axes)``
# with logical axis names; it is a no-op unless a mesh context is active
# (set by the runtime step builders at trace time), so pure model code
# stays mesh-agnostic and works on a single device.
# ----------------------------------------------------------------------
import contextlib
import contextvars

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_active_mesh", default=None
)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules=None):
    token = _ACTIVE.set((mesh, rules or DEFAULT_RULES))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def partitioned_by_compiler() -> bool:
    """Whether the program being traced is one the compiler partitions over
    several devices: a mesh of more than one device is active
    (``activation_sharding``) and the trace is not inside a ``shard_map``
    over all of its axes.  A Pallas kernel cannot be partitioned so."""
    ctx = _ACTIVE.get()
    if ctx is None or ctx[0].size == 1:
        return False
    am = jax.sharding.get_abstract_mesh()
    return not (am.axis_names and all(t == jax.sharding.AxisType.Manual
                                      for t in am.axis_types))


def constrain(x, logical_axes: Sequence[Optional[str]],
              require: Optional[str] = None):
    """Apply a logical-axis sharding constraint if it resolves.

    - no mesh context (single-device tests): no-op;
    - nothing maps: no-op (don't force replication);
    - ``require=<name>``: apply only if that logical axis actually mapped —
      used for all-or-nothing layouts (e.g. the EP-resident MoE constraints
      are wrong when n_experts < TP degree).
    """
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = resolve_pspec(x.shape, logical_axes, mesh, rules)
    padded = tuple(spec) + (None,) * (len(logical_axes) - len(spec))
    if all(s is None for s in padded):
        return x
    if require is not None:
        idx = list(logical_axes).index(require)
        if padded[idx] is None:
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def abstract_like(specs):
    """ParamSpec tree -> ShapeDtypeStruct tree (dry-run stand-ins)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec
    )


def sharded_zeros(specs, mesh: Mesh, rules=None):
    """Materialize a zeroed, sharded pytree from specs (for real runs)."""
    def mk(s):
        sh = spec_sharding(s, mesh, rules)
        return jax.make_array_from_callback(
            s.shape, sh, lambda idx: np.zeros(
                tuple(len(range(*i.indices(d))) for i, d in zip(idx, s.shape)),
                s.dtype,
            )
        )
    return jax.tree.map(mk, specs, is_leaf=is_spec)
