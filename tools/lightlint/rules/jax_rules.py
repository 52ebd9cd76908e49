"""JAX-correctness rules (LR101-LR106).

Each rule codifies a hazard this codebase has actually hit: a config
field missing from a cache key, a donated buffer read after donation, a
host sync inside a compiled region, jit re-construction in loops, model
builds / captured device arrays inside loss closures, and bf16
arithmetic without an f32 accumulator.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from lightlint.core import ERROR, FileContext, Finding, Project, Rule


def dotted(node) -> Optional[str]:
    """'jax.lax.scan' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted(node.func)


def _walk_no_defs(node):
    """Walk an AST without descending into nested function definitions."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(n))


_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit",
              "jax.experimental.pjit.pjit"}


# --------------------------------------------------------------------------
# LR101 — cache-key completeness
# --------------------------------------------------------------------------

CONFIG_REL = "src/repro/core/config.py"
MODELS_REL = "src/repro/core/models.py"
PROPAGATION_REL = "src/repro/core/propagation.py"

# config methods whose call covers a known field subset (the method body
# reads them; tracked here so attribute-level consumption stays local)
_METHOD_COVER = {
    "gap_distances": {"distance", "distances", "depth", "layers"},
    "resolved_layers": {"distance", "distances", "depth", "layers",
                        "approximation", "codesign", "device_levels",
                        "response_gamma", "n", "pixel_size"},
}

# cosmetic, explicitly non-identifying (config_static_key pops it)
_EXEMPT_FIELDS = {"name"}

_KEY_FUNCTIONS = ("config_static_key", "model_cache_key", "plan_cache_key")


def _dataclass_fields(tree: ast.AST, class_name: str) -> List[Tuple[str, int]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            return [
                (stmt.target.id, stmt.lineno)
                for stmt in node.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            ]
    return []


class _KeyFnConsumption:
    """Fields a cache-key function consumes from its config parameter."""

    def __init__(self, fn: ast.FunctionDef):
        self.fn = fn
        self.full = False  # asdict/__dict__: every field consumed
        self.attrs: Set[str] = set()
        self.layer_attrs: Set[str] = set()  # attrs on `for l in cfg.layers`
        self.delegates: Set[str] = set()  # other key fns called on the param
        if not fn.args.args:
            return
        param = fn.args.args[0].arg
        layer_vars: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name) and node.value.id == param:
                self.attrs.add(node.attr)
            if isinstance(node, ast.Call):
                name = call_name(node) or ""
                tail = name.split(".")[-1]
                arg_is_param = any(
                    isinstance(a, ast.Name) and a.id == param
                    for a in node.args
                )
                if tail == "asdict" and arg_is_param:
                    self.full = True
                if tail in _KEY_FUNCTIONS and arg_is_param:
                    self.delegates.add(tail)
            if isinstance(node, ast.Attribute) and node.attr == "__dict__":
                self.full = True
            # `for l in cfg.layers` / comprehensions over cfg.layers
            target_iter = None
            if isinstance(node, (ast.For, ast.AsyncFor)):
                target_iter = (node.target, node.iter)
            elif isinstance(node, ast.comprehension):
                target_iter = (node.target, node.iter)
            if target_iter is not None:
                tgt, it = target_iter
                if (isinstance(it, ast.Attribute)
                        and isinstance(it.value, ast.Name)
                        and it.value.id == param and it.attr == "layers"
                        and isinstance(tgt, ast.Name)):
                    layer_vars.add(tgt.id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name) and node.value.id in layer_vars:
                self.layer_attrs.add(node.attr)

    def config_fields(self) -> Set[str]:
        out = set(self.attrs)
        for m, cover in _METHOD_COVER.items():
            if m in self.attrs:
                out |= cover
        return out


class CacheKeyCompleteness(Rule):
    """LR101: every DONNConfig/LayerSpec field must feed a cache key.

    A field consumed by none of ``config_static_key`` /
    ``model_cache_key`` / ``plan_cache_key`` means two configs differing
    only in that field share cache entries — the stale-plan/stale-
    executable hazard the runtime guard test in tests/test_hetero.py
    checks dynamically; this rule pins it statically.
    """

    rule_id = "LR101"
    title = "cache-key completeness"
    severity = ERROR

    def finalize(self, project: Project) -> Iterable[Finding]:
        cfg_tree = project.tree_for(CONFIG_REL)
        models_tree = project.tree_for(MODELS_REL)
        prop_tree = project.tree_for(PROPAGATION_REL)
        if cfg_tree is None or (models_tree is None and prop_tree is None):
            return []
        donn_fields = _dataclass_fields(cfg_tree, "DONNConfig")
        layer_fields = _dataclass_fields(cfg_tree, "LayerSpec")
        if not donn_fields:
            return []
        cons: Dict[str, _KeyFnConsumption] = {}
        for tree in (models_tree, prop_tree):
            if tree is None:
                continue
            for node in ast.walk(tree):
                if (isinstance(node, ast.FunctionDef)
                        and node.name in _KEY_FUNCTIONS):
                    cons[node.name] = _KeyFnConsumption(node)
        if not cons:
            return []
        # resolve one level of delegation (model_cache_key ->
        # config_static_key)
        for c in cons.values():
            for d in c.delegates:
                if d in cons:
                    c.full = c.full or cons[d].full
                    c.attrs |= cons[d].attrs
                    c.layer_attrs |= cons[d].layer_attrs
        full = any(c.full for c in cons.values())
        consumed: Set[str] = set()
        layer_consumed: Set[str] = set()
        for c in cons.values():
            consumed |= c.config_fields()
            layer_consumed |= c.layer_attrs
        out = []
        for field, line in donn_fields:
            if field in _EXEMPT_FIELDS or full or field in consumed:
                continue
            out.append(Finding(
                CONFIG_REL, line, self.rule_id, self.severity,
                f"DONNConfig.{field} is not consumed by any cache-key "
                f"function ({'/'.join(sorted(cons))}): configs differing "
                f"only in this field would share plan/executable cache "
                f"entries"))
        if layer_fields and cons.get("plan_cache_key") is not None:
            plan = cons["plan_cache_key"]
            for field, line in layer_fields:
                if full or plan.full or field in layer_consumed:
                    continue
                out.append(Finding(
                    CONFIG_REL, line, self.rule_id, self.severity,
                    f"LayerSpec.{field} is not consumed by plan_cache_key's "
                    f"per-layer tuple: heterogeneous stacks differing only "
                    f"in this field would share a plan"))
        return out


# --------------------------------------------------------------------------
# LR102 — donation aliasing
# --------------------------------------------------------------------------

def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Donated arg positions of a cached_executable/jit call, else None."""
    name = call_name(call) or ""
    tail = name.split(".")[-1]
    if tail not in {"cached_executable", "jit", "pjit"}:
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            vals = []
            for e in v.elts:
                if not (isinstance(e, ast.Constant)
                        and isinstance(e.value, int)):
                    return None  # non-literal: cannot track
                vals.append(e.value)
            return tuple(vals)
        return None  # variable donate_argnums: cannot track
    return None


class DonationAliasing(Rule):
    """LR102: reading a buffer after it was donated to a compiled call.

    ``donate_argnums`` hands the argument's device buffer to XLA; the
    old array is invalid afterwards.  The safe idiom rebinds the name
    from the call's result (``params, opt = step(params, opt, ...)``) or
    copies first (``params = jax.tree.map(jnp.array, params)``).
    """

    rule_id = "LR102"
    title = "donation aliasing"
    severity = ERROR

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_fn(fn, ctx))
        return out

    def _check_fn(self, fn, ctx: FileContext) -> List[Finding]:
        donators: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                pos = _donate_positions(node.value)
                if pos:
                    donators[node.targets[0].id] = pos
        if not donators:
            return []
        loads: Dict[str, List[int]] = {}
        stores: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                (loads if isinstance(node.ctx, ast.Load)
                 else stores).setdefault(node.id, []).append(node.lineno)
        loops = [(n.lineno, n.end_lineno or n.lineno)
                 for n in ast.walk(fn)
                 if isinstance(n, (ast.For, ast.AsyncFor, ast.While))]
        out: List[Finding] = []
        for call in ast.walk(fn):
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Name)
                    and call.func.id in donators):
                continue
            positions = donators[call.func.id]
            donated: Set[str] = set()
            if any(isinstance(a, ast.Starred) for a in call.args):
                # ex(*args): every name feeding the call is possibly donated
                for a in call.args:
                    for n in ast.walk(a):
                        if isinstance(n, ast.Name):
                            donated.add(n.id)
            else:
                for p in positions:
                    if p < len(call.args) and isinstance(call.args[p],
                                                         ast.Name):
                        donated.add(call.args[p].id)
            c0, c1 = call.lineno, call.end_lineno or call.lineno
            loop = next(((l0, l1) for l0, l1 in sorted(
                loops, key=lambda r: r[1] - r[0])
                if l0 <= c0 <= l1), None)
            for name in sorted(donated):
                if loop is not None:
                    l0, l1 = loop
                    if any(l0 <= s <= l1 for s in stores.get(name, ())):
                        continue  # rebound somewhere in the loop: safe
                    bad = [ln for ln in loads.get(name, ())
                           if l0 <= ln <= l1 and not (c0 <= ln <= c1)]
                else:
                    rebinds = [s for s in stores.get(name, ()) if s > c1]
                    first_rebind = min(rebinds) if rebinds else float("inf")
                    bad = [ln for ln in loads.get(name, ())
                           if c1 < ln < first_rebind]
                if bad:
                    out.append(ctx.finding(
                        self, min(bad),
                        f"'{name}' is read after being donated to "
                        f"'{call.func.id}' (line {c0}): the donated buffer "
                        f"is invalid; rebind the name from the call result "
                        f"or copy before donating"))
        return out


# --------------------------------------------------------------------------
# LR103 — host sync in hot path
# --------------------------------------------------------------------------

_HOST_SYNC_CALLS = {
    "jax.device_get": "jax.device_get forces a device->host transfer",
    "np.asarray": "np.asarray on a traced value forces a host sync",
    "np.array": "np.array on a traced value forces a host sync",
    "numpy.asarray": "numpy.asarray on a traced value forces a host sync",
    "numpy.array": "numpy.array on a traced value forces a host sync",
    "print": "print inside a compiled region syncs (or burns in) values",
}


class HostSyncInHotPath(Rule):
    """LR103: host synchronization inside compiled/scanned code.

    Hot regions: functions decorated with jit, bodies handed to
    ``jax.lax.scan``, functions compiled via ``cached_executable``, and
    their nested defs.  ``.item()``, ``float()``/``int()``,
    ``np.asarray``, ``jax.device_get`` and ``print`` there either crash
    on tracers or silently serialize the device stream.  In
    ``benchmarks/``, printing between a ``time.perf_counter()`` start
    and its read also fires (it distorts the timed region).
    """

    rule_id = "LR103"
    title = "host sync in hot path"
    severity = ERROR

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        hot_names = self._hot_function_names(tree)
        hot_fns: List[Tuple[ast.AST, Set[str]]] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name in hot_names or any(
                        self._is_jit_decorator(d) for d in node.decorator_list
                ):
                    hot_fns.append((node, self._static_args(node)))
        # lambdas passed directly to jit are hot too
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (call_name(node) or "").split(
                    ".")[-1] in {"jit", "pjit"}:
                for a in node.args:
                    if isinstance(a, ast.Lambda):
                        hot_fns.append((a, set()))
        seen: Set[int] = set()
        for fn, statics in hot_fns:
            for f in self._check_hot_body(fn, ctx, statics):
                key = (f.line, hash(f.message))
                if key not in seen:
                    seen.add(key)
                    out.append(f)
        if ctx.rel.replace("\\", "/").startswith("benchmarks/"):
            out.extend(self._check_timed_regions(tree, ctx))
        return out

    @staticmethod
    def _is_jit_decorator(dec) -> bool:
        if dotted(dec) in _JIT_NAMES:
            return True
        if isinstance(dec, ast.Call):
            if dotted(dec.func) in _JIT_NAMES:
                return True
            if (dotted(dec.func) or "").split(".")[-1] == "partial":
                return bool(dec.args) and dotted(dec.args[0]) in _JIT_NAMES
        return False

    @staticmethod
    def _hot_function_names(tree) -> Set[str]:
        hot: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = (call_name(node) or "")
            tail = name.split(".")[-1]
            if name.endswith("lax.scan") and node.args and isinstance(
                    node.args[0], ast.Name):
                hot.add(node.args[0].id)
            elif tail == "cached_executable" and len(node.args) >= 2 and \
                    isinstance(node.args[1], ast.Name):
                hot.add(node.args[1].id)
            elif tail in {"jit", "pjit"} and node.args and isinstance(
                    node.args[0], ast.Name):
                hot.add(node.args[0].id)
            elif tail in {"checkpoint", "remat"} and node.args and isinstance(
                    node.args[0], ast.Name):
                hot.add(node.args[0].id)
        return hot

    @staticmethod
    def _static_args(fn) -> Set[str]:
        """Arg names marked static in a jit decorator (trace-time values)."""
        statics: Set[str] = set()
        arg_names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            kws = list(dec.keywords)
            # partial(jax.jit, static_argnames=...) carries the kwargs too
            for kw in kws:
                if kw.arg == "static_argnames":
                    v = kw.value
                    elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) \
                        else [v]
                    statics |= {e.value for e in elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)}
                elif kw.arg == "static_argnums":
                    v = kw.value
                    elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) \
                        else [v]
                    for e in elts:
                        if isinstance(e, ast.Constant) and isinstance(
                                e.value, int) and e.value < len(arg_names):
                            statics.add(arg_names[e.value])
        return statics

    def _check_hot_body(self, fn, ctx: FileContext,
                        statics: Set[str] = frozenset()) -> List[Finding]:
        out: List[Finding] = []
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        nodes = []
        for stmt in body:
            nodes.append(stmt)
            nodes.extend(ast.walk(stmt))
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                out.append(ctx.finding(
                    self, node, ".item() inside a compiled region blocks on "
                    "the device stream; return the array and sync outside"))
            elif name in _HOST_SYNC_CALLS:
                out.append(ctx.finding(
                    self, node, f"{_HOST_SYNC_CALLS[name]} inside a "
                    f"compiled region; hoist it out of the hot path"))
            elif name in {"float", "int"} and node.args and not isinstance(
                    node.args[0], ast.Constant) and not (
                    isinstance(node.args[0], ast.Name)
                    and node.args[0].id in statics):
                out.append(ctx.finding(
                    self, node, f"{name}() on a traced value inside a "
                    f"compiled region raises ConcretizationTypeError (or "
                    f"silently burns in a trace-time constant)"))
        return out

    def _check_timed_regions(self, tree, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            starts: List[Tuple[str, int]] = []
            for node in _walk_no_defs(fn):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and isinstance(node.value, ast.Call)
                        and (call_name(node.value) or "") in
                        {"time.perf_counter", "time.monotonic"}):
                    starts.append((node.targets[0].id, node.lineno))
            if not starts:
                continue
            loads: Dict[str, List[int]] = {}
            prints: List[int] = []
            for node in _walk_no_defs(fn):
                if isinstance(node, ast.Name) and isinstance(
                        node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append(node.lineno)
                if isinstance(node, ast.Call) and call_name(node) == "print":
                    prints.append(node.lineno)
            for var, line in starts:
                later = [ln for ln in loads.get(var, ()) if ln > line]
                if not later:
                    continue
                end = min(later)
                for p in prints:
                    if line < p < end:
                        out.append(ctx.finding(
                            self, p, f"print inside the timed region "
                            f"started by '{var}' at line {line} distorts "
                            f"the measurement; move it past the stop "
                            f"read"))
        return out


# --------------------------------------------------------------------------
# LR104 — jit constructed inside a loop
# --------------------------------------------------------------------------

class JitInLoop(Rule):
    """LR104: ``jax.jit(...)`` evaluated per loop iteration.

    Each evaluation creates a fresh jit wrapper with an empty compile
    cache keyed by the (often fresh) closure — every iteration retraces
    and recompiles.  Hoist the jit out of the loop or route through
    ``repro.core.propagation.cached_executable`` (process-wide cache
    keyed by config statics + avals).
    """

    rule_id = "LR104"
    title = "jit in loop"
    severity = ERROR

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        seen: Set[int] = set()
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            for stmt in loop.body + loop.orelse:
                for node in [stmt, *_walk_no_defs(stmt)]:
                    if (isinstance(node, ast.Call)
                            and call_name(node) in _JIT_NAMES
                            and id(node) not in seen):
                        seen.add(id(node))
                        out.append(ctx.finding(
                            self, node,
                            "jax.jit constructed inside a loop retraces and "
                            "recompiles every iteration; hoist it out of "
                            "the loop or route through cached_executable"))
        return out


# --------------------------------------------------------------------------
# LR105 — retrace hazards from closures
# --------------------------------------------------------------------------

_TRACE_ENTRY_TAILS = {"jit", "pjit", "grad", "value_and_grad",
                      "cached_executable"}


class ClosureRetraceHazard(Rule):
    """LR105: model builds / captured device arrays inside closures.

    The bug PR 2 fixed by hand in ``runtime/donn_steps``: a loss closure
    that (re)builds a model — or captures a freshly created ``jnp``
    array — defeats jit caching, because each call produces a new
    closure identity and retraces.  Build through ``cached_model`` /
    ``cached_apply`` and pass arrays as arguments instead.
    """

    rule_id = "LR105"
    title = "closure retrace hazard"
    severity = ERROR

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        fns = [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        # (a) build_model inside a nested def (a closure): every call of
        # the closure rebuilds layers/plans and retraces
        for outer in fns:
            for inner in ast.walk(outer):
                if inner is outer or not isinstance(
                        inner, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                for node in ast.walk(inner):
                    if isinstance(node, ast.Call) and (
                            call_name(node) or "").split(".")[-1] == \
                            "build_model":
                        out.append(ctx.finding(
                            self, node,
                            "build_model inside a closure rebuilds the "
                            "model (plans, TF planes) on every call and "
                            "retraces; use cached_model/cached_apply"))
        # (b) nested def passed to jit/grad capturing a jnp array bound
        # in the enclosing function
        for outer in fns:
            jnp_bindings: Dict[str, int] = {}
            for node in _walk_no_defs(outer):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and isinstance(node.value, ast.Call)
                        and (call_name(node.value) or "") in
                        {"jnp.array", "jnp.asarray", "jax.numpy.array",
                         "jax.numpy.asarray"}):
                    jnp_bindings[node.targets[0].id] = node.lineno
            if not jnp_bindings:
                continue
            inner_defs = {
                n.name: n for n in _walk_no_defs(outer)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            }
            traced: Set[str] = set()
            for node in _walk_no_defs(outer):
                if isinstance(node, ast.Call) and (
                        call_name(node) or "").split(".")[-1] in \
                        _TRACE_ENTRY_TAILS:
                    for a in node.args:
                        if isinstance(a, ast.Name) and a.id in inner_defs:
                            traced.add(a.id)
            for name in sorted(traced):
                inner = inner_defs[name]
                params = {a.arg for a in inner.args.args
                          + inner.args.kwonlyargs + inner.args.posonlyargs}
                assigned = {n.id for n in ast.walk(inner)
                            if isinstance(n, ast.Name)
                            and isinstance(n.ctx, ast.Store)}
                for node in ast.walk(inner):
                    if (isinstance(node, ast.Name)
                            and isinstance(node.ctx, ast.Load)
                            and node.id in jnp_bindings
                            and node.id not in params
                            and node.id not in assigned):
                        out.append(ctx.finding(
                            self, jnp_bindings[node.id],
                            f"'{node.id}' is a jnp array captured by "
                            f"closure '{name}' handed to a trace entry "
                            f"point: each fresh closure retraces; pass it "
                            f"as an argument or hoist to a module "
                            f"constant"))
                        break
        return out


# --------------------------------------------------------------------------
# LR106 — bf16 arithmetic without f32 accumulation
# --------------------------------------------------------------------------

_BF16_REDUCTIONS = {"jnp.sum", "jnp.mean", "jnp.dot", "jnp.matmul",
                    "jnp.einsum", "jnp.tensordot"}
_ACCUM_KWARGS = {"dtype", "preferred_element_type"}


def _mentions_bf16(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "bfloat16":
            return True
        if isinstance(n, ast.Constant) and n.value == "bfloat16":
            return True
    return False


class Bf16Accumulation(Rule):
    """LR106: bf16 values combined/reduced without an f32 accumulator.

    The ``tf_dtype`` contract: bf16 is a *storage* dtype for modulation
    and TF planes; arithmetic must upcast to float32 first (the
    ``a.astype(jnp.float32) * b`` idiom in ``core/propagation.py``) and
    reductions must carry an explicit f32 accumulator dtype, or half the
    mantissa silently disappears from the interference pattern.
    """

    rule_id = "LR106"
    title = "bf16 accumulation discipline"
    severity = ERROR

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        scopes = [tree] + [
            n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        seen: Set[int] = set()
        for scope in scopes:
            bf16: Set[str] = set()
            for node in _walk_no_defs(scope):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and _mentions_bf16(node.value)):
                    bf16.add(node.targets[0].id)
            if not bf16:
                continue
            for node in _walk_no_defs(scope):
                if id(node) in seen:
                    continue
                if (isinstance(node, ast.BinOp)
                        and isinstance(node.op, (ast.Add, ast.Sub, ast.Mult))
                        and isinstance(node.left, ast.Name)
                        and isinstance(node.right, ast.Name)
                        and node.left.id in bf16 and node.right.id in bf16):
                    seen.add(id(node))
                    out.append(ctx.finding(
                        self, node,
                        f"'{node.left.id}' and '{node.right.id}' are bf16; "
                        f"their product/sum stays bf16 — upcast one operand "
                        f"with .astype(jnp.float32) so accumulation runs "
                        f"in f32"))
                if (isinstance(node, ast.Call)
                        and (call_name(node) or "") in _BF16_REDUCTIONS
                        and any(isinstance(a, ast.Name) and a.id in bf16
                                for a in node.args)
                        and not any(kw.arg in _ACCUM_KWARGS
                                    for kw in node.keywords)):
                    seen.add(id(node))
                    out.append(ctx.finding(
                        self, node,
                        f"{call_name(node)} reduces a bf16 array without an "
                        f"explicit f32 accumulator; pass dtype=jnp.float32 "
                        f"(or preferred_element_type)"))
        return out


# --------------------------------------------------------------------------
# LR107 — complex promotion of split real/imag pairs in hot bodies
# --------------------------------------------------------------------------
class ComplexPromotionInHotPath(Rule):
    """LR107: ``a + 1j*b`` pair assembly inside compiled/scanned code.

    The propagation engine keeps transfer and modulation planes as split
    real/imag pairs, and the packed hop runs its matmul stages on a
    field's split pair.  Re-assembling a complex array from a split pair
    inside a scan body or jitted function (``a + 1j*b`` / ``a - 1j*b``)
    materializes an interleaved complex temporary and silently widens
    every downstream op to complex arithmetic.  Use
    ``jax.lax.complex(a, b)`` at the single boundary that genuinely needs
    a complex operand, or keep the pair split.

    Hot regions are discovered exactly like LR103: scan bodies,
    jit/pjit'd and remat'd functions, ``cached_executable`` targets, and
    their nested defs.
    """

    rule_id = "LR107"
    title = "complex pair promotion in hot path"
    severity = ERROR

    @staticmethod
    def _is_imag_mult(node) -> bool:
        """``1j * x`` / ``x * 1j`` (any complex constant coefficient)."""
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Mult)):
            return False
        return any(isinstance(s, ast.Constant) and isinstance(s.value, complex)
                   for s in (node.left, node.right))

    def visit(self, tree: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        hot_names = HostSyncInHotPath._hot_function_names(tree)
        hot_fns: List[ast.AST] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name in hot_names or any(
                        HostSyncInHotPath._is_jit_decorator(d)
                        for d in node.decorator_list
                ):
                    hot_fns.append(node)
            elif isinstance(node, ast.Call) and (
                    call_name(node) or "").split(".")[-1] in {"jit", "pjit"}:
                hot_fns.extend(a for a in node.args
                               if isinstance(a, ast.Lambda))
        seen: Set[int] = set()
        for fn in hot_fns:
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            nodes = []
            for stmt in body:
                nodes.append(stmt)
                nodes.extend(ast.walk(stmt))
            for node in nodes:
                if id(node) in seen:
                    continue
                if (isinstance(node, ast.BinOp)
                        and isinstance(node.op, (ast.Add, ast.Sub))
                        and (self._is_imag_mult(node.left)
                             or self._is_imag_mult(node.right))):
                    seen.add(id(node))
                    out.append(ctx.finding(
                        self, node,
                        "complex pair assembly (a +/- 1j*b) inside a "
                        "compiled region promotes split real/imag planes "
                        "to an interleaved complex temporary; use "
                        "jax.lax.complex(a, b) at the FFT boundary or "
                        "keep the pair split through the fused kernels"))
        return out
