"""HLO cost analyzer: validated against XLA on loop-free programs and on
hand-computable trip-counted scans (subprocess: needs >1 device for the
collective cases)."""
import json
import textwrap

import jax
import jax.numpy as jnp

from conftest import run_subprocess
from repro.runtime.hlo_analysis import analyze, parse_hlo


class TestLoopFree:
    def test_matches_xla_cost_analysis(self):
        def f(x, w):
            return jnp.sum(jax.nn.relu(x @ w) ** 2)

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((128, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 512), jnp.float32),
        ).compile()
        xla = c.cost_analysis()
        mine = analyze(c.as_text())
        assert abs(mine.flops - xla["flops"]) / xla["flops"] < 0.05
        assert abs(mine.bytes - xla["bytes accessed"]) / xla[
            "bytes accessed"] < 0.10

    def test_dot_flops_exact(self):
        def f(x, w):
            return x @ w

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 32), jnp.float32),
        ).compile()
        mine = analyze(c.as_text())
        assert mine.dot_flops == 2 * 64 * 128 * 32


class TestTripCounting:
    def test_scan_multiplies_body(self):
        def f(w, x):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=13)
            return jnp.sum(y)

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((32, 32), jnp.float32),
            jax.ShapeDtypeStruct((8, 32), jnp.float32),
        ).compile()
        mine = analyze(c.as_text())
        assert mine.dot_flops == 13 * 2 * 8 * 32 * 32

    def test_nested_scans(self):
        def f(w, x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ w, None
                c, _ = jax.lax.scan(inner, c, None, length=3)
                return c, None
            y, _ = jax.lax.scan(outer, x, None, length=5)
            return jnp.sum(y)

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((16, 16), jnp.float32),
            jax.ShapeDtypeStruct((4, 16), jnp.float32),
        ).compile()
        mine = analyze(c.as_text())
        assert mine.dot_flops == 15 * 2 * 4 * 16 * 16

    def test_xla_does_not_trip_count(self):
        """The reason this module exists: XLA reports ~1 iteration."""
        def f(w, x):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=50)
            return jnp.sum(y)

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((32, 32), jnp.float32),
            jax.ShapeDtypeStruct((8, 32), jnp.float32),
        ).compile()
        xla = c.cost_analysis()["flops"]
        mine = analyze(c.as_text()).dot_flops
        assert mine > 10 * xla  # mine trip-counts, XLA doesn't


COLLECTIVE_SUITE = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.runtime.hlo_analysis import analyze

    mesh = make_mesh((4,), ("model",))
    results = {}

    # per scan iteration the model-sharded dot output (32,16) is gathered
    # back to the replicated carry (32,64): 7 * 32*64*4 * (g-1)/g bytes
    def f(w, x):
        def body(c, _):
            return jax.nn.relu(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return jnp.sum(y)
    c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "model")),
                                 NamedSharding(mesh, P()))).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((32, 64), jnp.float32)).compile()
    m = analyze(c.as_text())
    results["ag_bytes"] = m.collective_breakdown.get("all-gather", 0)
    results["ag_expected"] = 7 * 32 * 64 * 4 * 3 / 4

    # all-reduce: contracting-dim sharded matmul
    def g(x, w):
        return jnp.sum(x @ w)
    c2 = jax.jit(g, in_shardings=(NamedSharding(mesh, P(None, "model")),
                                  NamedSharding(mesh, P("model", None)))
                 ).lower(jax.ShapeDtypeStruct((16, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
    m2 = analyze(c2.as_text())
    results["ar_bytes"] = m2.collective_breakdown.get("all-reduce", 0)
    results["ar_expected_min"] = 16 * 32 * 4 * 2 * 3 / 4  # 2(g-1)/g * out
    print("RESULTS:" + json.dumps(results))
""")


def test_collective_byte_model():
    proc = run_subprocess(COLLECTIVE_SUITE, device_count=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")][0][8:]
    )
    assert abs(res["ag_bytes"] - res["ag_expected"]) / res["ag_expected"] < 0.1
    assert res["ar_bytes"] >= res["ar_expected_min"] * 0.9


class TestParser:
    def test_parses_tuple_types_with_index_comments(self):
        txt = (
            "%c (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {\n"
            "  %p = (s32[], f32[4,4]{1,0}, /*index=5*/f32[2,2]{1,0}) parameter(0)\n"
            "  %w = (s32[], f32[4,4]) while(%p), condition=%cond, body=%c2\n"
            "}\n"
            "ENTRY %main () -> f32[] {\n"
            "  %k = f32[] constant(0)\n"
            "}\n"
        )
        comps = parse_hlo(txt)
        ops = comps["c"].ops
        assert any(o.opcode == "while" for o in ops)
