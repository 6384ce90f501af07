"""HLO cost-walker validation: the trip-count-aware analysis must agree with
XLA's own cost_analysis on unrolled modules and correctly scale rolled scans
(XLA counts while bodies once — the bug this walker exists to fix)."""

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_hlo


def _scan_fn(unroll):
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w, unroll=unroll)
        return c
    return f


def test_walker_matches_xla_on_unrolled():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    c = jax.jit(_scan_fn(True)).lower(x, w).compile()
    xla = float(c.cost_analysis()["flops"])
    mine = analyze_hlo(c.as_text()).flops
    assert abs(mine - xla) / xla < 0.02


def test_walker_scales_scan_by_trip_count():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    rolled = jax.jit(_scan_fn(False)).lower(x, w).compile()
    unrolled = jax.jit(_scan_fn(True)).lower(x, w).compile()
    f_rolled = analyze_hlo(rolled.as_text()).flops
    f_unrolled = analyze_hlo(unrolled.as_text()).flops
    assert abs(f_rolled - f_unrolled) / f_unrolled < 0.02
    # XLA's own count misses the 10x
    assert float(rolled.cost_analysis()["flops"]) < 0.2 * f_rolled


def test_nested_scan_multiplicity():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.tanh(ci @ wi), None
            ci, _ = jax.lax.scan(inner, c, None, length=5)
            return ci, None
        c, _ = jax.lax.scan(outer, x, w)
        return c
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    mine = analyze_hlo(c.as_text()).flops
    want = 4 * 5 * 2 * 64 ** 3                # 20 matmuls
    assert abs(mine - want) / want < 0.1


def test_grad_through_scan_counted():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return jnp.sum(c)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    c = jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, w).compile()
    mine = analyze_hlo(c.as_text()).flops
    # fwd (6) + 2 dots per step in bwd (12) = >= 18 matmuls
    assert mine > 17 * 2 * 64 ** 3


def test_collectives_with_multiplicity():
    """Sharded scan emits loop collectives; the walker must scale them by
    the trip count.  Runs in a subprocess so the 4 placeholder devices do
    not leak into the 1-device test session."""
    import subprocess
    import sys
    import os
    script = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import analyze_hlo
mesh = jax.make_mesh((2, 2), ("a", "b"), axis_types=(AxisType.Auto,) * 2)
def f(x, w):
    def body(c, wi):
        return c @ wi, None
    c, _ = jax.lax.scan(body, x, w)
    return c
x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
w = jax.ShapeDtypeStruct((7, 256, 256), jnp.float32)
jf = jax.jit(f, in_shardings=(NamedSharding(mesh, P("a", "b")),
                              NamedSharding(mesh, P(None, "b", None))))
c = jf.lower(x, w).compile()
cost = analyze_hlo(c.as_text())
assert cost.collectives, "expected TP all-reduces in the loop"
assert [cc for cc in cost.collectives if cc.multiplicity >= 7], \
    "loop collectives must carry the trip multiplicity"
ici, dcn = cost.wire_bytes(pod_size=0)
assert ici > 0 and dcn == 0
print("OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
