"""Manual EP dispatch (shard_map) — correctness vs the auto-sharding
reference, run in a subprocess with 8 placeholder devices so the 1-device
test session is untouched."""

import os
import subprocess
import sys

SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.distributed.ep_dispatch import make_ep_dispatch
from repro.models.layers import moe_layer_3d

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(AxisType.Auto,) * 2)
b, s, D, E, F, k = 4, 16, 32, 8, 16, 2
ks = jax.random.split(jax.random.key(0), 5)
x = jax.random.normal(ks[0], (b, s, D))
rw = jax.random.normal(ks[1], (D, E)) * 0.1
gw = jax.random.normal(ks[2], (E, D, F)) * 0.1
uw = jax.random.normal(ks[3], (E, D, F)) * 0.1
dw = jax.random.normal(ks[4], (E, F, D)) * 0.1
disp = make_ep_dispatch(mesh, batch_axes=('data',), fsdp_axis='data')
cf = E / k   # droppless: local-capacity routing == global routing

def f(x, rw, gw, uw, dw):
    return disp(x, rw, gw, uw, dw, top_k=k, capacity_factor=cf)

jf = jax.jit(f, in_shardings=(
    NamedSharding(mesh, P('data', None, None)),
    NamedSharding(mesh, P(None, None)),
    NamedSharding(mesh, P('model', 'data', None)),
    NamedSharding(mesh, P('model', 'data', None)),
    NamedSharding(mesh, P('model', None, 'data'))))
out, aux = jf(x, rw, gw, uw, dw)
ref, _ = moe_layer_3d(x, rw, gw, uw, dw, top_k=k, capacity_factor=cf,
                      impl='scatter')
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err

# gradients flow through the shard_map
g = jax.grad(lambda gw: jf(x, rw, gw, uw, dw)[0].astype(jnp.float32).sum())(gw)
assert float(jnp.abs(g).sum()) > 0

# the compiled module must contain no all-to-all / token all-gather: the
# only collectives are the combine psum (+ FSDP weight gathers)
txt = jf.lower(jax.ShapeDtypeStruct(x.shape, x.dtype),
               jax.ShapeDtypeStruct(rw.shape, rw.dtype),
               jax.ShapeDtypeStruct(gw.shape, gw.dtype),
               jax.ShapeDtypeStruct(uw.shape, uw.dtype),
               jax.ShapeDtypeStruct(dw.shape, dw.dtype)).compile().as_text()
assert 'all-to-all(' not in txt
print('OK')
"""


def test_ep_dispatch_matches_reference_and_grads():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
