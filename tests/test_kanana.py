"""The pieces kanana2-a3b adds to the program, on their own: the grouped
product under the lane grid's nested vmap, the per-task client optimizers,
and the round metrics' expert row counts.  (Program against the plain
reference: ``bench/tests/test_bench_kanana.py``.)"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import repro.core  # noqa: F401  (package import order)
from repro.launch.train import TASK_CLIENTS
from repro.models.layers import grouped_matmul
from repro.optim import adam, sgd


def _groups(key, n=2):
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (n, 10, 4))
    w = jax.random.normal(ks[1], (n, 3, 4, 5))
    sizes = jnp.asarray([[2, 3, 1], [4, 0, 5]][:n], jnp.int32)
    dy = jax.random.normal(ks[2], (n, 10, 5))
    return x, w, sizes, dy


def _plain(x, w, sizes):
    """Row by row: row r of group g times w[g]; rows past the groups 0."""
    out = np.zeros((x.shape[0], w.shape[-1]), np.float32)
    start = 0
    for g, n in enumerate(np.asarray(sizes)):
        out[start:start + n] = np.asarray(x[start:start + n]) @ np.asarray(w[g])
        start += n
    return out


def test_grouped_matmul_matches_row_by_row_under_nested_vmap_and_grad():
    x, w, sizes, dy = _groups(jax.random.key(0))
    out = jax.vmap(jax.vmap(grouped_matmul))(x[:, None], w[:, None], sizes[:, None])
    for i in range(2):
        np.testing.assert_allclose(out[i, 0], _plain(x[i], w[i], sizes[i]),
                                   rtol=1e-5, atol=1e-6)

    def f(x, w, sizes, dy):
        return jnp.sum(grouped_matmul(x, w, sizes) * dy)

    got = jax.vmap(jax.grad(f, argnums=(0, 1)))(x, w, sizes, dy)
    want = jax.vmap(jax.grad(
        lambda x, w, s, dy: jnp.sum(jax.lax.ragged_dot(x, w, s) * dy),
        argnums=(0, 1)))(x, w, sizes, dy)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # a weight shared by every lane (unbatched) batches too
    shared = jax.vmap(grouped_matmul, in_axes=(0, None, 0))(x, w[0], sizes)
    np.testing.assert_allclose(shared[1], _plain(x[1], w[0], sizes[1]), rtol=1e-5, atol=1e-6)


def test_task_client_optimizers():
    """mlm keeps Adam at 4e-5 bit for bit; kanana2-a3b trains plain SGD."""
    params = {"a": jnp.linspace(-1.0, 1.0, 7), "b": jnp.ones((2, 3))}
    grads = jax.tree.map(lambda p: jnp.sin(3.0 * p) + 0.1, params)
    for task, want in (("mlm", adam(4e-5)), ("kanana2-a3b", sgd(0.01)),
                       ("sr", sgd(0.05, momentum=0.9, weight_decay=5e-4)),
                       ("tg", sgd(0.8, momentum=0.9, weight_decay=5e-4))):
        got = TASK_CLIENTS[task]["optimizer"]()
        sg, sw = got.init(params), want.init(params)
        for _ in range(3):
            ug, sg = got.update(grads, sg, params)
            uw, sw = want.update(grads, sw, params)
            for a, b in zip(jax.tree.leaves(ug), jax.tree.leaves(uw)):
                np.testing.assert_array_equal(a, b)
    u, st = TASK_CLIENTS["kanana2-a3b"]["optimizer"]().update(grads, (), params)
    np.testing.assert_array_equal(u["a"], -0.01 * grads["a"])


def test_round_metrics_sum_the_model_counts_over_real_steps():
    from repro.fl.round import make_round_step

    def loss(p, batch):
        x = batch["x"]
        return jnp.mean((x @ p["w"]) ** 2), {"rows": jnp.float32(x.shape[0])}

    params = {"w": jnp.ones((3, 2))}
    W, P, S = 2, 2, 3
    batches = {"x": jnp.ones((W, P, S, 4, 3))}
    mask = jnp.ones((W, P, S)).at[0, 1, 2].set(0.0).at[1, 0, 1:].set(0.0)
    boundary = jnp.zeros((W, P, S)).at[:, :, -1].set(1.0)
    _, m = jax.jit(make_round_step(loss, sgd(0.1)))(params, batches, mask, boundary,
                                                     boundary)
    assert float(m.counts["rows"]) == 4 * float(mask.sum()) == 36.0
    _, m = jax.jit(make_round_step(lambda p, b: loss(p, b)[0], sgd(0.1)))(
        params, batches, mask, boundary, boundary)
    assert m.counts == {}


_LOWER = """
import hashlib, jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.models.lm import init_params, loss_and_counts
cfg = get_arch("kanana-2-30b-a3b").reduced()
p = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
b = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
f = jax.value_and_grad(lambda p, b: loss_and_counts(p, b, cfg), has_aux=True)
print(hashlib.sha1(jax.jit(f).lower(p, b).as_text(debug_info=False).encode()).hexdigest())
"""


def test_the_loss_program_does_not_depend_on_the_string_hashes():
    """The program text, and so its key in the persistent compilation cache,
    is the same in every process: the expert row counts are summed in a
    fixed order, not in a set's (hash seeds 1 and 4 gave two programs)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    texts = {subprocess.run(
        [sys.executable, "-c", _LOWER], capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": src}).stdout for seed in ("1", "4")}
    assert len(texts) == 1
