"""Per-architecture tests (deliverable f): every assigned arch at a
REDUCED same-family scale runs one forward + one federated train step on CPU
with correct shapes and no NaNs, and a padded step is an exact no-op.  The
FULL configs are traced through the round step and counted against their
published sizes (``jax.eval_shape``, no allocation).  The capacity-routed
MoE layer is checked against a dense top-k mixture."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  -- before repro.fl.round: the two import each other
from repro.configs import ARCH_NAMES, ARCHS
from repro.fl.round import make_round_step
from repro.models import (decode_step, forward, init_cache, init_params,
                          make_batch_spec, make_loss_fn, prefill)
from repro.models.layers import moe_layer_3d
from repro.optim import sgd

KEY = jax.random.key(0)


def _batch(cfg, b=2, s=16, seed=1):
    batch = {"tokens": jax.random.randint(jax.random.fold_in(KEY, seed),
                                          (b, s), 0, cfg.vocab_size)}
    if cfg.frontend == "patch":
        batch["patch_embed"] = jax.random.normal(
            jax.random.fold_in(KEY, seed + 1),
            (b, cfg.frontend_len, cfg.resolved_frontend_dim))
    if cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(KEY, seed + 1),
            (b, cfg.frontend_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_forward_shapes_and_finite(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(KEY, cfg)
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    logits = forward(params, batch, cfg)
    s_tot = s + (cfg.frontend_len if cfg.frontend == "patch" else 0)
    assert logits.shape == (b, s_tot, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_federated_train_step(arch):
    """One full federated round (W=2 workers, P=1, S=2 local steps) on the
    reduced config: loss finite, params updated, weights conserved."""
    cfg = ARCHS[arch].reduced()
    params = init_params(KEY, cfg)
    step = jax.jit(make_round_step(make_loss_fn(cfg), sgd(0.05, 0.9)))
    W, P, S, b, s = 2, 1, 2, 2, 16
    batch = _batch(cfg, W * P * S * b, s)
    batches = {k: v.reshape((W, P, S, b) + v.shape[1:])
               for k, v in batch.items()}
    ones = jnp.ones((W, P, S), jnp.float32)
    boundary = jnp.zeros((W, P, S)).at[:, :, -1].set(1.0)
    weight = boundary * 4.0
    new_params, metrics = step(params, batches, ones, boundary, weight)
    assert np.isfinite(float(metrics.loss))
    assert float(metrics.clients) == W * P
    assert float(metrics.total_weight) == W * P * 4.0
    # parameters must actually move
    diff = sum(float(jnp.abs(a.astype(jnp.float32)
                             - b2.astype(jnp.float32)).sum())
               for a, b2 in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params)))
    assert diff > 0


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "jamba-v0.1-52b",
                                  "whisper-base", "mamba2-2.7b",
                                  "qwen3-moe-235b-a22b"])
def test_reduced_decode_matches_forward(arch):
    """prefill + 2 decode steps == teacher-forced forward (one family per
    mixer/cache kind)."""
    from dataclasses import replace
    cfg = ARCHS[arch].reduced()
    if cfg.moe:   # droppless reference for capacity-free comparison
        cfg = replace(cfg, capacity_factor=cfg.n_experts / cfg.top_k)
    params = init_params(KEY, cfg)
    b, s = 2, 12
    batch = _batch(cfg, b, s + 2, seed=7)
    toks = batch["tokens"]
    full = forward(params, batch, cfg)
    pre = dict(batch)
    pre["tokens"] = toks[:, :s]
    off = cfg.frontend_len if cfg.frontend == "patch" else 0
    lg, cache = prefill(params, pre, cfg, max_len=off + s + 4)
    np.testing.assert_allclose(
        np.asarray(lg[:, :cfg.vocab_size]), np.asarray(full[:, off + s - 1]),
        rtol=2e-4, atol=2e-4)
    for i in range(2):
        lg, cache = decode_step(params, cache, toks[:, s + i:s + i + 1],
                                jnp.int32(off + s + i), cfg)
        np.testing.assert_allclose(
            np.asarray(lg[:, :cfg.vocab_size]),
            np.asarray(full[:, off + s + i]), rtol=3e-4, atol=3e-4)


def test_latent_attention_is_not_servable():
    """Only the MLA model lacks a serving cache; prefill, init_cache and
    decode refuse it rather than serve it wrongly."""
    assert {a for a in ARCH_NAMES if not ARCHS[a].servable} == {
        "kanana-2-30b-a3b"}
    cfg = ARCHS["kanana-2-30b-a3b"].reduced()
    assert not cfg.servable
    params = init_params(KEY, cfg)
    with pytest.raises(NotImplementedError):
        prefill(params, _batch(cfg, 1, 8), cfg, max_len=12)
    with pytest.raises(NotImplementedError):
        init_cache(cfg, 1, 12)
    with pytest.raises(NotImplementedError):
        decode_step(params, {}, jnp.zeros((1, 1), jnp.int32), jnp.int32(8),
                    cfg)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_masked_steps_are_exact_noops(arch):
    """A padded (masked) local step must leave the round result identical —
    the invariant Pollen's padding-as-idle-time mapping relies on."""
    cfg = ARCHS[arch].reduced()
    params = init_params(KEY, cfg)
    step = jax.jit(make_round_step(make_loss_fn(cfg), sgd(0.05, 0.9)))
    W, P, b, s = 1, 1, 2, 16
    batch = _batch(cfg, 2 * b, s, seed=3)
    bt = {k: v.reshape((W, P, 2, b) + v.shape[1:]) for k, v in batch.items()}
    # variant A: S=2 real steps
    ones = jnp.ones((W, P, 2))
    boundary = jnp.zeros((W, P, 2)).at[:, :, 1].set(1.0)
    weight = boundary * 2.0
    pa, _ = step(params, bt, ones, boundary, weight)
    # variant B: S=3 with a masked tail step (garbage data in the pad slot)
    bt3 = {k: jnp.concatenate(
        [v, jnp.ones_like(v[:, :, :1]) * 7], axis=2) for k, v in bt.items()}
    mask3 = jnp.concatenate([ones, jnp.zeros((W, P, 1))], axis=2)
    boundary3 = jnp.concatenate([boundary, jnp.zeros((W, P, 1))], axis=2)
    weight3 = boundary3 * 2.0
    pb, _ = step(params, bt3, mask3, boundary3, weight3)
    for a, b2 in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b2, np.float32),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_round_step_traces(arch):
    """The published config's round step traces at W = P = S = 1, b = 1,
    s = 64: the new parameters keep the init tree, shapes and dtypes, and
    the loss is a scalar."""
    cfg = ARCHS[arch]
    params = jax.eval_shape(lambda k: init_params(k, cfg), KEY)
    step = make_round_step(make_loss_fn(cfg), sgd(0.05, 0.9))
    batches = {k: jax.ShapeDtypeStruct((1, 1, 1) + shape, dtype)
               for k, (shape, dtype)
               in make_batch_spec(cfg, batch=1, seq_len=64).items()}
    mask = jax.ShapeDtypeStruct((1, 1, 1), jnp.float32)
    new_params, metrics = jax.eval_shape(step, params, batches, mask, mask,
                                         mask)
    assert jax.tree.structure(new_params) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert metrics.loss.shape == ()


def _dense_topk_moe(x3, router_w, gate, up, down, top_k):
    """Every expert on every token, mixed by the renormalised top-k softmax
    weights (zero for the experts a token did not choose)."""
    x = x3.reshape(-1, x3.shape[-1])
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / vals.sum(-1, keepdims=True)
    w = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(vals)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, gate)) \
        * jnp.einsum("td,edf->etf", x, up)
    y = jnp.einsum("etf,efd->etd", h, down)
    return jnp.einsum("te,etd->td", w, y).reshape(x3.shape)


@pytest.mark.parametrize("impl", ["einsum", "scatter"])
def test_capacity_moe_matches_dense_topk_mixture(impl):
    """With a capacity that drops no token (cf = E / k), the capacity-routed
    layer is the dense top-k mixture, in value and in every gradient."""
    b, s, D, E, F, k = 2, 8, 16, 4, 8, 2
    ks = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(ks[0], (b, s, D))
    ws = (jax.random.normal(ks[1], (D, E)),
          jax.random.normal(ks[2], (E, D, F)) * 0.3,
          jax.random.normal(ks[3], (E, D, F)) * 0.3,
          jax.random.normal(ks[4], (E, F, D)) * 0.3)
    r = jax.random.normal(ks[5], (b, s, D))

    def layer(x, *ws):
        return moe_layer_3d(x, *ws, top_k=k, capacity_factor=E / k,
                            impl=impl)[0]

    def dense(x, *ws):
        return _dense_topk_moe(x, *ws, top_k=k)

    np.testing.assert_allclose(np.asarray(layer(x, *ws)),
                               np.asarray(dense(x, *ws)),
                               rtol=1e-5, atol=1e-5)
    argnums = tuple(range(5))
    got = jax.grad(lambda *a: jnp.sum(layer(*a) * r), argnums)(x, *ws)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * r), argnums)(x, *ws)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


PUBLISHED_PARAMS = {
    "qwen3-0.6b": 0.6e9, "minitron-4b": 4.2e9, "internlm2-1.8b": 1.9e9,
    "command-r-plus-104b": 104e9, "granite-moe-3b-a800m": 3.3e9,
    "qwen3-moe-235b-a22b": 235e9, "internvl2-26b": 20e9,  # LM backbone
    "jamba-v0.1-52b": 52e9, "whisper-base": 74e6, "mamba2-2.7b": 2.7e9,
    "kanana-2-30b-a3b": 30e9,
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_param_counts_match_published_sizes(arch):
    """Full configs instantiate (eval_shape only) to the published sizes."""
    want = PUBLISHED_PARAMS[arch]
    shapes = jax.eval_shape(lambda k: init_params(k, ARCHS[arch]), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert abs(n - want) / want < 0.12, (arch, n, want)
