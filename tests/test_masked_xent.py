"""The mlm task's tied head and loss on the masked rows alone
(``models.layers.masked_tied_xent``) against the full-logits masked mean:
every mask size, under the lane grid's vmap, inside a scanned
value-and-grad, and through a FedAvg round step."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (package import order)
from repro.fl.round import make_round_step
from repro.models.layers import masked_tied_xent, xent_chunk_rows
from repro.models.papertasks import TASK_MODELS, mlm_encode, mlm_init
from repro.optim import adam

T, D, V, RATE = 1024, 16, 97, 0.15
C = xent_chunk_rows(T, RATE)


def _dense(h, embed, labels, mask):
    """The full-logits masked mean (the head at every row)."""
    logp = jax.nn.log_softmax(h.reshape(-1, h.shape[-1]) @ embed.T)
    gold = jnp.take_along_axis(logp, labels.reshape(-1)[:, None], -1)[:, 0]
    m = mask.reshape(-1).astype(jnp.float32)
    return -(gold * m).sum() / jnp.maximum(m.sum(), 1.0)


def _inputs(seed, n_masked):
    ks = jax.random.split(jax.random.key(seed), 4)
    h = jax.random.normal(ks[0], (T, D))
    embed = 0.3 * jax.random.normal(ks[1], (V, D))
    labels = jax.random.randint(ks[2], (T,), 0, V)
    mask = jnp.zeros(T, bool).at[jax.random.permutation(ks[3], T)[:n_masked]].set(True)
    return h, embed, labels, mask


def _trips(n):
    return max(1, math.ceil(n / C))


def _assert_close(got, want):
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_chunk_rows_follow_the_batch_and_the_rate():
    assert C == 256
    assert xent_chunk_rows(20 * 128, 0.15) == 512     # mlm's batch
    assert xent_chunk_rows(64, 0.15) == 64            # never past the tokens
    assert xent_chunk_rows(4096, 0.0) == 128


@pytest.mark.parametrize("n_masked", [0, 1, C, C + 1, T],
                         ids=["none", "one", "C", "C+1", "all"])
def test_loss_grads_and_counts_match_the_dense_head(n_masked):
    h, embed, labels, mask = _inputs(n_masked, n_masked)
    (loss, counts), (dh, de) = jax.value_and_grad(
        lambda h, e: masked_tied_xent(h, e, labels, mask, rate=RATE),
        (0, 1), has_aux=True)(h, embed)
    want, (wh, we) = jax.value_and_grad(_dense, (0, 1))(h, embed, labels, mask)
    _assert_close(loss, want)
    _assert_close(dh, wh)
    _assert_close(de, we)
    assert float(counts["head_rows"]) == n_masked
    assert float(counts["head_buffer_rows"]) == _trips(n_masked) * C
    if n_masked == 0:
        assert float(loss) == 0.0 and not np.any(dh) and not np.any(de)


def test_lanes_with_differing_trips_under_vmap():
    lanes = [_inputs(10 + i, n) for i, n in enumerate((3, C + 1, T, 0))]
    h, embed, labels, mask = (jnp.stack(x) for x in zip(*lanes))

    def grad(fn):
        return jax.vmap(jax.value_and_grad(fn, (0, 1), has_aux=True))

    (loss, counts), (dh, de) = grad(
        lambda h, e, lab, m: masked_tied_xent(h, e, lab, m, rate=RATE))(
            h, embed, labels, mask)
    (want, _), (wh, we) = grad(
        lambda h, e, lab, m: (_dense(h, e, lab, m), 0.0))(h, embed, labels, mask)
    _assert_close(loss, want)
    _assert_close(dh, wh)
    _assert_close(de, we)
    np.testing.assert_array_equal(counts["head_rows"], [3, C + 1, T, 0])
    np.testing.assert_array_equal(counts["head_buffer_rows"],
                                  [C, 2 * C, 4 * C, C])


def test_jit_value_and_grad_inside_a_scan():
    steps = [_inputs(20 + i, n) for i, n in enumerate((150, C + 7, T))]
    h, _, labels, mask = (jnp.stack(x) for x in zip(*steps))
    embed0 = steps[0][1]

    def run(loss_fn):
        def step(e, inp):
            hk, lab, m = inp
            loss, g = jax.value_and_grad(lambda e: loss_fn(hk, e, lab, m))(e)
            return e - 0.5 * g, loss

        return jax.jit(lambda e: jax.lax.scan(step, e, (h, labels, mask)))(embed0)

    got_e, got_l = run(lambda *a: masked_tied_xent(*a, rate=RATE)[0])
    want_e, want_l = run(_dense)
    _assert_close(got_l, want_l)
    _assert_close(got_e, want_e)


def test_round_step_matches_the_dense_mlm_loss():
    """A 2 x 2 lane grid of the mlm task at a reduced width: the round's loss
    and new global model are those of the same round with the dense head,
    and its counts hold the head's rows over the real steps."""
    params = mlm_init(jax.random.key(0), vocab=64, d_model=16, n_layers=1,
                      n_heads=4, d_ff=32)
    loss_fn = TASK_MODELS["mlm"].loss_fn

    def dense_loss(p, batch):
        toks = batch["tokens"]
        mask = (toks * 2_654_435 % 100) < 15
        h = mlm_encode(p, jnp.where(mask, 3, toks))
        return _dense(h, p["embed"], toks, mask)

    W, P, S = 2, 2, 3
    toks = jax.random.randint(jax.random.key(1), (W, P, S, 4, 64), 0, 64)
    toks = toks.at[1, 1, 2].set(0)           # all masked: two trips
    step_mask = jnp.ones((W, P, S)).at[0, 1, 2].set(0.0).at[1, 0, 1:].set(0.0)
    boundary = jnp.zeros((W, P, S)).at[:, :, -1].set(1.0).at[1, 0, 0].set(1.0)
    weight = boundary * jnp.asarray([3.0, 1.0, 2.0])
    args = ({"tokens": toks}, step_mask, boundary, weight)
    got, gm = jax.jit(make_round_step(loss_fn, adam(1e-2)))(params, *args)
    want, wm = jax.jit(make_round_step(dense_loss, adam(1e-2)))(params, *args)
    _assert_close(gm.loss, wm.loss)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    masked = np.asarray((toks * 2_654_435 % 100) < 15).sum(axis=(-2, -1))
    real = np.asarray(step_mask) > 0
    n_rows = float((masked * real).sum())
    C_mlm = xent_chunk_rows(4 * 64, 0.15)
    buffer = float((np.maximum(1, np.ceil(masked / C_mlm)) * C_mlm * real).sum())
    assert float(gm.counts["head_rows"]) == n_rows
    assert float(gm.counts["head_buffer_rows"]) == buffer
    assert buffer > real.sum() * C_mlm   # the all-masked step's second trip
    assert wm.counts == {}
