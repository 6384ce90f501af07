"""Property-based invariant tests (hypothesis; deterministic stub fallback).

Three families of generated checks replace hand-enumerated grids (the old
matrices in tests/test_mesh.py / tests/test_hosts.py stay as the shrunk
regression corpus):

* **Bit-identity matrix** — scheduling knobs (pipeline_depth, bucket_mode,
  mesh shard count under the flat combine, host count under the pairwise
  combine) must never change losses.  Each drawn config is normalised to a
  valid combination, mapped to its *arithmetic family* (flat / tree@K /
  hosts), and compared bitwise against a memoized per-family reference.
* **Error-feedback conservation** — the compressed combine's invariant:
  ``sent + e_new == u`` exactly, per leaf, for both wire formats.  int8's
  residual is a cancellation difference of nearby floats (Sterbenz-exact),
  topk's is an exact scatter complement — both hold bitwise, and losing
  either silently degrades convergence rather than failing loudly.
* **topk_k clamp bounds** — ``1 <= k <= size``, exact integer arithmetic,
  monotone in ``frac``, and ``frac=1.0`` keeps everything.

Plus the host-hierarchy algebra: reducing aligned pow2 blocks first, then
the block results, must reproduce the flat pairwise tree exactly — under
arbitrary dead-shard holes.  That lemma is WHY hosts=H is bit-identical
to hosts=1; checking it on the pure function is cheap enough to fuzz.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.compress import make_encode_step, topk_k
from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, make_placement)
from repro.data import make_federated_dataset
from repro.distributed import WorkerPool
from repro.distributed.sharding import HostShardMap
from repro.fl.round import make_payload_decode_step
from repro.models.papertasks import make_task_model
from repro.optim import sgd


def _engine(mesh=0, depth=1, bucket="round", combine="flat",
            compress="none", hosts=0):
    ds = make_federated_dataset("sr", n_clients=64, input_dim=16,
                                batch_size=4, size_mu=2.5, size_sigma=0.8)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=16,
                                   width=32, n_blocks=2)
    return FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params,
        optimizer=sgd(0.1, momentum=0.9),
        placement=make_placement("lb"), sampler=UniformSampler(64, 8),
        pool=WorkerPool.homogeneous(4, type_name="a40", concurrency=2),
        telemetry=SyntheticTelemetry(),
        config=EngineConfig(steps_cap=4, batch_size=4, lanes_per_worker=2,
                            pipeline_depth=depth, mesh_workers=mesh,
                            bucket_mode=bucket, combine_mode=combine,
                            combine_compress=compress, hosts=hosts))


# -- bit-identity matrix ------------------------------------------------------

_REFERENCE: dict = {}      # family key -> [(loss, makespan), ...]


def _signature(results):
    return [(r.loss, r.makespan) for r in results]


def _normalise(depth, bucket, mesh, compress, hosts):
    """Map an arbitrary draw onto a valid engine config + its arithmetic
    family.  Scheduling knobs (depth, bucket — and mesh under flat, hosts
    under the pairwise tree) are the dimensions bit-identity quantifies
    over; everything else picks the family."""
    if hosts >= 1:
        mesh, combine = 4, "tree"
        family = ("hosts", compress)
        ref = dict(mesh=4, combine="tree", compress=compress, hosts=1)
    elif compress != "none":
        mesh, combine = (mesh or 2), "tree"
        family = ("tree", mesh, compress)
        ref = dict(mesh=mesh, combine="tree", compress=compress)
    else:
        combine = "flat"
        family = ("flat",)
        ref = dict(mesh=0)
        if bucket == "worker":
            # Per-worker S buckets exist only on the mesh path.
            mesh = max(mesh, 2)
    cfg = dict(depth=depth, bucket=bucket, mesh=mesh, combine=combine,
               compress=compress, hosts=hosts)
    return cfg, family, ref


@settings(max_examples=8, deadline=None)
@given(depth=st.sampled_from([0, 1, 2]),
       bucket=st.sampled_from(["round", "worker"]),
       mesh=st.sampled_from([0, 2, 4]),
       compress=st.sampled_from(["none", "int8", "topk"]),
       hosts=st.sampled_from([0, 1, 2]))
def test_losses_bit_identical_within_arithmetic_family(depth, bucket, mesh,
                                                       compress, hosts):
    cfg, family, ref = _normalise(depth, bucket, mesh, compress, hosts)
    if family not in _REFERENCE:
        _REFERENCE[family] = _signature(_engine(**ref).run(3))
    got = _signature(_engine(**cfg).run(3))
    assert got == _REFERENCE[family], (cfg, family)


# -- error-feedback conservation ---------------------------------------------

def _rand_tree(rng, scale):
    def leaf(shape):
        return jnp.asarray(rng.standard_normal(shape) * scale,
                           dtype=jnp.float32)
    return {"w": leaf((6, 5)), "b": leaf((7,))}


def _dense_sent(mode, payload, like):
    """Reconstruct exactly what the wire carries, as a dense f32 tree —
    the same arithmetic the fused dequant-merge applies."""
    if mode == "int8":
        q, scales = payload
        return jax.tree.map(
            lambda qq, s: qq.astype(jnp.float32) * s, q, scales)
    out = {}
    for k, (idx, vals) in payload.items():
        flat = jnp.zeros(like[k].size, jnp.float32).at[idx].set(vals)
        out[k] = flat.reshape(like[k].shape)
    return out


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000),
       scale=st.sampled_from([1e-3, 1.0, 50.0]),
       mode=st.sampled_from(["int8", "topk"]),
       frac=st.sampled_from([0.01, 0.1, 0.5, 1.0]))
def test_error_feedback_conserves_update_exactly(seed, scale, mode, frac):
    rng = np.random.default_rng(seed)
    g = _rand_tree(rng, scale)
    theta = _rand_tree(rng, scale)
    residual = _rand_tree(rng, scale * 0.1)
    encode = make_encode_step(mode, frac)
    payload, e_new = encode(g, theta, residual)
    u = jax.tree.map(lambda t, gg, e: t - gg + e, theta, g, residual)
    sent = _dense_sent(mode, payload, g)
    for k in u:
        np.testing.assert_array_equal(
            np.asarray(sent[k] + e_new[k]), np.asarray(u[k]),
            err_msg=f"mode={mode} frac={frac} leaf={k}")


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), frac=st.sampled_from([0.05, 0.25]))
def test_decode_step_matches_dense_reconstruction(seed, frac):
    """The host-hierarchy decode (g + sent) must agree with the manual
    dense reconstruction — drift here would silently break the compressed
    hosts=H bit-identity."""
    rng = np.random.default_rng(seed)
    g = _rand_tree(rng, 1.0)
    theta = _rand_tree(rng, 1.0)
    zero = jax.tree.map(jnp.zeros_like, g)
    for mode in ("int8", "topk"):
        payload, _ = make_encode_step(mode, frac)(g, theta, zero)
        got = make_payload_decode_step(mode)(g, payload)
        want = jax.tree.map(lambda gg, s: gg + s, g,
                            _dense_sent(mode, payload, g))
        for k in g:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=mode)


# -- topk_k clamp bounds ------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(size=st.integers(1, 1 << 20),
       frac=st.floats(min_value=1e-6, max_value=1.0))
def test_topk_k_clamped_and_monotone(size, frac):
    k = topk_k(size, frac)
    assert 1 <= k <= size
    assert topk_k(size, 1.0) == size
    if frac < 0.5:
        assert k <= topk_k(size, min(1.0, frac * 2)), (size, frac)


# -- host-block pairwise algebra ---------------------------------------------

@settings(max_examples=30, deadline=None)
@given(log_block=st.integers(0, 3), hosts=st.integers(1, 4),
       holes=st.integers(0, 2 ** 16 - 1))
def test_blocked_pairwise_reduce_equals_flat(log_block, hosts, holes):
    block = 2 ** log_block
    n = block * hosts
    merge = lambda a, b: ("+", a, b)          # records the exact tree shape
    slots = [None if (holes >> i) & 1 else f"s{i}" for i in range(n)]
    flat = HostShardMap.pairwise_reduce(list(slots), merge)
    per_host = [HostShardMap.pairwise_reduce(slots[h * block:(h + 1) * block],
                                             merge)
                for h in range(hosts)]
    assert HostShardMap.pairwise_reduce(per_host, merge) == flat
