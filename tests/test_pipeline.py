"""Pipelined round execution: vectorized packing, buffer reuse, S-bucketing
bounds, compile-cache accounting, and host/device-overlap equivalence."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, make_placement, s_bucket)
from repro.core.placement import Assignment, ClientInfo, WorkerInfo
from repro.data import make_federated_dataset
from repro.data.batching import (PackBuffers, build_round_arrays,
                                 build_round_arrays_loop, plan_round)
from repro.distributed import WorkerPool
from repro.fl.round import StepCompileCache, round_shape_key
from repro.models.papertasks import make_task_model
from repro.optim import sgd


# -- vectorized packer ≡ loop packer ----------------------------------------

def _random_assignment(rng, ds, n_clients, n_workers):
    cids = rng.choice(min(ds.n_clients, 500), size=n_clients, replace=False)
    clients = [ClientInfo(cid=int(c), n_batches=ds.n_batches(int(c)),
                          n_samples=ds.n_samples(int(c))) for c in cids]
    workers = [WorkerInfo(wid=int(w))
               for w in rng.choice(64, size=n_workers, replace=False)]
    per = {w.wid: [] for w in workers}
    for c in clients:
        per[workers[rng.integers(n_workers)].wid].append(c)
    return Assignment(per_worker=per), workers


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n_clients=st.integers(1, 16),
       n_workers=st.integers(1, 4), lanes=st.integers(1, 3))
def test_vectorized_packer_bit_identical_to_loop(seed, n_clients, n_workers,
                                                 lanes):
    ds = make_federated_dataset("sr", n_clients=64, input_dim=8, batch_size=2)
    rng = np.random.default_rng(seed)
    assignment, workers = _random_assignment(rng, ds, n_clients, n_workers)
    kw = dict(lanes_per_worker=lanes, steps_cap=4, batch_size=2)
    vec = build_round_arrays(ds, assignment, workers, **kw)
    ref = build_round_arrays_loop(ds, assignment, workers, **kw)
    assert vec.n_steps == ref.n_steps
    np.testing.assert_array_equal(vec.step_mask, ref.step_mask)
    np.testing.assert_array_equal(vec.boundary, ref.boundary)
    np.testing.assert_array_equal(vec.weight, ref.weight)
    assert set(vec.batches) == set(ref.batches)
    for name in vec.batches:
        np.testing.assert_array_equal(vec.batches[name], ref.batches[name])


def test_packer_tokens_task_bit_identical():
    ds = make_federated_dataset("tg")
    rng = np.random.default_rng(3)
    assignment, workers = _random_assignment(rng, ds, 9, 3)
    kw = dict(lanes_per_worker=2, steps_cap=3, batch_size=2, seq_len=16)
    vec = build_round_arrays(ds, assignment, workers, **kw)
    ref = build_round_arrays_loop(ds, assignment, workers, **kw)
    np.testing.assert_array_equal(vec.batches["tokens"], ref.batches["tokens"])
    np.testing.assert_array_equal(vec.step_mask, ref.step_mask)


def test_round_plan_indices_cover_each_step_once():
    ds = make_federated_dataset("sr", n_clients=64, input_dim=8, batch_size=2)
    rng = np.random.default_rng(11)
    assignment, workers = _random_assignment(rng, ds, 12, 3)
    plan = plan_round(assignment, workers, lanes_per_worker=2, steps_cap=5)
    # no slot is written twice
    flat = plan.w_idx * 10_000 + plan.p_idx * 1_000 + plan.s_idx
    assert len(np.unique(flat)) == plan.n_steps_total
    # every client's steps are contiguous and batch_idx counts from 0
    assert plan.batch_idx.min() == 0
    assert plan.s_idx.max() < plan.s_real
    # one boundary per placed client, at that client's last step
    n_placed = sum(len(v) for v in assignment.per_worker.values())
    assert plan.n_clients == n_placed


def test_s_align_allocates_bucketed_no_pad_needed():
    ds = make_federated_dataset("sr", n_clients=64, input_dim=8, batch_size=2)
    rng = np.random.default_rng(5)
    assignment, workers = _random_assignment(rng, ds, 10, 2)
    arrays = build_round_arrays(ds, assignment, workers, steps_cap=9,
                                batch_size=2, s_align=s_bucket)
    assert arrays.n_steps == s_bucket(arrays.n_real_steps)
    # the bucket tail is pure masked padding
    assert arrays.step_mask[..., arrays.n_real_steps:].sum() == 0
    for v in arrays.batches.values():
        assert v.shape[2] == arrays.n_steps


def test_padded_slots_carry_a_batch_of_the_round():
    """Padded slots repeat the round's first batch, in a fresh buffer and in
    a reused one alike: never zeros (all masked for the mlm head) nor an
    earlier round's content."""
    ds = make_federated_dataset("tg")
    buf = PackBuffers(depth=1)
    for seed in (3, 4):
        assignment, workers = _random_assignment(np.random.default_rng(seed),
                                                 ds, 5, 2)
        arrays = build_round_arrays(ds, assignment, workers,
                                    lanes_per_worker=2, steps_cap=3,
                                    batch_size=2, seq_len=16,
                                    s_align=lambda s: 8, buffers=buf)
        toks, real = arrays.batches["tokens"], arrays.step_mask > 0
        assert (~real).any()
        padded = toks[~real]
        assert (padded == padded[0]).all() and padded[0].any()
        assert (toks[real] == padded[0]).all(axis=(1, 2)).any()


def test_pack_buffers_ring_reuses_and_isolates():
    ds = make_federated_dataset("sr", n_clients=64, input_dim=8, batch_size=2)
    rng = np.random.default_rng(9)
    assignment, workers = _random_assignment(rng, ds, 8, 2)
    buf = PackBuffers(depth=2)
    kw = dict(steps_cap=4, batch_size=2, s_align=s_bucket, buffers=buf)
    r1 = build_round_arrays(ds, assignment, workers, **kw)
    r2 = build_round_arrays(ds, assignment, workers, **kw)
    r3 = build_round_arrays(ds, assignment, workers, **kw)
    # depth-2 double buffering: consecutive rounds never share arrays …
    assert r1.step_mask is not r2.step_mask
    # … and the ring wraps on the third acquire
    assert r3.step_mask is r1.step_mask
    np.testing.assert_array_equal(r2.step_mask, r3.step_mask)
    np.testing.assert_array_equal(r2.weight, r3.weight)


# -- S-bucketing bound -------------------------------------------------------

def test_s_bucket_monotone_idempotent_and_bounded():
    prev = 0
    for s in range(1, 4096):
        b = s_bucket(s)
        assert b >= s                      # never truncates
        assert b >= prev                   # monotone non-decreasing
        assert s_bucket(b) == b            # buckets are fixed points
        if s > 8:
            # true worst case for base-8 {1.0, 1.5} buckets: sup of
            # bucket(s)/s is 1.5, approached at s = 8*2^k + 1, never hit.
            assert b < 1.5 * s
        prev = b
    # the sup really is approached (so the documented 1.5 is tight)
    s = 8 * 2 ** 10 + 1
    assert s_bucket(s) / s > 1.49


# -- compile cache -----------------------------------------------------------

def test_step_cache_counts_compiles_hits_evictions():
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=8,
                                   width=16, n_blocks=1)
    from repro.fl.round import make_round_step
    cache = StepCompileCache(lambda: make_round_step(loss, sgd(0.1)),
                             capacity=2)

    def arrays_for(S):
        rng = np.random.default_rng(S)
        batches = {"x": rng.normal(size=(1, 1, S, 2, 8)).astype(np.float32),
                   "y": rng.integers(0, 35, size=(1, 1, S, 2)).astype(np.int32)}
        mask = np.ones((1, 1, S), np.float32)
        boundary = np.zeros((1, 1, S), np.float32)
        boundary[..., -1] = 1.0
        return batches, mask, boundary, boundary.copy()

    # donation invalidates the params passed in (that is the point: XLA
    # updates them in place) — thread the returned params forward like the
    # engine does.
    for S, compiles, hits in [(4, 1, 0), (4, 1, 1), (6, 2, 1), (4, 2, 2)]:
        b, m, bd, w = arrays_for(S)
        params, metrics = cache(params, b, m, bd, w)
        assert np.isfinite(float(metrics.loss))
        assert cache.compiles == compiles and cache.hits == hits
    assert cache.evictions == 0
    # third distinct shape evicts the LRU entry (capacity 2) …
    b, m, bd, w = arrays_for(8)
    params, _ = cache(params, b, m, bd, w)
    assert cache.evictions == 1 and len(cache) == 2
    # … so the evicted shape recompiles when it comes back
    before = cache.compiles
    b, m, bd, w = arrays_for(6)
    params, _ = cache(params, b, m, bd, w)
    assert cache.compiles == before + 1


def test_round_shape_key_ignores_content():
    a = {"x": np.zeros((2, 1, 4, 3, 8), np.float32)}
    b = {"x": np.ones((2, 1, 4, 3, 8), np.float32)}
    m = np.zeros((2, 1, 4), np.float32)
    assert round_shape_key(a, m) == round_shape_key(b, m)
    c = {"x": np.zeros((2, 1, 6, 3, 8), np.float32)}
    assert round_shape_key(a, m) != round_shape_key(c, np.zeros((2, 1, 6),
                                                               np.float32))


# -- pipelined engine ≡ synchronous engine -----------------------------------

def _engine(pipeline_depth, placement="rr", rounds_per_ckpt=100,
            donate=True):
    ds = make_federated_dataset("sr", n_clients=64, input_dim=16,
                                batch_size=4, size_mu=2.5, size_sigma=0.8)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=16,
                                   width=32, n_blocks=2)
    return FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params,
        optimizer=sgd(0.1, momentum=0.9),
        placement=make_placement(placement),
        sampler=UniformSampler(64, 8),
        pool=WorkerPool.homogeneous(2, type_name="a40", concurrency=2),
        telemetry=SyntheticTelemetry(),
        config=EngineConfig(steps_cap=4, batch_size=4,
                            rounds_per_checkpoint=rounds_per_ckpt,
                            pipeline_depth=pipeline_depth,
                            donate_buffers=donate))


def test_pipeline_depth1_matches_depth0_losses_exactly():
    """RR placement is telemetry-independent, so depth 0 and depth 1 run
    byte-identical rounds — losses must agree bit-for-bit."""
    sync = _engine(0).run(6)
    pipe = _engine(1).run(6)
    assert [r.loss for r in sync] == [r.loss for r in pipe]
    assert [r.s_steps for r in sync] == [r.s_steps for r in pipe]
    assert [r.n_clients for r in sync] == [r.n_clients for r in pipe]


def test_pipeline_depth1_matches_depth0_losses_lb():
    """Both depths feed round u's assignment a fit on data <= u-2 (the
    pipelined refit just runs one call earlier), so LB placements — and
    therefore losses — are bit-identical too."""
    sync = _engine(0, placement="lb").run(6)
    pipe = _engine(1, placement="lb").run(6)
    assert [r.loss for r in sync] == [r.loss for r in pipe]


def test_pipeline_depth2_matches_depth0_exactly():
    """Depth 2 keeps two preps in flight, but every host mutation (sampler
    RNG, refit, telemetry draws, observe) runs in round order on the single
    producer thread — losses AND simulated telemetry must be bit-identical
    to the synchronous loop, for both telemetry-free and LB placement."""
    for placement in ("rr", "lb"):
        sync = _engine(0, placement=placement).run(6)
        deep = _engine(2, placement=placement).run(6)
        assert [r.loss for r in sync] == [r.loss for r in deep], placement
        assert [r.makespan for r in sync] == \
            [r.makespan for r in deep], placement
        assert [r.idle_time for r in sync] == \
            [r.idle_time for r in deep], placement
        assert [r.s_steps for r in sync] == [r.s_steps for r in deep]


def test_pipeline_depth2_split_runs_resume_cleanly():
    """Splitting a depth-2 run across run() calls must not change results —
    the prep queue drains at the run boundary and refills correctly."""
    for placement in ("rr", "lb"):
        whole = _engine(2, placement=placement).run(6)
        eng = _engine(2, placement=placement)
        split = eng.run(2) + eng.run(3) + eng.run(1)
        assert [r.loss for r in whole] == [r.loss for r in split], placement
        assert eng.round_idx == 6


def test_execute_failure_stops_producer():
    """A device-step failure must abort the producer too: queued preps for
    rounds that will never execute may not keep consuming sampler RNG or
    telemetry draws.  (The one prep already in flight may finish.)"""
    import threading

    eng = _engine(2, placement="lb")
    release = threading.Event()
    prepared = []
    orig_prep = eng._prepare_round
    orig_advance = eng.pool.advance_to

    def slow_advance(t):
        if t >= 1:
            # Hold the producer inside prep(1) until well after the abort
            # flag is set, so prep(2)'s guard check is deterministic.
            assert release.wait(timeout=30)
        return orig_advance(t)

    def spy_prep(t):
        prepared.append(t)
        return orig_prep(t)

    eng.pool.advance_to = slow_advance
    eng._prepare_round = spy_prep

    def boom(prep):
        raise RuntimeError("device step died")

    eng._execute = boom
    # Unblock the producer only after run() has set the abort flag (it is
    # set before the exception propagates, on the same thread).
    threading.Timer(1.0, release.set).start()
    with pytest.raises(RuntimeError, match="device step died"):
        eng.run(5)
    release.set()
    assert prepared == [0, 1]          # preps 2..4 stopped at the guard
    assert eng.round_idx == 0          # round 0 never executed
    rows = [r for m in eng.placement.models.values() for (r, _, _) in m._xs]
    assert all(r <= 1 for r in rows)   # no telemetry for unreached rounds


def test_engine_config_rejects_bad_depth_and_cache():
    with pytest.raises(ValueError, match="pipeline_depth"):
        EngineConfig(pipeline_depth=-1)
    with pytest.raises(ValueError, match="pipeline_depth"):
        EngineConfig(pipeline_depth=1.5)
    with pytest.raises(ValueError, match="device_cache_batches"):
        EngineConfig(device_cache_batches=-4)
    with pytest.raises(ValueError, match="compile_cache_size"):
        EngineConfig(compile_cache_size=0)


def test_pack_buffer_ring_sized_depth_plus_one():
    """Rounds t..t+depth are in flight together: the ring must hold
    depth+1 slot sets so the producer never rewrites a live buffer."""
    for depth in (0, 1, 2, 3):
        assert _engine(depth)._pack_buffers.depth == depth + 1


def test_pipeline_split_runs_resume_cleanly():
    """Splitting a pipelined run must not change results — including under
    LB placement, whose refit cadence crosses the run() boundary."""
    for placement in ("rr", "lb"):
        whole = _engine(1, placement=placement).run(6)
        eng = _engine(1, placement=placement)
        split = eng.run(3) + eng.run(3)
        assert [r.loss for r in whole] == [r.loss for r in split], placement
        assert eng.round_idx == 6


def test_pipeline_reports_overlap_and_recompiles():
    eng = _engine(1)
    res = eng.run(5)
    assert eng.compile_stats["compiles"] >= 1
    assert res[-1].recompiles == eng.compile_stats["compiles"]
    assert all(r.pack_time > 0 for r in res)
    # rounds after the first had their pack overlapped with execution
    assert any(r.overlap_fraction > 0 for r in res[1:])
    assert all(0.0 <= r.overlap_fraction <= 1.0 for r in res)


def test_background_prep_failure_preserves_executed_round():
    """If preparing round t+1 dies on the pack thread, round t (already
    executed on device) must still be recorded before the error surfaces —
    otherwise a retrying caller would train round t twice."""
    eng = _engine(1)
    orig = eng.sampler.sample

    def boom(t):
        if t == 2:
            raise RuntimeError("sampler died")
        return orig(t)

    eng.sampler.sample = boom
    with pytest.raises(RuntimeError, match="sampler died"):
        eng.run(4)
    assert eng.round_idx == 2
    assert len(eng.history) == 2
    assert all(np.isfinite(r.loss) for r in eng.history)


def test_deep_prep_failure_books_all_executed_rounds():
    """Depth 2: the failing prep (round 2) is two ahead when submitted; the
    rounds that DID execute (0 and 1) must both land in history, later
    queued preps are cancelled, and the error still surfaces."""
    eng = _engine(2)
    orig = eng.sampler.sample

    def boom(t):
        if t == 2:
            raise RuntimeError("sampler died")
        return orig(t)

    eng.sampler.sample = boom
    with pytest.raises(RuntimeError, match="sampler died"):
        eng.run(5)
    assert eng.round_idx == 2
    assert len(eng.history) == 2
    assert [r.round_idx for r in eng.history] == [0, 1]
    assert all(np.isfinite(r.loss) for r in eng.history)


def test_engine_defaults_not_shared_across_instances():
    """Mutable-default regression: two engines must not share strategy or
    config dataclass instances."""
    e1, e2 = _engine(0), _engine(0)
    assert e1.cfg is not e2.cfg
    assert e1.strategy is not e2.strategy
    e3 = FederatedEngine(
        dataset=e1.dataset, loss_fn=e1.loss_fn, init_params=e1.params,
        optimizer=e1.optimizer, placement=make_placement("rr"),
        sampler=UniformSampler(64, 4), pool=WorkerPool.homogeneous(1))
    e4 = FederatedEngine(
        dataset=e1.dataset, loss_fn=e1.loss_fn, init_params=e1.params,
        optimizer=e1.optimizer, placement=make_placement("rr"),
        sampler=UniformSampler(64, 4), pool=WorkerPool.homogeneous(1))
    assert e3.cfg is not e4.cfg
    assert e3.strategy is not e4.strategy
    e3.cfg.steps_cap = 2
    assert e4.cfg.steps_cap != 2


def test_no_post_pack_padding_copy_in_run_round():
    """The engine must consume packer output as-is: arrays leave the packer
    already at the bucketed size (acceptance: zero post-pack full copies)."""
    eng = _engine(0)
    r = eng.run_round()
    arrays = eng.history and eng._pack_buffers  # buffers exist and are used
    assert arrays is not None
    assert r.s_steps == s_bucket(r.s_steps) or r.s_steps == \
        eng.cfg.s_bucket_base
    import inspect
    src = inspect.getsource(type(eng).run_round) + inspect.getsource(
        type(eng)._prepare_round)
    assert "np.pad" not in src
