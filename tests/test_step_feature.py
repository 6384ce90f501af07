"""The placement feature is the number of steps a client trains this round:
its batch count under ``steps_cap``.  LB placement, the telemetry rows and
``plan_round`` all see that count; the FedAvg weight stays the sample
count, and where no client exceeds the cap nothing changes."""

import jax
import pytest

from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, make_placement)
from repro.core.placement import ClientInfo
from repro.data import make_federated_dataset
from repro.distributed import WorkerPool
from repro.models.papertasks import make_task_model
from repro.obs import make_observability
from repro.optim import sgd


def _engine(*, steps_cap, workers=2, cohort=8, lanes=2, obs=None,
            **data_kw):
    data = dict(n_clients=64, input_dim=16, batch_size=4, size_mu=2.5,
                size_sigma=0.8)
    data.update(data_kw)
    ds = make_federated_dataset("sr", **data)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=16,
                                   width=32, n_blocks=2)
    return FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params,
        optimizer=sgd(0.1, momentum=0.9),
        placement=make_placement("lb"),
        sampler=UniformSampler(ds.n_clients, cohort),
        pool=WorkerPool.homogeneous(workers, type_name="a40",
                                    concurrency=lanes),
        telemetry=SyntheticTelemetry(),
        config=EngineConfig(steps_cap=steps_cap, batch_size=4,
                            lanes_per_worker=lanes,
                            rounds_per_checkpoint=100),
        obs=obs)


def _record(engine, rounds):
    """Run ``rounds`` and keep each round's cohort, assignment and result."""
    seen = []
    engine._round_observer = lambda prep, result: seen.append(
        (prep.clients,
         {w: [c.cid for c in cs]
          for w, cs in sorted(prep.assignment.per_worker.items())},
         prep.n_steps_real, result))
    engine.run(rounds)
    return seen


def _old_client_info(engine):
    """``ClientInfo`` as built before the feature was capped."""
    ds = engine.dataset
    return lambda cid: ClientInfo(cid=cid, n_batches=ds.n_batches(cid),
                                  n_samples=ds.n_samples(cid))


def test_capped_clients_are_placed_by_the_steps_they_train():
    cap = 3
    obs = make_observability(trace_rounds=256)
    engine = _engine(steps_cap=cap, obs=obs)
    ds = engine.dataset
    seen = _record(engine, 4)
    total = 0
    for clients, _, n_real, result in seen:
        for c in clients:
            assert c.n_batches == min(ds.n_batches(c.cid), cap)
            assert c.weight == ds.n_samples(c.cid)
        above = sum(1 for c in clients if ds.n_batches(c.cid) > cap)
        assert result.capped_clients == above
        assert n_real == sum(c.n_batches for c in clients)
        total += above
    assert total > 0, "the draw has no client above the cap"
    counts = [e[3] for e in obs.tracer.snapshot()
              if e[0] == "C" and e[1] == "capped_clients"]
    assert counts == [float(r.capped_clients) for *_, r in seen]


@pytest.mark.parametrize("steps_cap", [None, 400])
def test_no_client_above_the_cap_places_as_before(steps_cap):
    """Under a cap no sampled client reaches, or with no cap, the cohort's
    ``ClientInfo`` is the batch count itself: assignments, losses and
    stream lengths are bit-identical to the uncapped feature."""
    new = _engine(steps_cap=steps_cap)
    old = _engine(steps_cap=steps_cap)
    old._client_info = _old_client_info(old)
    a, b = _record(new, 5), _record(old, 5)
    ds = new.dataset
    for (clients, assign_a, _, ra), (_, assign_b, _, rb) in zip(a, b):
        if steps_cap is not None:
            assert max(ds.n_batches(c.cid) for c in clients) <= steps_cap
        assert assign_a == assign_b
        assert ra.loss == rb.loss
        assert ra.s_steps == rb.s_steps
        assert ra.makespan == rb.makespan
        assert ra.capped_clients == rb.capped_clients == 0


def test_zipf_cohort_packs_lanes_by_capped_steps():
    """A homogeneous 4-worker x 2-lane LB engine on Zipf-sized clients,
    many above the cap: placed by their capped steps, the lanes of rounds
    3-20 are mostly real work.  Placed by the uncapped batch count, a
    worker that draws one large client trains 8 steps and idles while the
    others set the round's stream length (about half the scan padded)."""
    engine = _engine(steps_cap=8, workers=4, cohort=32, n_clients=2000,
                     size_dist="zipf", zipf_a=1.35, size_max=60_000)
    seen = _record(engine, 21)[3:]
    padded = sum(r.padded_steps for *_, r in seen)
    real = sum(n for _, _, n, _ in seen)
    assert sum(r.capped_clients for *_, r in seen) >= len(seen)
    assert padded / (padded + real) < 0.30
