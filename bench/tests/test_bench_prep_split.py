"""The producer's split: ``prep_device_wait_ms`` (its wait on the chip for
the data generator's output) and ``prep_host_ms`` (its own host work) read
the engine's spans and sum to ``prep_ms``."""

import threading
from types import SimpleNamespace

import jax
import pytest

from bench import registry

SPLIT = ("prep_device_wait_ms", "prep_host_ms")


def _record(spans, n_rounds=2):
    return SimpleNamespace(rounds=[{"steps": 1}] * n_rounds, spans=spans)


def _read(name, rec):
    return registry.metric_reader(name)(rec)


SPANS = [("X", "prep.refit", 0.0, 0.005, "pollen-pack_0", 0, {"t": 5}),
         ("X", "prep.pack", 0.0, 0.100, "pollen-pack_0", 0, {"t": 5}),
         ("X", "wait.generator", 0.0, 0.080, "pollen-pack_0", 1, {"t": 5}),
         ("X", "prep.h2d", 0.0, 0.002, "pollen-pack_0", 0, {"t": 5}),
         ("X", "prep.pack", 0.0, 0.090, "pollen-pack_0", 0, {"t": 6}),
         ("X", "wait.generator", 0.0, 0.070, "pollen-pack_0", 1, {"t": 6}),
         ("X", "exec.wait_prep", 0.0, 0.004, "MainThread", 0, {"t": 6}),
         ("X", "exec.wait", 0.0, 1.000, "MainThread", 0, {"t": 5}),
         ("I", "compile", 0.0, 0.0, "MainThread", 0, None)]


def test_split_readers_on_spans():
    rec = _record(SPANS)
    assert _read("prep_device_wait_ms", rec) == pytest.approx(75.0)
    assert _read("prep_host_ms", rec) == pytest.approx(23.5)
    assert (_read("prep_device_wait_ms", rec) + _read("prep_host_ms", rec)
            == pytest.approx(_read("prep_ms", rec)))


@pytest.mark.parametrize("name", SPLIT)
def test_split_readers_find_nothing_without_the_wait_span(name):
    assert _read(name, _record(None)) is None
    assert _read(name, _record(SPANS, n_rounds=0)) is None
    # a program that books no ``wait.generator`` (one older than the span)
    older = [s for s in SPANS if s[1] != "wait.generator"]
    assert _read(name, _record(older)) is None


def test_split_on_a_traced_engine_run_sums_to_prep_ms():
    from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                            UniformSampler, make_placement)
    from repro.data import make_federated_dataset
    from repro.distributed import WorkerPool
    from repro.models.papertasks import make_task_model
    from repro.obs import make_observability
    from repro.optim import sgd

    ds = make_federated_dataset("sr", n_clients=64, input_dim=16, batch_size=4,
                                size_mu=2.5, size_sigma=0.8)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=16,
                                   width=32, n_blocks=2)
    obs = make_observability(trace_rounds=16)
    FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params, optimizer=sgd(0.1),
        placement=make_placement("lb"), sampler=UniformSampler(64, 8),
        pool=WorkerPool.homogeneous(4, type_name="a40", concurrency=2),
        telemetry=SyntheticTelemetry(),
        config=EngineConfig(steps_cap=4, batch_size=4, lanes_per_worker=2,
                            pipeline_depth=1),
        obs=obs).run(4)
    rec = _record(obs.tracer.snapshot(), n_rounds=4)
    wait, host = (_read(n, rec) for n in SPLIT)
    assert wait > 0 and host > 0
    assert wait + host == pytest.approx(_read("prep_ms", rec))
    lanes = {s[4] for s in rec.spans if s[1] == "wait.generator"}
    assert lanes == {threading.current_thread().name, "pollen-pack_0"}
