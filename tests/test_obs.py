"""Observability plane: span tracer and its mirror on the profiler's
clock, round critique, flight recorder — and the tentpole invariant that
tracing NEVER perturbs training (losses and SLO fields bit-identical with
the tracer on or off, across pipeline depths and mesh shard counts,
controller live)."""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, make_placement)
from repro.data import make_federated_dataset
from repro.distributed import WorkerPool
from repro.models.papertasks import make_task_model
from repro.obs import (NULL_TRACER, FlightRecorder, MetricsRegistry, Tracer,
                       critique_round, make_observability, profiler_trace)
from repro.optim import sgd


CHECKOUT = Path(__file__).resolve().parents[1]


def _engine(mesh=0, depth=1, obs=None, drift=0.0, adapt=0,
            granularity="type", cache=0):
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
                            drift_threshold=drift, adapt_interval=adapt,
                            adapt_granularity=granularity,
                            device_cache_batches=cache),
        obs=obs)


def _signature(results):
    """Everything the tracer must not perturb: training losses, the
    simulated schedule, and the deadline-SLO fields."""
    return [(r.loss, r.makespan, r.idle_time, r.slo_p50, r.slo_p99,
             r.n_clients) for r in results]


# -- ring buffer + tracer (unit) ----------------------------------------------

def test_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=16)
    for i in range(40):
        tr.instant(f"ev{i}")
    st = tr.stats()
    assert st["spans"] == 16 and st["dropped"] == 24
    assert tr.dropped == 24
    names = [r[1] for r in tr.snapshot()]
    # overwrite-oldest: exactly the newest 16 events survive, in order
    assert names == [f"ev{i}" for i in range(24, 40)]


def test_tracer_capacity_floor_and_never_blocks():
    tr = Tracer(capacity=1)            # clamped up to the 16-slot floor
    assert tr.capacity == 16
    for i in range(100):
        tr.counter("c", float(i))
    assert tr.stats()["spans"] == 16   # degraded, never raised/blocked


def test_span_nesting_records_depth_per_thread():
    tr = Tracer()
    with tr.span("outer", t=1):
        with tr.span("inner"):
            pass
        tr.instant("mark")
    recs = {r[1]: r for r in tr.snapshot()}
    assert recs["inner"][5] == 1       # nested one level down
    assert recs["outer"][5] == 0
    assert recs["mark"][5] == 1        # emitted inside the outer span
    assert recs["outer"][4] == threading.current_thread().name
    assert recs["outer"][6] == {"t": 1}


def test_lanes_are_thread_names_and_add_span_overrides():
    tr = Tracer()

    def work():
        with tr.span("threaded"):
            pass

    th = threading.Thread(target=work, name="pollen-pack_0")
    th.start()
    th.join()
    tr.add_span("sync", 1.0, 0.5, lane="worker3", wid=3)
    lanes = {r[1]: r[4] for r in tr.snapshot()}
    assert lanes["threaded"] == "pollen-pack_0"
    assert lanes["sync"] == "worker3"


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("x"):
        NULL_TRACER.instant("y")
        NULL_TRACER.counter("z", 1.0)
        NULL_TRACER.add_span("w", 0.0, 1.0)
    assert NULL_TRACER.snapshot() == []
    assert NULL_TRACER.stats()["spans"] == 0


# -- metrics registry (unit) --------------------------------------------------

def test_metrics_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("rounds")
    m.inc("rounds", 2)
    m.gauge("loss", 0.5)
    m.gauge("loss", 0.25)
    for v in (0.0005, 0.05, 5.0, 100.0):
        m.observe("wall_s", v)
    snap = m.snapshot()
    assert snap["counters"]["rounds"] == 3
    assert snap["gauges"]["loss"] == 0.25
    h = snap["histograms"]["wall_s"]
    assert h["n"] == 4 and h["sum"] == pytest.approx(105.0505)
    assert len(h["counts"]) == len(h["edges"]) + 1
    assert sum(h["counts"]) == 4
    assert h["counts"][0] == 1          # 0.0005 below the first edge
    assert h["counts"][-1] == 1         # 100.0 above the last edge


# -- round critique (unit) ----------------------------------------------------

def test_critique_idle_fraction_and_critical_path():
    c = critique_round(round_idx=3, pack_s=0.2, overlap_s=0.2, exec_s=1.0,
                       combine_s=0.1, makespan=2.0, idle_time=1.0,
                       n_workers=4)
    assert c.idle_fraction == pytest.approx(1.0 / 8.0)
    assert c.critical_path == "exec"    # 0.9 exec beats 0.1 combine
    d = c.as_dict()
    assert d["round"] == 3 and d["critical_path"] == "exec"
    # fully exposed pack dominating everything => pack-bound round
    c2 = critique_round(round_idx=0, pack_s=3.0, overlap_s=0.0, exec_s=1.0)
    assert c2.critical_path == "pack"


# -- the tentpole invariant ---------------------------------------------------

def test_tracer_on_off_bit_identity_matrix():
    """Acceptance matrix: depths {0,1,2} x shard counts {1,2}, controller
    live (hair-trigger drift + per-worker slot climbing).  The traced run
    must be indistinguishable from the untraced run in every result field
    that feeds training, the schedule, or the SLO report."""
    kw = dict(drift=0.4, adapt=2, granularity="worker")
    for mesh in (0, 2):
        for depth in (0, 1, 2):
            base = _signature(_engine(mesh=mesh, depth=depth, **kw).run(5))
            obs = make_observability(trace_rounds=16)
            traced = _engine(mesh=mesh, depth=depth, obs=obs, **kw)
            got = _signature(traced.run(5))
            tag = f"mesh={mesh} depth={depth}"
            assert got == base, f"tracer perturbed results at {tag}"
            st = obs.tracer.stats()
            assert st["spans"] > 0, f"no spans recorded at {tag}"
            names = {r[1] for r in obs.tracer.snapshot()}
            assert "prep.pack" in names and "exec.wait" in names, names
            assert "wait.generator" in names, names
            assert ("exec.wait_prep" in names) == (depth > 0), names
            if mesh:
                assert "exec.sync" in names, names
                sync_lanes = {r[4] for r in obs.tracer.snapshot()
                              if r[1] == "exec.sync"}
                assert sync_lanes == {f"worker{w}" for w in range(4)}


def test_traced_engine_produces_producer_lane_spans():
    """Pipeline depth 2: producer spans must land on the pollen-pack lane
    and consumer spans on the main thread — the two-track trace is what
    makes the idle-gap visible in Perfetto."""
    obs = make_observability(trace_rounds=16)
    eng = _engine(depth=2, obs=obs)
    eng.run(4)
    by_lane = {}
    for r in obs.tracer.snapshot():
        if r[0] == "X":
            by_lane.setdefault(r[4], []).append(r[1])
    pack_lanes = [ln for ln in by_lane if ln.startswith("pollen-pack")]
    assert pack_lanes, by_lane.keys()
    assert "prep.pack" in by_lane[pack_lanes[0]]
    main = threading.current_thread().name
    assert "exec.wait" in by_lane[main]
    # only the pipeline's one priming prep runs on the consumer thread;
    # every steady-state prep lands on the producer lane
    assert by_lane[main].count("prep.pack") == 1
    assert by_lane[pack_lanes[0]].count("prep.pack") == 3


def _nested(inner, outer, eps=1e-9):
    """``inner`` ran inside ``outer``, one level down on the same lane."""
    return (inner[4] == outer[4] and inner[5] == outer[5] + 1
            and inner[2] >= outer[2]
            and inner[2] + inner[3] <= outer[2] + outer[3] + eps)


@pytest.mark.parametrize("mesh,cache", [(0, 0), (0, 64), (2, 0), (2, 64)],
                         ids=["fused", "device-cache", "mesh", "mesh-cache"])
def test_traced_pipeline_books_generator_and_prep_waits(mesh, cache):
    """Every packing path books its blocking fetch of the generator's
    output as ``wait.generator``, nested in that round's ``prep.pack`` on
    the producer's lane (round 0 is primed inline on the main thread); the
    consumer books each wait for the next round's prep as
    ``exec.wait_prep`` on the main thread, after the round it follows."""
    obs = make_observability(trace_rounds=16)
    _engine(mesh=mesh, depth=1, cache=cache, obs=obs).run(4)
    recs = [r for r in obs.tracer.snapshot() if r[0] == "X"]
    main = threading.current_thread().name
    packs = {r[6]["t"]: r for r in recs if r[1] == "prep.pack"}
    gens = [r for r in recs if r[1] == "wait.generator"]
    for g in gens:
        assert _nested(g, packs[g[6]["t"]]), (g, packs[g[6]["t"]])
        assert g[4] == (main if g[6]["t"] == 0 else "pollen-pack_0"), g
    rounds = sorted(g[6]["t"] for g in gens)
    if cache:
        # a round whose content the cache serves whole fetches nothing
        assert rounds[0] == 0 and set(rounds) <= {0, 1, 2, 3}, rounds
    else:
        assert rounds == [0, 1, 2, 3], rounds
    waits = {r[6]["t"]: r for r in recs if r[1] == "exec.wait"}
    preps = [r for r in recs if r[1] == "exec.wait_prep"]
    assert sorted(r[6]["t"] for r in preps) == [1, 2, 3]
    for r in preps:
        assert r[4] == main and r[5] == 0, r
        before = waits[r[6]["t"] - 1]
        assert r[2] >= before[2] + before[3] - 1e-9, (r, before)


def _host_event_names(directory) -> set:
    path = next(Path(directory).rglob("*.xplane.pb"))
    return {e.name for pl in ProfileData.from_file(str(path)).planes
            if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events}


ENGINE_SPANS = {"prep.pack", "prep.refit", "wait.generator", "exec.dispatch",
                "exec.wait", "exec.wait_prep"}


def test_profiler_trace_holds_the_engine_spans(tmp_path):
    obs = make_observability(trace_rounds=16)
    eng = _engine(depth=1, obs=obs)
    eng.run(1)                                   # compile outside the trace
    with profiler_trace(str(tmp_path)):
        eng.run(3)
    assert ENGINE_SPANS <= _host_event_names(tmp_path)


def test_untraced_engine_adds_nothing_to_the_profiler_trace(tmp_path):
    eng = _engine(depth=1)
    eng.run(1)
    with profiler_trace(str(tmp_path)):
        eng.run(2)
    assert not ENGINE_SPANS & _host_event_names(tmp_path)


def test_live_spans_reach_the_profiler_under_their_bare_name(tmp_path):
    """A span enters the profiler as its name alone, so one phase groups
    across rounds; retroactive spans, instants and counters stay in the
    ring (the profiler cannot stamp them after the fact)."""
    tr = Tracer()
    with profiler_trace(str(tmp_path)):
        with tr.span("unit.live", t=3):
            with tr.span("unit.inner"):
                pass
        tr.add_span("unit.retro", 0.0, 1.0, lane="worker0")
        tr.instant("unit.instant")
        tr.counter("unit.counter", 1.0)
    names = _host_event_names(tmp_path)
    assert {"unit.live", "unit.inner"} <= names
    assert not {n for n in names if n.startswith("unit.")} - \
        {"unit.live", "unit.inner"}
    assert {r[1] for r in tr.snapshot()} == {
        "unit.live", "unit.inner", "unit.retro", "unit.instant",
        "unit.counter"}


def test_cli_trace_out_writes_a_profiler_trace(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.pathsep.join(
                   [str(CHECKOUT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--task", "sr",
         "--rounds", "2", "--cohort", "2", "--workers", "1",
         "--concurrency", "1", "--steps-cap", "1",
         "--trace-out", str(tmp_path / "trace")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    run = next((tmp_path / "trace").rglob("*.xplane.pb")).parent
    assert (run / "perfetto_trace.json.gz").is_file()
    assert ENGINE_SPANS <= _host_event_names(run)


def test_round_results_report_idle_fraction_and_critical_path():
    res = _engine(mesh=2, depth=1).run(4)
    for r in res:
        assert 0.0 <= r.idle_fraction < 1.0
        assert r.critical_path in ("exec", "pack", "barrier", "combine")
    # deterministic: a rerun reproduces the fractions bit-for-bit
    again = _engine(mesh=2, depth=1).run(4)
    assert [r.idle_fraction for r in res] == \
        [r.idle_fraction for r in again]


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_retention_is_bounded(tmp_path):
    tr = Tracer()
    fr = FlightRecorder(tr, MetricsRegistry(), rounds=3,
                        path=str(tmp_path / "flight.json"))
    for i in range(10):
        fr.on_round(i, {"loss": float(i)})
    assert fr.dump("unit test") is not None
    doc = json.load(open(fr.path))
    assert [r["round"] for r in doc["rounds"]] == [7, 8, 9]
    assert doc["reason"] == "unit test"
    assert fr.dumps == 1 and fr.last_reason == "unit test"


def test_flight_recorder_dump_never_raises(tmp_path):
    fr = FlightRecorder(Tracer(), path=str(tmp_path / "no" / "\0bad"))
    assert fr.dump("boom") is None      # unwritable path swallowed
    assert fr.dumps == 0


def test_flight_recorder_dumps_on_injected_prep_failure(tmp_path):
    path = str(tmp_path / "flight.json")
    obs = make_observability(trace_rounds=16, flight_rounds=4,
                             flight_path=path)
    eng = _engine(depth=1, obs=obs)
    eng.run(3)

    def boom(t):
        raise RuntimeError("injected prep failure")

    eng.placement.refit = boom
    with pytest.raises(RuntimeError, match="injected prep failure"):
        eng.run(2)
    doc = json.load(open(path))
    assert "abort" in doc["reason"]
    assert "injected prep failure" in doc["reason"]
    assert doc["rounds"], "flight dump lost the retained rounds"
    assert doc["rounds"][-1]["round"] == 2
    assert "critique" in doc["rounds"][-1]
    assert doc["spans"], "flight dump lost the span window"
    assert doc["metrics"]["counters"]["rounds"] == 3
