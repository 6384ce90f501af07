"""Mesh execution: per-worker device programs over worker shards.

The decomposition invariant (acceptance-gated): synthetic-mode losses are
bit-identical across mesh shard counts 1/2/4 at pipeline depths 0/1/2 —
shard count 1 IS the fused single-program path — even with the control
plane live.  Measured mode on a mesh records exact per-worker wall times;
the round-level predicted-share attribution path is never used.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, ZipfSampler, apply_cache_affinity,
                        make_placement)
from repro.core.placement import Assignment, ClientInfo, WorkerInfo
from repro.data import make_federated_dataset
from repro.distributed import WorkerPool
from repro.distributed.sharding import WorkerShardMap
from repro.fl.strategy import FedMedian
from repro.models.papertasks import make_task_model
from repro.optim import sgd


def _engine(mesh=0, depth=1, cache=0, placement="lb", telemetry="synthetic",
            drift=0.0, adapt=0, sampler="uniform", affinity=False,
            granularity="type", strategy=None, workers=4, bucket="round",
            combine="flat", compress="none", hosts=0, pool=None,
            steps_cap=4):
    ds = make_federated_dataset("sr", n_clients=64, input_dim=16,
                                batch_size=4, size_mu=2.5, size_sigma=0.8)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=16,
                                   width=32, n_blocks=2)
    samp = (ZipfSampler(64, 8, a=1.2) if sampler == "zipf"
            else UniformSampler(64, 8))
    return FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params,
        optimizer=sgd(0.1, momentum=0.9),
        placement=make_placement(placement), sampler=samp,
        pool=pool or WorkerPool.homogeneous(workers, type_name="a40",
                                            concurrency=2),
        telemetry=SyntheticTelemetry(), strategy=strategy,
        config=EngineConfig(steps_cap=steps_cap, batch_size=4,
                            lanes_per_worker=2,
                            pipeline_depth=depth, mesh_workers=mesh,
                            device_cache_batches=cache,
                            cache_affinity=affinity,
                            bucket_mode=bucket, combine_mode=combine,
                            combine_compress=compress, hosts=hosts,
                            telemetry_mode=telemetry,
                            drift_threshold=drift, adapt_interval=adapt,
                            adapt_granularity=granularity))


def _hetero_pool():
    """Two fast + two slow workers: LB placement hands the slow ones fewer
    batches, so their lanes are genuinely shorter — the workload where
    per-worker S buckets save padded steps."""
    return WorkerPool.from_specs([("a40", 1.0, 2), ("a40", 1.0, 2),
                                  ("2080ti", 0.35, 2), ("2080ti", 0.35, 2)])


# -- the decomposition invariant ---------------------------------------------

def test_losses_bit_identical_across_shard_counts_and_depths():
    """The acceptance matrix: bucket modes {round, worker} x shard counts
    {1, 2, 4} x depths {0, 1, 2}, controller live (drift detection +
    per-worker slot climbing): losses, makespans and S are bit-identical.
    Shard count 1 is the fused single-program path (its one program has one
    S, so bucket_mode does not apply); bucket_mode="worker" truncates short
    workers' trailing masked steps, which the guarded fold makes bitwise
    no-ops — this test is what enforces that."""
    kw = dict(drift=0.4, adapt=2, granularity="worker")
    base = _engine(mesh=0, depth=1, **kw).run(5)
    for mesh in (2, 4):
        for depth in (0, 1, 2):
            for bucket in ("round", "worker"):
                res = _engine(mesh=mesh, depth=depth, bucket=bucket,
                              **kw).run(5)
                tag = f"mesh={mesh} depth={depth} bucket={bucket}"
                assert [r.loss for r in res] == [r.loss for r in base], tag
                assert ([r.makespan for r in res]
                        == [r.makespan for r in base]), tag
                assert [r.s_steps for r in res] == [r.s_steps for r in base], tag


def test_worker_buckets_cut_padded_steps_and_stay_bit_identical():
    """bucket_mode="worker" on a heterogeneous pool: fewer dispatched-but-
    masked steps than bucket_mode="round" (the padding the per-worker S
    buckets exist to cut), with bit-identical losses, O(log S) worker-step
    executables, and the compile cache still mostly hitting."""
    kw = dict(mesh=2, depth=1, sampler="zipf", steps_cap=16)
    rnd = _engine(pool=_hetero_pool(), bucket="round", **kw)
    r_round = rnd.run(6)
    wrk = _engine(pool=_hetero_pool(), bucket="worker", **kw)
    r_worker = wrk.run(6)
    assert [r.loss for r in r_worker] == [r.loss for r in r_round]
    padded_round = sum(r.padded_steps for r in r_round)
    padded_worker = sum(r.padded_steps for r in r_worker)
    assert padded_worker < padded_round, (padded_worker, padded_round)
    # O(log S) executables: bounded by the distinct S buckets seen, far
    # below one-per-(worker x round) (4 workers x 6 rounds dispatches).
    ws = wrk.compile_stats["worker_step"]
    assert ws["compiles"] <= 8
    assert ws["hits"] >= 6 * 4 - ws["compiles"]


def test_tree_combine_hierarchy():
    """combine_mode="tree" (§3.3's shard-local partial merge before the
    cross-shard combine): losses match the flat combine to float tolerance
    (the hierarchy re-associates the cross-lane mean — documented, not
    hidden), are bit-identical across depths AND bucket modes at fixed K,
    and the cross-shard transfer shrinks from O(K*lanes) to O(K)."""
    flat = _engine(mesh=4, depth=1)
    r_flat = flat.run(6)
    tree = _engine(mesh=4, depth=1, combine="tree")
    r_tree = tree.run(6)
    fl = np.asarray([r.loss for r in r_flat])
    tr = np.asarray([r.loss for r in r_tree])
    assert np.allclose(fl, tr, rtol=1e-5), (fl, tr)
    # scheduling-only changes keep the tree path bit-identical
    r_d2 = _engine(mesh=4, depth=2, combine="tree").run(6)
    assert [r.loss for r in r_d2] == [r.loss for r in r_tree]
    r_wb = _engine(mesh=4, depth=1, combine="tree", bucket="worker").run(6)
    assert [r.loss for r in r_wb] == [r.loss for r in r_tree]
    # transfer: flat ships every lane partial (W x P = 8), tree one merged
    # partial per live shard (4)
    assert all(r.combine_bytes > 0 for r in r_flat + r_tree)
    assert r_tree[-1].combine_bytes < r_flat[-1].combine_bytes
    assert (r_flat[-1].combine_bytes
            == 2 * r_tree[-1].combine_bytes)  # 8 lanes vs 4 shard partials
    # the merge programs are counted like every other compiled step
    assert tree.compile_stats["merge_step"]["compiles"] >= 1


def test_mesh_cache_bit_identical_and_per_shard_accounting():
    """Per-shard pools serve exact bytes: a Zipf (hot-client) run is
    bit-identical fused vs 2-shard mesh, and the per-shard hit/miss/bytes
    counters sum to the global stats."""
    fused = _engine(mesh=0, depth=1, cache=64, sampler="zipf").run(6)
    eng = _engine(mesh=2, depth=1, cache=64, sampler="zipf")
    res = eng.run(6)
    assert [r.loss for r in fused] == [r.loss for r in res]
    st = eng.cache_stats
    assert st["n_shards"] == 2 and len(st["per_shard"]) == 2
    for key in ("hit_steps", "miss_steps", "hit_clients", "miss_clients",
                "insertions", "evictions", "bytes_saved", "clients_cached",
                "rows_used"):
        assert sum(s[key] for s in st["per_shard"]) == st[key], key
    # capacity split evenly; shards must both have seen traffic
    assert all(s["capacity_rows"] == 32 for s in st["per_shard"])
    assert all(s["miss_steps"] > 0 for s in st["per_shard"])
    # ONE worker-step executable serves every worker: compiles are bounded
    # by the distinct S buckets, not workers x rounds (4 x 6 dispatches).
    ws = eng.compile_stats["worker_step"]
    assert ws["compiles"] <= 4
    assert ws["hits"] >= 6 * 4 - ws["compiles"]


def test_mesh_measured_mode_exact_per_worker_times():
    """Multi-shard measured runs never use predicted-share attribution:
    every row comes from a per-worker device sync, every worker gets a
    residual, and the refit barrier audit stays clean."""
    eng = _engine(mesh=2, depth=1, telemetry="measured", drift=0.4)
    eng.run(5)
    st = eng.control.stats()
    assert st["barrier"]["rows_attributed"] == 0
    assert st["barrier"]["rows_exact"] > 0
    assert st["audit_violations"] == 0
    # every live worker accumulated a measured-vs-predicted residual
    assert sorted(st["worker_residuals"]) == [0, 1, 2, 3]
    assert all(r.exec_time > 0 for r in eng.history)


def test_mesh_requires_associative_strategy():
    with pytest.raises(ValueError, match="associative"):
        _engine(mesh=2, strategy=FedMedian())


def test_engine_config_rejects_bad_mesh_knobs():
    with pytest.raises(ValueError, match="mesh_workers"):
        EngineConfig(mesh_workers=-1)
    with pytest.raises(ValueError, match="cache_affinity"):
        EngineConfig(cache_affinity=True, device_cache_batches=8)
    with pytest.raises(ValueError, match="device cache"):
        EngineConfig(cache_affinity=True, mesh_workers=2)
    with pytest.raises(ValueError, match="adapt_granularity"):
        EngineConfig(adapt_granularity="lane")
    with pytest.raises(ValueError, match="bucket_mode"):
        EngineConfig(bucket_mode="lane", mesh_workers=2)
    with pytest.raises(ValueError, match="mesh_workers >= 2"):
        EngineConfig(bucket_mode="worker")        # fused path: no per-worker S
    with pytest.raises(ValueError, match="mesh_workers >= 2"):
        EngineConfig(bucket_mode="worker", mesh_workers=1)
    with pytest.raises(ValueError, match="combine_mode"):
        EngineConfig(combine_mode="ring", mesh_workers=2)
    with pytest.raises(ValueError, match="mesh_workers >= 2"):
        EngineConfig(combine_mode="tree")
    # valid combinations construct fine
    EngineConfig(mesh_workers=2, bucket_mode="worker", combine_mode="tree")


# -- worker shard map --------------------------------------------------------

def test_worker_shard_map_stable_under_churn():
    workers = [WorkerInfo(wid=w) for w in (0, 1, 2, 5, 8)]
    m = WorkerShardMap.build(workers, 3)
    assert m.shard_of(5) == 2 and m.shard_of(8) == 2 and m.shard_of(1) == 1
    # a worker keeps its shard when OTHER workers fail/join
    m2 = WorkerShardMap.build([w for w in workers if w.wid != 1], 3)
    assert all(m2.shard_of(w.wid) == m.shard_of(w.wid)
               for w in workers if w.wid != 1)
    assert m.workers_in(2) == [2, 5, 8]
    assert m.device_for(0) is None            # no devices bound
    with pytest.raises(ValueError, match="n_shards"):
        WorkerShardMap.build(workers, 0)
    # the combine-tree topology: shard -> live workers in dispatch order
    assert m.live_shards() == {0, 1, 2}
    assert m.merge_groups() == {0: [0], 1: [1], 2: [2, 5, 8]}
    # a shard whose last worker left drops out of the tree
    m3 = WorkerShardMap.build([w for w in workers if w.wid != 1], 3)
    assert m3.live_shards() == {0, 2}
    assert 1 not in m3.merge_groups()


def test_fl_combine_topology_binds_merges_and_root():
    from repro.launch.mesh import fl_combine_topology, fl_shard_devices

    devs, root = fl_combine_topology(4)
    assert len(devs) == 4
    assert devs == fl_shard_devices(4)      # merges live on the shard leads
    assert root == devs[0]                  # cross-shard combine at the root


# -- cache-aware placement ---------------------------------------------------

def test_apply_cache_affinity_is_load_neutral():
    """A swap exchanges equal-batch clients between equal-type workers: the
    per-worker batch multiset (and thus every placement metric) is
    unchanged, while the cached client lands on its home shard."""
    cs = [ClientInfo(cid=i, n_batches=nb)
          for i, nb in enumerate([4, 4, 6, 6])]
    workers = [WorkerInfo(wid=0, type_name="a40"),
               WorkerInfo(wid=1, type_name="a40")]
    asg = Assignment(per_worker={0: [cs[0], cs[2]], 1: [cs[1], cs[3]]})
    shard_of_wid = {0: 0, 1: 1}
    # client 1 (x=4, on worker 1 / shard 1) is cached in shard 0
    cached = {1: 0}.get
    out, n = apply_cache_affinity(asg, workers, shard_of_wid, cached)
    assert n == 1
    assert [c.cid for c in out.per_worker[0]] == [1, 2]   # cid 1 went home
    assert [c.cid for c in out.per_worker[1]] == [0, 3]
    for wid in (0, 1):   # load-neutral: batch multisets unchanged
        assert (sorted(c.n_batches for c in out.per_worker[wid])
                == sorted(c.n_batches for c in asg.per_worker[wid]))
    # no eligible partner (different type) -> no swap
    workers2 = [WorkerInfo(wid=0, type_name="a40"),
                WorkerInfo(wid=1, type_name="2080ti")]
    _, n2 = apply_cache_affinity(asg, workers2, shard_of_wid, cached)
    assert n2 == 0


def test_cache_affinity_improves_hit_rate_on_skew():
    off = _engine(mesh=2, depth=1, cache=64, sampler="zipf")
    r_off = off.run(8)
    on = _engine(mesh=2, depth=1, cache=64, sampler="zipf", affinity=True)
    r_on = on.run(8)
    assert sum(r.affinity_swaps for r in r_on) > 0
    assert sum(r.affinity_swaps for r in r_off) == 0
    assert (on.cache_stats["hit_steps"] >= off.cache_stats["hit_steps"])


# -- per-worker slot climbing ------------------------------------------------

def test_adapt_granularity_worker_moves_single_wid():
    eng = _engine(mesh=2, depth=1, adapt=1, granularity="worker")
    eng.run(6)
    traj = eng.control.autoconc.trajectory
    assert traj, "climber never moved"
    # knobs are per-wid ("w<wid>"), round-robined across workers
    moved_keys = {k for (_, k, _, _) in traj}
    assert all(k.startswith("w") for k in moved_keys)
    assert len(moved_keys) >= 2
    # the last move landed on exactly that worker's pool entry
    _, key, _, new = traj[-1]
    assert eng.pool.workers[int(key[1:])].concurrency == new


# -- one worker program per device -------------------------------------------

_MESH4_SCRIPT = r"""
import json, sys
import jax
from test_mesh import _engine

assert len(jax.devices()) == 4, jax.devices()

out = {"fused": [r.loss for r in _engine(mesh=0).run(3)]}
for name, kw in (("flat", {}), ("tree_hosts2", dict(combine="tree", hosts=2))):
    eng = _engine(mesh=4, **kw)
    losses = [eng.run(1)[0].loss]
    st = eng.compile_stats
    warm = (st["compiles"], st["executables"])
    losses += [r.loss for r in eng.run(2)]
    st = eng.compile_stats
    out[name] = dict(
        losses=losses, warm=warm, after=(st["compiles"], st["executables"]),
        worker_executables=st["worker_step"]["executables"],
        shard_devices=len({str(d) for d in eng._shard_devices}))
print(json.dumps(out))
"""


def test_mesh_workers_on_four_devices():
    """``mesh_workers=4`` on a 4-device host: each worker program runs on
    its own device, partials cross to the root for the flat combine and
    for the shard → host → root tree, and no round after round 0
    compiles — neither a new step-cache entry nor a hidden jit recompile
    from a change of argument placement.  Flat matches the fused run
    bitwise; tree/hosts to float tolerance (as in the one-device tests).
    Runs in a subprocess so the 4 virtual CPU devices do not leak into the
    1-device test session."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _MESH4_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    fused = out["fused"]
    assert out["flat"]["losses"] == fused
    assert np.allclose(out["tree_hosts2"]["losses"], fused, rtol=1e-5)
    for name in ("flat", "tree_hosts2"):
        run = out[name]
        assert run["shard_devices"] == 4, name
        assert run["worker_executables"] == 4, name   # one per device
        assert run["after"] == run["warm"], name
