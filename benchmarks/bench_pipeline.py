"""Host data-path benchmark: vectorized packing + pipelined rounds.

Scoreboard for the pipelined-round-execution PR (the host side of the
paper's idle-time argument applied to the simulator itself):

* **pack**: per-round host time to build `[W, P, S, ...]` arrays for the
  largest `bench_scalability`-style cohort — the old per-batch loop packer
  plus the engine's former post-hoc S-bucket ``np.pad`` recopy, vs the
  vectorized packer that allocates at the bucketed size and reuses buffers
  (acceptance: >= 2x).
* **engine**: end-to-end rounds with ``pipeline_depth`` 0 vs 1 vs 2 — wall
  time per round, fraction of the pack hidden under device execution, and
  the compile-cache recompile count (losses are asserted bit-identical
  across depths).
* **device_cache**: a Zipf-skewed sampling workload (hot clients recur)
  with the HBM batch cache off vs on — hit rate, H2D bytes saved, and the
  bit-identity of the cached run.  NOTE: on CPU CI host and "device"
  share memory, so the saved bytes buy no wall time here (the cache costs
  an extra fused scatter pass); hit rate and bytes/round are the metrics
  that transfer to accelerators with a real host↔device interconnect.
* **mesh**: the same skewed workload executed as per-worker device
  programs over 1/2/4 mesh shards (shard count 1 = the fused program) —
  losses asserted bit-identical across shard counts, per-shard cache-pool
  accounting (must sum to the global counters), and the worker-step
  compile count (all workers share ONE executable per S bucket).
* **hierarchy**: the hierarchical-mesh refinements on a heterogeneous
  (fast + slow) pool — ``bucket_mode="worker"`` per-worker S buckets
  (padded-step counts must drop vs ``"round"``, losses bit-identical,
  executables O(log S)) and ``combine_mode="tree"`` shard-local combine
  trees (cross-shard transfer bytes must shrink, losses equal to the flat
  combine within float tolerance).

Emits machine-readable JSON (default ``BENCH_pipeline.json`` at the repo
root, override with ``POLLEN_BENCH_OUT``) so future PRs get a perf
trajectory; ``benchmarks.perf_gate`` compares a fresh run against the
checked-in JSON in CI and fails the PR on regression.
"""

import json
import os
import time

import numpy as np

__all__ = ["run"]


def _pack_comparison(*, cohort: int, workers: int, rounds: int) -> dict:
    from repro.core import s_bucket
    from repro.core.placement import Assignment, ClientInfo, WorkerInfo
    from repro.data import make_federated_dataset
    from repro.data.batching import (PackBuffers, build_round_arrays,
                                     build_round_arrays_loop)

    ds = make_federated_dataset("ic", input_dim=64)
    rng = np.random.default_rng(23)
    winfos = [WorkerInfo(wid=i) for i in range(workers)]
    kw = dict(lanes_per_worker=2, steps_cap=16, batch_size=20)

    def sample_assignment():
        cids = rng.choice(ds.n_clients, size=cohort, replace=False)
        clients = [ClientInfo(cid=int(c), n_batches=ds.n_batches(int(c)),
                              n_samples=ds.n_samples(int(c))) for c in cids]
        per = {w.wid: [] for w in winfos}
        for i, c in enumerate(clients):
            per[winfos[i % workers].wid].append(c)
        return Assignment(per_worker=per)

    def pad_to_bucket(arrays):
        # the engine's former post-pack recopy, reproduced for the baseline
        S = s_bucket(arrays.n_steps)
        if S == arrays.n_steps:
            return arrays
        pad = S - arrays.n_steps

        def pad_s(a):
            widths = [(0, 0)] * a.ndim
            widths[2] = (0, pad)
            return np.pad(a, widths)

        arrays.batches = {k: pad_s(v) for k, v in arrays.batches.items()}
        arrays.step_mask = pad_s(arrays.step_mask)
        arrays.boundary = pad_s(arrays.boundary)
        arrays.weight = pad_s(arrays.weight)
        arrays.n_steps = S
        return arrays

    assignments = [sample_assignment() for _ in range(rounds)]
    buf = PackBuffers(depth=2)
    # warm the gather jit cache outside the timed region
    build_round_arrays(ds, assignments[0], winfos, buffers=buf,
                       s_align=s_bucket, **kw)

    old_s, new_s, steps = [], [], 0
    for a in assignments:
        t0 = time.perf_counter()
        arrays = pad_to_bucket(build_round_arrays_loop(ds, a, winfos, **kw))
        old_s.append(time.perf_counter() - t0)
        steps = int(arrays.step_mask.sum())
        t0 = time.perf_counter()
        vec = build_round_arrays(ds, a, winfos, buffers=buf,
                                 s_align=s_bucket, **kw)
        new_s.append(time.perf_counter() - t0)
        assert vec.n_steps == arrays.n_steps
        np.testing.assert_array_equal(vec.step_mask, arrays.step_mask)

    return {
        "cohort": cohort, "workers": workers, "rounds": rounds,
        "real_steps_per_round": steps,
        "loop_pack_pad_s_per_round": float(np.mean(old_s)),
        "vectorized_pack_s_per_round": float(np.mean(new_s)),
        "speedup_x": float(np.mean(old_s) / np.mean(new_s)),
    }


def _build_engine(*, depth: int, sampler=None, device_cache: int = 0,
                  mesh: int = 0, bucket: str = "round", combine: str = "flat",
                  compress: str = "none", frac: float = 0.05, hosts: int = 0,
                  pool=None, steps_cap: int = 8, dataset=None, obs=None):
    import jax

    from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                            UniformSampler, make_placement)
    from repro.data import make_federated_dataset
    from repro.distributed import WorkerPool
    from repro.models.papertasks import make_task_model
    from repro.optim import sgd

    ds = dataset if dataset is not None else make_federated_dataset(
        "sr", n_clients=256, input_dim=32, batch_size=8)
    params, loss = make_task_model("sr", jax.random.key(0), input_dim=32,
                                   width=64, n_blocks=2)
    return FederatedEngine(
        dataset=ds, loss_fn=loss, init_params=params,
        optimizer=sgd(0.1, momentum=0.9), placement=make_placement("lb"),
        sampler=sampler or UniformSampler(256, 32),
        pool=pool or WorkerPool.homogeneous(4, type_name="a40",
                                            concurrency=2),
        telemetry=SyntheticTelemetry(),
        config=EngineConfig(steps_cap=steps_cap, batch_size=8,
                            pipeline_depth=depth,
                            device_cache_batches=device_cache,
                            mesh_workers=mesh, bucket_mode=bucket,
                            combine_mode=combine, combine_compress=compress,
                            combine_topk_frac=frac, hosts=hosts),
        obs=obs)


def _engine_comparison(*, rounds: int, repeats: int = 3) -> dict:
    out = {}
    losses = {}
    for depth in (0, 1, 2):
        eng = _build_engine(depth=depth)
        eng.run(2)                          # warm compile outside the timing
        # Best-of-N measurement: overlap_fraction is a scheduling-quality
        # signal, but any single attempt is hostage to runner load (a
        # stolen core stalls the producer thread and the fraction craters
        # with no structural cause).  The max over attempts estimates what
        # the schedule CAN hide on this machine — stable enough to gate at
        # a tight slack, where the single-shot mean needed 0.15.
        walls, overlaps, all_res = [], [], []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = eng.run(rounds)
            walls.append((time.perf_counter() - t0) / rounds)
            overlaps.append(float(np.mean(
                [r.overlap_fraction for r in res])))
            all_res.extend(res)
        losses[depth] = [r.loss for r in all_res]
        out[f"depth{depth}"] = {
            "rounds": rounds,
            "repeats": repeats,
            "wall_s_per_round": float(min(walls)),
            "pack_s_per_round": float(np.mean(
                [r.pack_time for r in all_res])),
            "overlap_fraction": float(max(overlaps)),
            "overlap_fraction_attempts": overlaps,
            "idle_fraction": float(np.mean(
                [r.idle_fraction for r in all_res])),
            "recompiles": eng.compile_stats["compiles"],
            "cache_hits": eng.compile_stats["hits"],
            "final_loss": float(all_res[-1].loss),
        }
    # depth is a pure scheduling change: training must be bit-identical
    assert losses[0] == losses[1] == losses[2], "depths disagree on losses"
    out["pipeline_speedup_x"] = (out["depth0"]["wall_s_per_round"] /
                                 out["depth1"]["wall_s_per_round"])

    # traced depth-1 rerun: the flight-recorder plane must not perturb
    # training (losses bit-identical to the untraced run) and its wall
    # overhead must stay inside the gated budget (benchmarks.perf_gate:
    # <= 2% relative, with an absolute noise floor)
    from repro.obs import make_observability, profiler_trace

    obs = make_observability(trace_rounds=rounds + 4)
    eng = _build_engine(depth=1, obs=obs)
    eng.run(2)                              # warm compile outside the timing
    t0 = time.perf_counter()
    res = eng.run(rounds)
    traced_wall = (time.perf_counter() - t0) / rounds
    assert ([r.loss for r in res]
            == losses[1][:rounds]), "tracer perturbed training"
    stats = obs.tracer.stats()
    base = out["depth1"]["wall_s_per_round"]
    out["depth1_traced"] = {
        "rounds": rounds,
        "wall_s_per_round": traced_wall,
        "spans": stats["spans"],
        "dropped_spans": stats["dropped"],
    }
    out["tracer_overhead_fraction"] = max(0.0, (traced_wall - base) / base)
    trace_out = os.environ.get("POLLEN_TRACE_OUT")
    if trace_out:
        # A profiler trace (a directory) of two more traced rounds, after
        # the timing: the engine's spans beside the device's operations.
        with profiler_trace(trace_out):
            eng.run(2)
    return out


def _cache_comparison(*, rounds: int, capacity: int = 768) -> dict:
    """Zipf-skewed sampling (hot clients recur): HBM batch cache off vs on."""
    from repro.core import ZipfSampler

    def skew():
        return ZipfSampler(256, 32, a=1.2)

    out = {}
    final = {}
    for tag, cap in (("off", 0), ("on", capacity)):
        eng = _build_engine(depth=1, sampler=skew(), device_cache=cap)
        eng.run(4)     # warm the step + gather/assembly shape buckets
        t0 = time.perf_counter()
        res = eng.run(rounds)
        wall = time.perf_counter() - t0
        final[tag] = [r.loss for r in res]
        entry = {
            "rounds": rounds,
            "wall_s_per_round": wall / rounds,
            "pack_s_per_round": float(np.mean([r.pack_time for r in res])),
            "hit_rate": float(np.mean([r.cache_hit_rate for r in res])),
            "bytes_saved_per_round": float(np.mean(
                [r.cache_bytes_saved for r in res])),
        }
        if cap:
            entry.update({"capacity_rows": cap, **{
                k: eng.cache_stats[k]
                for k in ("hit_steps", "miss_steps", "insertions",
                          "evictions", "clients_cached")}})
        out[tag] = entry
    # the cache replays identical bytes: training must be unchanged
    assert final["off"] == final["on"], "device cache changed training"
    assert out["on"]["hit_rate"] > 0.0, out["on"]
    return out


def _mesh_comparison(*, rounds: int, capacity: int = 768) -> dict:
    """Per-worker device programs over 1/2/4 mesh shards (shard count 1 =
    the fused single program) on the Zipf workload with the device cache
    on: losses must be bit-identical at every shard count; per-shard pool
    accounting must sum to the global counters; the per-worker programs
    must share ONE compiled executable (bounded worker-step compiles)."""
    from repro.core import ZipfSampler

    out = {}
    losses = {}
    for mesh in (0, 2, 4):
        eng = _build_engine(depth=1, mesh=mesh,
                            sampler=ZipfSampler(256, 32, a=1.2),
                            device_cache=capacity)
        eng.run(4)     # warm the step + gather/assembly shape buckets
        t0 = time.perf_counter()
        res = eng.run(rounds)
        wall = time.perf_counter() - t0
        losses[mesh] = [r.loss for r in res]
        tag = "fused" if mesh == 0 else f"shards{mesh}"
        entry = {
            "rounds": rounds,
            "wall_s_per_round": wall / rounds,
            "hit_rate": float(np.mean([r.cache_hit_rate for r in res])),
        }
        if mesh:
            cs = eng.cache_stats
            ws = eng.compile_stats["worker_step"]
            entry["worker_step_compiles"] = ws["compiles"]
            entry["worker_step_hits"] = ws["hits"]
            entry["per_shard"] = [
                {k: s[k] for k in ("hit_steps", "miss_steps", "insertions",
                                   "evictions", "bytes_saved",
                                   "capacity_rows")}
                for s in cs["per_shard"]]
            entry["per_shard_sums_to_global"] = all(
                sum(s[k] for s in cs["per_shard"]) == cs[k]
                for k in ("hit_steps", "miss_steps", "insertions",
                          "evictions", "bytes_saved"))
        out[tag] = entry
    # the mesh decomposition is a scheduling/measurement change only
    assert losses[0] == losses[2] == losses[4], "shard counts disagree"
    out["losses_identical"] = True
    return out


def _hierarchy_comparison(*, rounds: int) -> dict:
    """Hierarchical mesh execution on a HETEROGENEOUS pool (two fast + two
    slow workers — LB placement hands the slow ones fewer batches, so
    per-worker stream lengths genuinely differ) under zipf skew:

    * ``bucket_mode="worker"`` must dispatch fewer padded steps than
      ``"round"`` with bit-identical losses and O(log S) executables;
    * ``combine_mode="tree"`` (per-shard partial merge) must shrink the
      cross-shard combine transfer, with losses equal to the flat combine
      to float tolerance (the hierarchy re-associates the mean);
    * ``combine_compress="int8"/"topk"`` must shrink the compressed
      ``combine_bytes`` by the gated ratios vs the FLAT combine (>= 3.5x /
      >= 10x at frac=0.05), with final losses no more than the documented
      25% WORSE than the exact tree run (the deviation is signed: error
      feedback often converges lower, which is not a failure) and a
      bounded residual-norm trajectory (error feedback is draining, not
      accumulating)."""
    import numpy as np

    from repro.core import ZipfSampler
    from repro.distributed import WorkerPool

    def hetero_pool():
        return WorkerPool.from_specs([("a40", 1.0, 2), ("a40", 1.0, 2),
                                      ("2080ti", 0.35, 2),
                                      ("2080ti", 0.35, 2)])

    variants = {
        "round": dict(bucket="round", combine="flat"),
        "worker": dict(bucket="worker", combine="flat"),
        "tree": dict(bucket="worker", combine="tree"),
        "int8": dict(bucket="worker", combine="tree", compress="int8"),
        "topk": dict(bucket="worker", combine="tree", compress="topk",
                     frac=0.05),
    }
    # 2 shards x 2 workers: each shard has a real multi-worker block to
    # merge locally (4 shards over 4 workers would leave one lane per
    # shard — nothing for the tree to shrink).
    out: dict = {"shards": 2, "rounds": rounds}
    losses = {}
    for tag, kw in variants.items():
        eng = _build_engine(depth=1, mesh=2, steps_cap=16,
                            sampler=ZipfSampler(256, 32, a=1.2),
                            pool=hetero_pool(), **kw)
        eng.run(2)     # warm the executables outside the timing
        t0 = time.perf_counter()
        res = eng.run(rounds)
        wall = time.perf_counter() - t0
        losses[tag] = [r.loss for r in res]
        out[tag] = {
            "wall_s_per_round": wall / rounds,
            "padded_steps": int(sum(r.padded_steps for r in res)),
            "combine_bytes": int(res[-1].combine_bytes),
            "worker_step_compiles":
                eng.compile_stats["worker_step"]["compiles"],
        }
        if kw.get("compress"):
            out[tag]["residual_norms"] = [
                round(r.residual_norm, 6) for r in res]
    for tag in ("int8", "topk"):
        out[tag]["compression_ratio_vs_flat"] = round(
            out["round"]["combine_bytes"] / out[tag]["combine_bytes"], 2)
        # SIGNED deviation: positive = compressed run ends worse than the
        # exact tree run, negative = better (error feedback's smoothing
        # often lands lower once losses hit the 1e-3 floor, where an
        # absolute deviation would be pure noise).  Only degradation gates.
        out[tag]["final_loss_rel_dev_vs_tree"] = round(
            (losses[tag][-1] - losses["tree"][-1])
            / abs(losses["tree"][-1]), 4)
    out["bucket_modes_identical"] = losses["round"] == losses["worker"]
    out["tree_combine_allclose"] = bool(np.allclose(
        np.asarray(losses["worker"]), np.asarray(losses["tree"]),
        rtol=1e-5))
    pr, pw = out["round"]["padded_steps"], out["worker"]["padded_steps"]
    out["padded_saved_fraction"] = 1.0 - pw / pr if pr else 0.0
    # acceptance: per-worker buckets trade O(log S) executables for
    # strictly less padding; the shard-local merge tree strictly shrinks
    # the cross-shard transfer
    assert out["bucket_modes_identical"], losses
    assert out["tree_combine_allclose"], losses
    assert pw < pr, out
    assert out["tree"]["combine_bytes"] < out["round"]["combine_bytes"], out
    # acceptance: the compressed wire format shrinks the transfer by the
    # gated ratios and error feedback keeps training near the exact run
    assert out["int8"]["compression_ratio_vs_flat"] >= 3.5, out
    assert out["topk"]["compression_ratio_vs_flat"] >= 10.0, out
    for tag in ("int8", "topk"):
        # signed: < 0.25 means "at most 25% worse than exact" — a
        # compressed run that converges lower passes trivially
        assert out[tag]["final_loss_rel_dev_vs_tree"] < 0.25, out
        norms = out[tag]["residual_norms"]
        assert norms[-1] < 10.0 * max(norms[0], 1e-6), out  # bounded, not
        #                                                     runaway growth
    return out


def _population_comparison(*, rounds: int) -> dict:
    """Open-world population workload (docs/POPULATION.md): a 1M-client
    hash-derived registry sampled by the streaming OnlinePoolSampler.

    * **store_peak_kb**: tracemalloc peak of registering one MILLION clients
      — the store is a seed plus hash streams, so the peak must stay O(1)
      (gated at a few hundred KB, ~3 orders below a materialized table);
    * depths 0/1/2 over the same registry must produce bit-identical losses
      (the online pool is drawn producer-side in round order, like every
      other host mutation);
    * the deadline-SLO metrics (slo_p50/p99, stale_fraction, online_pool)
      and the rejection-draw budget (draws bounded by
      ``max_draw_factor * cohort``) are recorded for the trend lane."""
    import tracemalloc

    from repro.population import (ArrivalIndex, ClientMetadataStore,
                                  OnlinePoolSampler, PopulationDataset)

    population, cohort = 1_000_000, 64

    tracemalloc.start()
    store = ClientMetadataStore(population, seed=11, batch_size=8)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    out: dict = {"population": population, "cohort": cohort,
                 "rounds": rounds,
                 "store_peak_kb": round(peak / 1024, 2)}
    losses = {}
    for depth in (0, 1, 2):
        from repro.data import make_federated_dataset

        base = make_federated_dataset("sr", n_clients=256, input_dim=32,
                                      batch_size=8)
        store = ClientMetadataStore(population, seed=11, batch_size=8)
        index = ArrivalIndex(store)
        sampler = OnlinePoolSampler(index, cohort, seed=11)
        eng = _build_engine(depth=depth, sampler=sampler,
                            dataset=PopulationDataset(base, store))
        eng.run(2)                          # warm compile outside the timing
        t0 = time.perf_counter()
        res = eng.run(rounds)
        wall = time.perf_counter() - t0
        losses[depth] = [r.loss for r in res]
        if depth == 1:
            stats = sampler.last_stats
            out.update({
                "wall_s_per_round": wall / rounds,
                "stale_fraction": float(np.mean(
                    [r.stale_fraction for r in res])),
                "slo_p50": float(np.mean([r.slo_p50 for r in res])),
                "slo_p99": float(np.mean([r.slo_p99 for r in res])),
                "online_pool": float(np.mean([r.online_pool for r in res])),
                "draws_per_round": int(stats["draws"]),
                "probes_per_round": round(index.probes / (rounds + 2), 1),
                "draws_bounded": bool(
                    stats["draws"] <= sampler.max_draw_factor * cohort),
            })
    out["losses_identical"] = losses[0] == losses[1] == losses[2]
    # acceptance: registering 1M clients is O(1) host memory; the pipeline
    # depths agree bit-for-bit; the rejection loop respected its budget
    assert out["store_peak_kb"] < 512, out
    assert out["losses_identical"], losses
    assert out["draws_bounded"], out
    assert out["slo_p99"] >= out["slo_p50"] > 0.0, out
    return out


def _multihost_comparison(*, rounds: int) -> dict:
    """The host level above the shard→root combine (EngineConfig.hosts):
    one merged partial per host crosses to the root, so the accounted
    combine_bytes scale O(H) instead of O(K) — at bit-identical losses
    across H (hosts=1 is the reference pairwise tree) and no pack-time
    regression (the producer pipeline is untouched by the combine shape).

    hosts=0 is the legacy scan-fold tree combine: a different (pre-hosts)
    arithmetic family, benched here as the O(K)-bytes / pack-time anchor.
    """
    out = {}
    losses = {}
    for hosts in (0, 1, 2, 4):
        eng = _build_engine(depth=1, mesh=4, combine="tree", hosts=hosts)
        eng.run(2)                          # warm compile outside the timing
        t0 = time.perf_counter()
        res = eng.run(rounds)
        wall = time.perf_counter() - t0
        losses[hosts] = [r.loss for r in res]
        out[f"hosts{hosts}"] = {
            "rounds": rounds,
            "wall_s_per_round": wall / rounds,
            "pack_s_per_round": float(np.mean([r.pack_time for r in res])),
            "combine_bytes": int(res[-1].combine_bytes),
            "final_loss": float(res[-1].loss),
        }
    out["losses_identical"] = (losses[1] == losses[2] == losses[4])
    h1 = out["hosts1"]["combine_bytes"]
    out["root_bytes_ratio_h2_h1"] = out["hosts2"]["combine_bytes"] / h1
    out["root_bytes_ratio_h4_h1"] = out["hosts4"]["combine_bytes"] / h1
    out["root_bytes_ratio_legacy_h1"] = out["hosts0"]["combine_bytes"] / h1
    out["pack_ratio_vs_legacy"] = (out["hosts2"]["pack_s_per_round"] /
                                   out["hosts0"]["pack_s_per_round"])
    # acceptance: O(H) at the root (exact byte accounting, machine-
    # independent), bit-identity in H, and the producer untouched (banded:
    # pack time is wall-clock)
    assert out["losses_identical"], losses
    assert out["root_bytes_ratio_h2_h1"] == 2.0, out
    assert out["root_bytes_ratio_h4_h1"] == 4.0, out
    assert out["root_bytes_ratio_legacy_h1"] == 4.0, out   # K=4 shards
    assert out["pack_ratio_vs_legacy"] <= 1.5, out
    return out


def run(*, cohort: int = 1000, workers: int = 16, pack_rounds: int = 3,
        engine_rounds: int = 8) -> list[str]:
    pack = _pack_comparison(cohort=cohort, workers=workers,
                            rounds=pack_rounds)
    engine = _engine_comparison(rounds=engine_rounds)
    cache = _cache_comparison(rounds=engine_rounds)
    mesh = _mesh_comparison(rounds=engine_rounds)
    hierarchy = _hierarchy_comparison(rounds=engine_rounds)
    population = _population_comparison(rounds=engine_rounds)
    multihost = _multihost_comparison(rounds=engine_rounds)

    record = {"benchmark": "pipeline", "pack": pack, "engine": engine,
              "device_cache": cache, "mesh": mesh, "hierarchy": hierarchy,
              "population": population, "multihost": multihost}
    out_path = os.environ.get(
        "POLLEN_BENCH_OUT",
        os.path.join(os.path.dirname(__file__), "..", "BENCH_pipeline.json"))
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")

    rows = ["bench_pipeline,metric,value"]
    rows.append(f"bench_pipeline,loop_pack_pad_s,"
                f"{pack['loop_pack_pad_s_per_round']:.3f}")
    rows.append(f"bench_pipeline,vectorized_pack_s,"
                f"{pack['vectorized_pack_s_per_round']:.3f}")
    rows.append(f"bench_pipeline,pack_speedup_x,{pack['speedup_x']:.1f}")
    for depth in ("depth0", "depth1", "depth2"):
        e = engine[depth]
        rows.append(f"bench_pipeline,{depth}_wall_s_per_round,"
                    f"{e['wall_s_per_round']:.3f}")
        rows.append(f"bench_pipeline,{depth}_overlap_fraction,"
                    f"{e['overlap_fraction']:.2f}")
        rows.append(f"bench_pipeline,{depth}_recompiles,{e['recompiles']}")
    rows.append(f"bench_pipeline,pipeline_speedup_x,"
                f"{engine['pipeline_speedup_x']:.2f}")
    rows.append(f"bench_pipeline,depth1_idle_fraction,"
                f"{engine['depth1']['idle_fraction']:.3f}")
    rows.append(f"bench_pipeline,tracer_overhead_fraction,"
                f"{engine['tracer_overhead_fraction']:.3f}")
    rows.append(f"bench_pipeline,cache_hit_rate,"
                f"{cache['on']['hit_rate']:.2f}")
    rows.append(f"bench_pipeline,cache_bytes_saved_per_round,"
                f"{cache['on']['bytes_saved_per_round']:.0f}")
    for tag in ("shards2", "shards4"):
        m = mesh[tag]
        rows.append(f"bench_pipeline,mesh_{tag}_hit_rate,"
                    f"{m['hit_rate']:.2f}")
        rows.append(f"bench_pipeline,mesh_{tag}_worker_step_compiles,"
                    f"{m['worker_step_compiles']}")
    for tag in ("round", "worker", "tree", "int8", "topk"):
        h = hierarchy[tag]
        rows.append(f"bench_pipeline,hierarchy_{tag}_padded_steps,"
                    f"{h['padded_steps']}")
        rows.append(f"bench_pipeline,hierarchy_{tag}_combine_bytes,"
                    f"{h['combine_bytes']}")
    rows.append(f"bench_pipeline,hierarchy_padded_saved_fraction,"
                f"{hierarchy['padded_saved_fraction']:.2f}")
    for tag in ("int8", "topk"):
        rows.append(f"bench_pipeline,hierarchy_{tag}_compression_x,"
                    f"{hierarchy[tag]['compression_ratio_vs_flat']:.1f}")
        rows.append(f"bench_pipeline,hierarchy_{tag}_loss_rel_dev,"
                    f"{hierarchy[tag]['final_loss_rel_dev_vs_tree']:.4f}")
    rows.append(f"bench_pipeline,population_store_peak_kb,"
                f"{population['store_peak_kb']:.1f}")
    rows.append(f"bench_pipeline,population_wall_s_per_round,"
                f"{population['wall_s_per_round']:.3f}")
    rows.append(f"bench_pipeline,population_stale_fraction,"
                f"{population['stale_fraction']:.3f}")
    rows.append(f"bench_pipeline,population_slo_p99_s,"
                f"{population['slo_p99']:.2f}")
    rows.append(f"bench_pipeline,population_online_pool,"
                f"{population['online_pool']:.0f}")
    for tag in ("hosts0", "hosts1", "hosts2", "hosts4"):
        rows.append(f"bench_pipeline,multihost_{tag}_combine_bytes,"
                    f"{multihost[tag]['combine_bytes']}")
    rows.append(f"bench_pipeline,multihost_pack_ratio_vs_legacy,"
                f"{multihost['pack_ratio_vs_legacy']:.2f}")
    # acceptance: the vectorized pack must at least halve host pack+pad time
    assert pack["speedup_x"] >= 2.0, pack
    # acceptance: deepening the pipeline never hides less of the pack.
    # Both fractions are best-of-3 (see _engine_comparison), which removes
    # the runner-load noise that forced the old single-shot slack out to
    # 0.15 — the gate is back at 0.08 (perf_gate matches).
    assert (engine["depth2"]["overlap_fraction"] >=
            engine["depth1"]["overlap_fraction"] - 0.08), engine
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
