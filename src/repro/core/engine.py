"""The Pollen round engine (host-side orchestration; paper Fig. 6).

Per round:
  1. ``WorkerPool.advance_to(t)`` applies elastic fail/join events;
  2. the sampler draws a cohort (placement is independent of selection, §3.1);
  3. optional deadline trim drops predicted stragglers (over-sampled cohort);
  4. the placement strategy one-shot assigns clients to workers (push-based);
  5. the vectorized packer (``build_round_arrays``) fills reusable host
     buffers already sized to the S-bucket — slot indices via numpy fancy
     indexing, content via one bulk ``gather_batches`` call, zero post-pack
     copies;
  6. the jitted round step trains + partially aggregates on device, through
     an explicit :class:`~repro.fl.round.StepCompileCache` (donated buffers,
     counted recompiles, LRU eviction);
  7. telemetry (measured or synthetic) is appended;
  8. periodic checkpoint.

The time model is refit at the START of preparing round t (before its
assignment), so the fit literally runs while round t-1 trains and —
together with TrainingTimeModel's data <= t-2 cutoff — every assignment
sees the same model regardless of pipeline depth or how run() calls are
split.

Pipelining (``EngineConfig.pipeline_depth``, paper §3.2's push-based
pipelining applied to the simulator itself):

* ``depth = 0`` — fully synchronous loop;
* ``depth >= 1`` — a single *producer* thread prepares rounds
  t+1 .. t+depth (sample → place → pack → async ``device_put``) behind a
  bounded queue while the consumer executes round t on device.  The
  producer runs EVERY host-state mutation — pool events, sampler RNG
  draws, the time-model refit, telemetry draws, and ``placement.observe``
  — in strict round order on one thread, which is what makes losses (and
  telemetry) bit-identical across depths: refit for round u always sees
  exactly the rounds <= u-2 the TrainingTimeModel cutoff asks for, no
  matter how many rounds are in flight.  Telemetry for round t is
  *simulated/synthesized from the assignment*, never from device results,
  so drawing it at prepare time (producer) instead of finish time is
  side-effect-order-preserving.
* The host pack buffers form a ring of ``depth + 1`` slot sets
  (:class:`~repro.data.batching.PackBuffers`): rounds t .. t+depth are in
  flight at once, and slot k is only rewritten at round t+depth+1 — after
  round t's device arrays were consumed (the loop syncs on round t's loss
  before submitting round t+depth+1).

Device-resident client cache (``EngineConfig.device_cache_batches > 0``):
hot clients' batch rows stay in HBM (:class:`~repro.data.device_cache
.DeviceBatchCache`) and no full-size host batch buffer exists at all — the
per-round H2D is one compact ``[n_miss, ...]`` array (plus masks), and a
single fused device scatter assembles a persistent round base from the
miss rows and the pool (recycling inserted misses into the pool on the
way).  A cache-hit client therefore skips the host gather/scatter AND the
transfer entirely.  The step does not donate its batch input while the
cache is active (the base must survive it); params and masks still donate.
Hit-rate and bytes saved surface per round in :class:`RoundResult`.

Closed-loop control (``EngineConfig.telemetry_mode`` / drift / adaptive
concurrency — ``repro.control``): with ``telemetry_mode="measured"`` the
per-client times feeding the placement model come from *measured* round
execution (consumer-side wall clock, attributed to clients by predicted
share) instead of prepare-time synthetic draws.  Because the producer runs
up to ``depth`` rounds ahead, a depth-aware **refit barrier** gates the
flush: the prep of round u may only consume telemetry from rounds that had
finished executing when it flushed — policy ``"stall"`` blocks until round
u-2 (the refit cutoff) is in, policy ``"reuse"`` deterministically reuses
the previous fit until the data arrives.  The controller's drift detector
can fall placement back to Batches-Based while the model mispredicts, and
its hill climber retunes per-type worker concurrency online; both act
producer-side in round order, so synthetic-mode runs stay bit-identical
across pipeline depths even with the controller enabled.

Mesh execution (``EngineConfig.mesh_workers = K >= 2``): the round runs as
**one device program per FL worker** over K mesh shards instead of one
fused step.  The packer partitions the cohort's plan by worker
(``split_plan_by_worker``), each worker's ``[1, P, S]`` block is H2D'd to
its shard's device (``WorkerShardMap``: ``wid % K``, stable under churn),
the per-worker programs — ONE shared compiled executable with
``bucket_mode="round"``, or one per distinct per-worker S bucket with
``bucket_mode="worker"`` (O(log S) executables; short workers skip their
trailing padded steps, counted in ``RoundResult.padded_steps``) — are
dispatched asynchronously and **synced individually**, and the lane
partials reduce through either one global combine (``combine_mode="flat"``:
exactly the fused step's tail on the concatenated partials) or §3.3's
hierarchy (``combine_mode="tree"``: a per-shard partial-merge program,
then the same tail over one merged partial per shard — O(K) cross-shard
transfer, ``RoundResult.combine_bytes``).  Losses are bit-identical
across shard counts 1/2/4 × bucket modes at any pipeline depth
(test-enforced; shard count 1 IS the fused single-program path; the tree
combine matches to float tolerance and is itself depth/bucket-invariant),
while the per-worker syncs give ``MeasuredTelemetry`` exact per-worker
wall times on any backend — the round-level predicted-share attribution
path is unused — and the device cache splits into per-shard pools with
optional cache-aware placement (``cache_affinity``: load-neutral
equal-batch/equal-type swaps toward the shard holding a client's rows)
and orphan-shard reclamation (``DeviceBatchCache.rebalance``: a shard
whose last worker failed lends its row budget to the survivors until a
matching wid rejoins).

The number of distinct compiled programs is bounded by bucketing the stream
length S to the next {1x, 1.5x} power-of-two multiple (beyond-paper
optimization "S-bucketing": O(log S) shapes, padding overhead strictly
< 1.5x worst-case — sup over s of bucket(s)/s approaches 1.5 from below at
s = 2^k + 1 — and ~1.2x in expectation for uniformly-landing S).
"""

from __future__ import annotations

import json
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import (Assignment, ClientInfo,
                                  LearningBasedPlacement, Placement,
                                  apply_cache_affinity)
from repro.core.sampling import restore_sampler, sampler_state
from repro.data.batching import (PackBuffers, RoundArrays, build_round_arrays,
                                 build_round_masks, gather_content_rows,
                                 padding_stats, plan_round,
                                 split_plan_by_worker, worker_stream_lengths)
from repro.data.device_cache import CachePlan, DeviceBatchCache
from repro.distributed.sharding import HostShardMap, WorkerShardMap
from repro.fl.round import (StepCompileCache, make_combine_step,
                            make_compressed_combine_step,
                            make_gather_round_step,
                            make_host_node_merge_step,
                            make_payload_decode_step, make_round_step,
                            make_shard_merge_step, make_worker_round_step)
from repro.fl.strategy import FedAvg, Strategy
from repro.obs import NULL_TRACER, critique_round


def s_bucket(s: int, *, base: int = 8) -> int:
    """Round S up to {base, base*1.5, base*2, ...}: O(log S) distinct
    compiled shapes, padding strictly < 1.5x (the sup of bucket(s)/s over
    s > base is 1.5, approached at s = base*2^k + 1 but never attained;
    e.g. base 8: s=9 -> 12 (1.33x), s=17 -> 24 (1.41x), s=33 -> 48 (1.45x))."""
    if s <= base:
        return base
    b = base
    while True:
        for m in (1.0, 1.5):
            cand = int(b * m)
            if s <= cand:
                return cand
        b *= 2


# StepCompileCache counters summed across the round's program caches
_COUNTERS = ("compiles", "evictions", "hits", "entries", "executables")


def _cat_parts(outs, i):
    """Concatenate worker/shard partial-output tuples along the W axis.
    i == 0 is the theta pytree (leafwise concat); 1/2 are the weight/loss
    stacks.  Host-side glue only — no arithmetic, so exactness holds."""
    if i == 0:
        return jax.tree.map(
            lambda *leaves: jnp.concatenate(leaves, axis=0),
            *[o[0] for o in outs])
    return jnp.concatenate([o[i] for o in outs], axis=0)


def _partial_to_numpy(part):
    """Wire form of one host's (theta, n, loss) partial for the
    process-per-host exchange: plain numpy trees (pickle-safe, and f32 →
    numpy → f32 is bit-exact, so shipping a partial through the coordinator
    never perturbs the reduction).  ``None`` (an all-holes block) passes
    through."""
    if part is None:
        return None
    theta, n, ls = part
    return (jax.tree.map(np.asarray, theta), np.asarray(n), np.asarray(ls))


def _slo_percentiles(rows) -> tuple[float, float]:
    """p50/p99 of the per-client round times in ``rows`` ([(type, x, t_c)]).

    Computed producer-side from whichever per-client times the prepare
    stage already has — synthetic draws or measured-mode predictions — so
    the SLO metrics exist at every pipeline depth and on the mesh path
    (which nulls the ``shares`` attribution list afterwards).
    """
    if not rows:
        return 0.0, 0.0
    ts = np.asarray([r[2] for r in rows], dtype=np.float64)
    p50, p99 = np.percentile(ts, [50.0, 99.0])
    return float(p50), float(p99)


def _probe_row_bytes(dataset, *, batch_size=None, seq_len=None) -> int:
    """Bytes of one packed batch row (all leaves), from a one-batch gather."""
    probe = dataset.gather_batches(np.asarray([0]), np.asarray([0]),
                                   batch_size=batch_size, seq_len=seq_len)
    return int(sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
                   for v in probe.values()))


@dataclass
class RoundResult:
    round_idx: int
    loss: float
    n_clients: int
    makespan: float          # simulated/measured wall time of slowest worker
    idle_time: float         # paper Table 2 metric
    useful_fraction: float   # padding efficiency of the compiled step
    wall_time: float         # actual host wall time of the round
    placement: str
    s_steps: int
    pack_time: float = 0.0         # host time packing this round's arrays
    overlap_fraction: float = 0.0  # fraction of pack hidden under execution
    recompiles: int = 0            # cumulative step compiles so far
    cache_hit_rate: float = 0.0    # device-cache step hit rate this round
    cache_bytes_saved: int = 0     # H2D bytes skipped via the device cache
    exec_time: float = 0.0         # measured device-execution wall seconds
    barrier_stall_s: float = 0.0   # producer stall at the refit barrier
    drift_fallback: bool = False   # placed by the BB fallback (drift alarm)
    affinity_swaps: int = 0        # cache-affinity client swaps this round
    padded_steps: int = 0          # dispatched-but-masked scan steps (the
    #                                idle time bucket_mode="worker" attacks)
    combine_bytes: int = 0         # cross-shard combine transfer (mesh path)
    residual_norm: float = 0.0     # L2 of the error-feedback residuals after
    #                                this round (compressed combine only)
    # -- deadline-SLO metrics (open-world population; see docs/POPULATION.md)
    slo_p50: float = 0.0           # median per-client round time (simulated
    #                                draws or prepare-time predictions)
    slo_p99: float = 0.0           # tail per-client round time — the
    #                                deadline-SLO gauge
    stale_fraction: float = 0.0    # cohort fraction drafted while OFFLINE
    #                                (the online pool could not fill it)
    online_pool: float = 0.0       # expected online-pool size at sample time
    #                                (0 for closed-registry samplers)
    # -- round critique (repro.obs; see docs/OBSERVABILITY.md) -------------
    idle_fraction: float = 0.0     # simulated worker-seconds left idle:
    #                                idle_time / (makespan * n_workers) —
    #                                deterministic, so the perf gate bands it
    critical_path: str = ""        # stage bounding this round's wall time:
    #                                exec | pack | barrier | combine
    #                                (timing-derived, like exec_time)


@dataclass
class EngineConfig:
    lanes_per_worker: int = 1
    steps_cap: int | None = 64
    rounds_per_checkpoint: int = 25
    s_bucket_base: int = 8
    batch_size: int | None = None
    seq_len: int | None = None
    agg_impl: str = "xla"
    grad_clip: float | None = None
    deadline_rho: float = 0.0     # >0 enables over-sample + trim
    seed: int = 1337
    pipeline_depth: int = 1       # 0 = sync; d >= 1 = prep t+1..t+d during t
    compile_cache_size: int = 8   # LRU cap on distinct compiled round steps
    donate_buffers: bool = True   # donate params+batches into the step
    device_cache_batches: int = 0  # HBM rows pinned for hot clients; 0 = off
    device_cache_bytes: int = 0    # HBM cache capacity in bytes; 0 = off
    # -- mesh execution (per-worker device programs) -----------------------
    mesh_workers: int = 0          # 0/1 = one fused program; K >= 2 = one
    #                                program per worker over K mesh shards
    cache_affinity: bool = False   # prefer the shard holding a client's rows
    bucket_mode: str = "round"     # "round": every worker program shares the
    #                                round's bucketed S (ONE executable);
    #                                "worker": each worker compiles at its own
    #                                bucketed S (O(log S) executables, fewer
    #                                padded steps for short workers)
    combine_mode: str = "flat"     # "flat": one global combine over all lane
    #                                partials; "tree": per-shard partial merge
    #                                before the cross-shard combine (§3.3)
    combine_compress: str = "none"  # compress each shard's merged partial
    #                                before the cross-shard combine: "none"
    #                                (exact, the bit-identity reference) |
    #                                "int8" (per-leaf symmetric quant) |
    #                                "topk" (sparsify, combine_topk_frac);
    #                                both delta-encode against the global
    #                                model with error-feedback residuals
    combine_topk_frac: float = 0.05  # fraction of entries topk sends per leaf
    hosts: int = 0                 # host level above the shard→root combine:
    #                                0 = legacy two-level tree (byte-identical
    #                                to pre-host builds); H >= 1 = the K mesh
    #                                shards partition into H contiguous host
    #                                blocks, each merging its shards locally
    #                                and shipping ONE partial to the root —
    #                                combine_bytes O(K) → O(H).  hosts=1 is
    #                                the single-host reference every hosts=H
    #                                run is bit-identical to (canonical
    #                                pairwise reduction; see HostShardMap).
    # -- control plane (repro.control): any non-default knob enables it ----
    telemetry_mode: str = "synthetic"   # "synthetic" | "measured"
    barrier_policy: str = "reuse"       # "reuse" | "stall" (measured mode)
    drift_threshold: float = 0.0        # residual EWMA alarm; 0 = off
    drift_window: int = 16
    adapt_interval: int = 0             # rounds per hill-climb move; 0 = off
    adapt_max_slots: int = 64
    adapt_granularity: str = "type"     # "type" | "worker" (per-wid slots)

    def __post_init__(self):
        depth = self.pipeline_depth
        if not isinstance(depth, int) or depth < 0:
            raise ValueError(
                f"pipeline_depth must be an int >= 0, got {depth!r}")
        if self.device_cache_batches < 0:
            raise ValueError("device_cache_batches must be >= 0, got "
                             f"{self.device_cache_batches!r}")
        if self.device_cache_bytes < 0:
            raise ValueError("device_cache_bytes must be >= 0, got "
                             f"{self.device_cache_bytes!r}")
        if not isinstance(self.mesh_workers, int) or self.mesh_workers < 0:
            raise ValueError("mesh_workers must be an int >= 0, got "
                             f"{self.mesh_workers!r}")
        if self.cache_affinity:
            if self.mesh_workers < 2:
                raise ValueError(
                    "cache_affinity requires mesh_workers >= 2 (with one "
                    "shard there is no 'other' pool to prefer)")
            if self.device_cache_batches <= 0 and self.device_cache_bytes <= 0:
                raise ValueError(
                    "cache_affinity requires an enabled device cache "
                    "(device_cache_batches or device_cache_bytes)")
        if self.bucket_mode not in ("round", "worker"):
            raise ValueError("bucket_mode must be 'round' or 'worker', "
                             f"got {self.bucket_mode!r}")
        if self.bucket_mode == "worker" and self.mesh_workers < 2:
            # Mirrors the mesh/strategy check: the fused single program has
            # exactly one S — there is no per-worker program to bucket.
            raise ValueError(
                "bucket_mode='worker' requires mesh_workers >= 2 (the fused "
                "single-program path has one shared stream length; only the "
                "per-worker mesh programs can compile at their own S)")
        if self.combine_mode not in ("flat", "tree"):
            raise ValueError("combine_mode must be 'flat' or 'tree', "
                             f"got {self.combine_mode!r}")
        if self.combine_mode == "tree" and self.mesh_workers < 2:
            raise ValueError(
                "combine_mode='tree' requires mesh_workers >= 2 (with one "
                "shard there is no shard-local partial merge to run before "
                "the cross-shard combine)")
        if self.combine_compress not in ("none", "int8", "topk"):
            raise ValueError("combine_compress must be 'none', 'int8' or "
                             f"'topk', got {self.combine_compress!r}")
        if self.combine_compress != "none" and self.combine_mode != "tree":
            # Compression acts on a SHARD's merged partial — the §3.3
            # hierarchy's node→server upload.  The flat combine ships raw
            # lane partials and stays the bit-identity reference; silently
            # compressing it would blur which path is exact.
            raise ValueError(
                "combine_compress requires combine_mode='tree' (and hence "
                "mesh_workers >= 2): only the per-shard merged partials of "
                "the hierarchical combine have a shard→root upload to "
                "compress; the flat combine is the exact reference path")
        if not 0.0 < self.combine_topk_frac <= 1.0:
            raise ValueError("combine_topk_frac must be in (0, 1], got "
                             f"{self.combine_topk_frac!r}")
        if not isinstance(self.hosts, int) or self.hosts < 0:
            raise ValueError(f"hosts must be an int >= 0, got {self.hosts!r}")
        if self.hosts >= 1:
            if self.combine_mode != "tree" or self.mesh_workers < 2:
                raise ValueError(
                    "hosts >= 1 requires combine_mode='tree' and "
                    "mesh_workers >= 2: the host level sits above the "
                    "shard-local merges of the hierarchical combine — the "
                    "flat combine and the fused single program have no "
                    "shard partials to group into host blocks")
            if self.mesh_workers % self.hosts != 0:
                raise ValueError(
                    f"hosts ({self.hosts}) must divide mesh_workers "
                    f"({self.mesh_workers}): host blocks are equal "
                    "contiguous shard ranges")
            blk = self.mesh_workers // self.hosts
            if self.hosts >= 2 and blk & (blk - 1):
                raise ValueError(
                    f"shards-per-host ({blk}) must be a power of two for "
                    "hosts >= 2 — only aligned pow2 blocks are exact "
                    "subtrees of the canonical pairwise combine, which is "
                    "what keeps losses bit-identical across host counts")
        if self.adapt_granularity not in ("type", "worker"):
            raise ValueError("adapt_granularity must be 'type' or 'worker', "
                             f"got {self.adapt_granularity!r}")
        if self.compile_cache_size < 1:
            raise ValueError("compile_cache_size must be >= 1, got "
                             f"{self.compile_cache_size!r}")
        if self.telemetry_mode not in ("synthetic", "measured"):
            raise ValueError("telemetry_mode must be 'synthetic' or "
                             f"'measured', got {self.telemetry_mode!r}")
        if self.barrier_policy not in ("reuse", "stall"):
            raise ValueError("barrier_policy must be 'reuse' or 'stall', "
                             f"got {self.barrier_policy!r}")
        if self.barrier_policy == "stall" and self.telemetry_mode != "measured":
            # Silently inert would be worse than loud: the barrier only
            # exists for measured telemetry (synthetic draws happen at
            # prepare time and never need gating).
            raise ValueError("barrier_policy='stall' requires "
                             "telemetry_mode='measured' (synthetic "
                             "telemetry is drawn at prepare time; there is "
                             "no finish-time barrier to stall on)")
        if self.drift_threshold < 0:
            raise ValueError("drift_threshold must be >= 0, got "
                             f"{self.drift_threshold!r}")
        if self.adapt_interval < 0:
            raise ValueError("adapt_interval must be >= 0, got "
                             f"{self.adapt_interval!r}")

    @property
    def control_enabled(self) -> bool:
        return (self.telemetry_mode == "measured"
                or self.drift_threshold > 0 or self.adapt_interval > 0)


@dataclass
class _PreparedRound:
    """Everything round t needs, produced (possibly on the producer thread)
    before the device is asked to run it."""

    t: int
    clients: list
    workers: list
    assignment: Assignment
    arrays: RoundArrays
    device: tuple | None     # (batches, step_mask, boundary, weight) on
    #                          device — None on the mesh path (per-worker
    #                          bundles live in worker_programs instead)
    pack_s: float            # host pack time (plan + gather + scatter)
    makespan: float          # simulated/predicted round time (prepare time)
    idle_time: float
    overlap_s: float = 0.0   # portion of pack_s hidden under execution
    cache_plan: CachePlan | None = None
    n_steps_real: int = 0    # unpadded step count (throughput accounting)
    shares: list | None = None  # (type, x, pred) attribution weights (measured)
    stall_s: float = 0.0     # producer stall at the refit barrier
    fallback: bool = False   # placed by the drift fallback (BB)
    sampler_st: dict | None = None  # RNG/config snapshot after this sample
    telemetry_st: dict | None = None  # synthetic-telemetry RNG snapshot
    exec_t0: float = 0.0     # consumer-set: execution dispatch timestamp
    exec_s: float = 0.0      # measured execution wall time (consumer-set)
    combine_t0: float = 0.0  # consumer-set: cross-shard combine dispatch
    combine_s: float = 0.0   # measured combine wall (dispatch -> loss sync)
    control_st: dict | None = None  # control-plane snapshot after this prep
    # -- mesh execution (per-worker device programs) -----------------------
    worker_programs: list | None = None
    # [(wid, type_name, shard, device_arrays, cache_plan, xs, pred_s)]
    combine_masks: tuple | None = None  # full (mask, boundary, weight) on dev
    affinity_swaps: int = 0  # cache-affinity swap count this round
    worker_times: list | None = None
    # consumer-set: [(wid, type_name, xs, pred_s, meas_s)]
    padded_steps: int = 0    # dispatched-but-masked scan steps this round
    combine_bytes: int = 0   # consumer-set: cross-shard combine transfer
    residual_norm: float = 0.0  # consumer-set: error-feedback residual L2
    # -- deadline-SLO metrics, computed producer-side in round order -------
    slo_p50: float = 0.0
    slo_p99: float = 0.0
    stale_fraction: float = 0.0
    online_pool: float = 0.0


class FederatedEngine:
    """Composable engine: dataset x model(loss_fn, params) x optimizer x
    placement x sampler x worker pool (+ telemetry source)."""

    def __init__(self, *, dataset, loss_fn, init_params, optimizer, placement: Placement,
                 sampler, pool, telemetry=None, strategy: Strategy | None = None,
                 config: EngineConfig | None = None, checkpoint_store=None,
                 eval_fn=None, obs=None):
        # None-defaults: dataclass instances must be per-engine, or telemetry
        # counters / config mutations would leak across engines.
        strategy = FedAvg() if strategy is None else strategy
        config = EngineConfig() if config is None else config
        self.dataset = dataset
        self.loss_fn = loss_fn
        self.params = init_params
        self.optimizer = optimizer
        self.placement = placement
        self.sampler = sampler
        self.pool = pool
        self.telemetry = telemetry
        self.strategy = strategy
        self.cfg = config
        self.ckpt = checkpoint_store
        self.eval_fn = eval_fn
        self.round_idx = 0
        self.history: list[RoundResult] = []
        # Rounds t .. t+depth are in flight at once, so the host buffer ring
        # needs depth+1 slot sets: the producer never rewrites a slot whose
        # device copy may still be pending.  (EngineConfig.__post_init__
        # rejects negative depths.)
        self._pack_buffers = PackBuffers(depth=config.pipeline_depth + 1)
        self._sampler_ckpt_state = None
        self._telemetry_ckpt_state = None
        self._control_ckpt_state = None
        # Observability bundle (repro.obs).  The tracer is threaded through
        # the full round lifecycle unconditionally; when no bundle rides
        # along every site hits the constant-time NULL_TRACER no-ops, and
        # span bookkeeping never touches an RNG path either way — losses
        # are bit-identical with tracing on or off (test-enforced).
        self.obs = obs
        self._tracer = obs.tracer if obs is not None else NULL_TRACER
        self._metrics = obs.metrics if obs is not None else None
        self._ctl_log_seen = 0
        if config.control_enabled:
            # Deferred import: repro.control imports repro.core.placement,
            # so a module-level import here would cycle through the package.
            from repro.control.controller import (ControlPlane,
                                                  ControllerConfig)
            self.control = ControlPlane(
                ControllerConfig(
                    telemetry_mode=config.telemetry_mode,
                    barrier_policy=config.barrier_policy,
                    drift_threshold=config.drift_threshold,
                    drift_window=config.drift_window,
                    adapt_interval=config.adapt_interval,
                    adapt_max_slots=config.adapt_max_slots,
                    adapt_granularity=config.adapt_granularity),
                placement=placement, pool=pool)
        else:
            self.control = None
        # Mesh execution: one device program per worker over K shards
        # (mesh_workers <= 1 keeps the single fused program — the 1-shard
        # special case IS that program).
        self._mesh_shards = (config.mesh_workers
                             if config.mesh_workers >= 2 else 0)
        self._shard_devices = []
        self._combine_root = None
        self._shard_params: dict = {}
        if self._mesh_shards:
            if not strategy.associative:
                raise ValueError(
                    "mesh_workers >= 2 requires an associative strategy: "
                    "the gather path ships every client model and reduces "
                    "host-side in one shot — it has no per-worker partials "
                    "to combine")
            from repro.launch.mesh import fl_combine_topology
            devs, root = fl_combine_topology(self._mesh_shards)
            if len(set(devs)) == 1 and devs[0] == jax.devices()[0]:
                # Single-device host: every shard resolves to the default
                # device anyway — leave arrays UNCOMMITTED (device=None) so
                # jit sees the same arg shardings as the fused path and
                # never silently recompiles between rounds 0 and 1 (an
                # explicitly committed input changes the lowering key once
                # params become jit outputs).
                devs = []
            else:
                # Multi-device mesh: each worker program runs on its
                # shard's device, and the combine runs on the root (§3.3's
                # server side), which also holds the global params between
                # rounds.  Committing them there from the start keeps
                # every round's arg shardings equal to round 0's.
                self._combine_root = root
                self.params = jax.device_put(init_params, root)
            self._shard_devices = devs
        cache_rows = config.device_cache_batches
        row_bytes = 0
        if config.device_cache_bytes > 0:
            # Byte capacity -> rows: probe one batch for the per-row size
            # (leaf shapes are uniform across clients by construction).
            row_bytes = _probe_row_bytes(dataset, batch_size=config.batch_size,
                                         seq_len=config.seq_len)
        self._device_cache = (
            DeviceBatchCache(cache_rows,
                             capacity_bytes=config.device_cache_bytes,
                             row_bytes=row_bytes,
                             compile_cache_size=config.compile_cache_size,
                             n_shards=self._mesh_shards or 1,
                             devices=self._shard_devices)
            if (cache_rows > 0 or config.device_cache_bytes > 0) else None)
        donate = "all" if config.donate_buffers else "none"
        step_donate_argnums = None
        if self._device_cache is not None and config.donate_buffers:
            # The batches argument is the cache's persistent device-side
            # round base, which must survive the step — donate params and
            # masks only (argnums 0, 2, 3, 4; batches is argnum 1).
            step_donate_argnums = (0, 2, 3, 4)
        if not strategy.associative:
            # The gather path reuses global_params after the step (the
            # strategy's host-side reduce), so params cannot be donated.
            self._gather_step = StepCompileCache(
                lambda: make_gather_round_step(loss_fn, optimizer,
                                               grad_clip=config.grad_clip),
                capacity=config.compile_cache_size, donate="none")
            self._round_step = None
            self._step_cache = self._gather_step
        else:
            self._round_step = StepCompileCache(
                lambda: make_round_step(loss_fn, optimizer,
                                        agg_impl=config.agg_impl,
                                        grad_clip=config.grad_clip),
                capacity=config.compile_cache_size, donate=donate,
                donate_argnums=step_donate_argnums)
            self._gather_step = None
            self._step_cache = self._round_step
        self._worker_step = None
        self._combine_step = None
        self._merge_step = None
        # Cross-shard combine transfer accounting (mesh path): one lane
        # partial is a params-shaped theta plus its weight and loss scalars.
        self._partial_bytes = int(sum(
            int(np.prod(np.shape(leaf))) * np.dtype(
                getattr(leaf, "dtype", np.float32)).itemsize
            for leaf in jax.tree.leaves(init_params))) + 8
        if self._mesh_shards:
            # Per-worker programs share ONE executable with
            # bucket_mode="round" (every worker is a [1, P, S] block at the
            # round's bucketed S); bucket_mode="worker" compiles one per
            # distinct per-worker S bucket (O(log S)) + one combine.
            worker_donate = None
            if config.donate_buffers:
                # Batches donate unless they are the device cache's
                # persistent per-worker round base; masks always donate.
                # Params (argnum 0) never donate here — every worker
                # program and the combine read them.
                worker_donate = ((2, 3, 4) if self._device_cache is not None
                                 else (1, 2, 3, 4))
            self._worker_step = StepCompileCache(
                lambda: make_worker_round_step(loss_fn, optimizer,
                                               agg_impl=config.agg_impl,
                                               grad_clip=config.grad_clip),
                capacity=config.compile_cache_size, donate="none",
                donate_argnums=worker_donate)
            self._combine_step = StepCompileCache(
                lambda: make_combine_step(),
                capacity=config.compile_cache_size, donate="none",
                donate_argnums=(0,) if config.donate_buffers else ())
            if config.combine_mode == "tree":
                # Per-shard partial merge (§3.3 hierarchy).  No donation:
                # the [1, 1, ...] merged outputs cannot alias the [W_s, P,
                # ...] lane-partial inputs, so donating would only emit
                # unusable-buffer warnings.
                self._merge_step = StepCompileCache(
                    lambda: make_shard_merge_step(),
                    capacity=config.compile_cache_size, donate="none")
        # Host hierarchy (hosts >= 1): shard partials combine through the
        # canonical pairwise tree — per-host blocks first, then the root
        # over one partial per host.  The 2-ary node program is shared by
        # every tree level.  _host_rank / _host_exchange / _round_observer
        # are the process-per-host harness's seams (launch/multihost.py):
        # rank r executes only its block's worker programs and all-gathers
        # host partials through the exchange; the observer ships per-round
        # control rows onto the sidecar channel.  All three default to the
        # in-process path (None), which computes every block locally.
        self._host_map = None
        self._host_node_step = None
        self._decode_step = None
        self._host_rank: int | None = None
        self._host_exchange = None
        self._round_observer = None
        if config.hosts >= 1:
            self._host_map = HostShardMap.build(self._mesh_shards,
                                                config.hosts)
            self._host_node_step = StepCompileCache(
                lambda: make_host_node_merge_step(),
                capacity=config.compile_cache_size, donate="none")
            if config.combine_compress != "none":
                self._decode_step = StepCompileCache(
                    lambda: make_payload_decode_step(config.combine_compress),
                    capacity=config.compile_cache_size, donate="none")
        # Compressed cross-shard combine (combine_compress != "none"): the
        # shard→root payload is a delta-encoded int8/topk tree instead of a
        # dense partial, with per-shard error-feedback residuals owned by
        # the compressor (consumer-side, strict round order — same ownership
        # as params).  The "none" path above stays byte-for-byte untouched.
        self._compress = None
        self._encode_step = None
        self._compressed_combine_step = None
        if config.combine_compress != "none":
            from repro.compress import CombineCompressor, make_encode_step
            self._compress = CombineCompressor(
                config.combine_compress, init_params,
                topk_frac=config.combine_topk_frac)
            self._encode_step = StepCompileCache(
                lambda: make_encode_step(config.combine_compress,
                                         config.combine_topk_frac),
                capacity=config.compile_cache_size, donate="none")
            self._compressed_combine_step = StepCompileCache(
                lambda: make_compressed_combine_step(
                    config.combine_compress, agg_impl=config.agg_impl),
                capacity=config.compile_cache_size, donate="none",
                donate_argnums=(0,) if config.donate_buffers else ())
        # Persistent per-shard sync pool (engine lifetime): spawning and
        # joining an executor inside every round's _execute_mesh would add
        # thread churn to exactly the window measured as exec_s.
        self._sync_pool = (
            ThreadPoolExecutor(max_workers=self._mesh_shards,
                               thread_name_prefix="pollen-sync")
            if self._mesh_shards else None)
        if obs is not None:
            # Compile-event instants: every step cache reports fresh
            # lowerings to the tracer (labelled by cache role), and the
            # device cache books its producer-side plan() as a span.
            for label, cache in (("round_step", self._round_step),
                                 ("gather_step", self._gather_step),
                                 ("worker_step", self._worker_step),
                                 ("combine_step", self._combine_step),
                                 ("merge_step", self._merge_step),
                                 ("host_node_step", self._host_node_step),
                                 ("decode_step", self._decode_step),
                                 ("encode_step", self._encode_step),
                                 ("compressed_combine_step",
                                  self._compressed_combine_step)):
                if cache is not None:
                    cache.tracer = self._tracer
                    cache.trace_label = label
            if self._device_cache is not None:
                self._device_cache.tracer = self._tracer

    # -- helpers -------------------------------------------------------------
    @property
    def _compiles_total(self) -> int:
        n = self._step_cache.compiles
        if self._worker_step is not None:
            n += self._worker_step.compiles + self._combine_step.compiles
        if self._merge_step is not None:
            n += self._merge_step.compiles
        if self._host_node_step is not None:
            n += self._host_node_step.compiles
        if self._decode_step is not None:
            n += self._decode_step.compiles
        if self._compress is not None:
            n += (self._encode_step.compiles
                  + self._compressed_combine_step.compiles)
        return n

    @property
    def compile_stats(self) -> dict:
        """Recompile/eviction/hit counters of the round-step cache(s).  On
        the mesh path the totals fold in the per-worker and combine
        programs (also broken out under ``worker_step`` / ``combine_step``
        and, with ``combine_mode="tree"``, ``merge_step``)."""
        stats = self._step_cache.stats()
        if self._worker_step is not None:
            ws, cs = self._worker_step.stats(), self._combine_step.stats()
            for k in _COUNTERS:
                stats[k] = stats[k] + ws[k] + cs[k]
            stats["worker_step"] = ws
            stats["combine_step"] = cs
            if self._merge_step is not None:
                ms = self._merge_step.stats()
                for k in _COUNTERS:
                    stats[k] = stats[k] + ms[k]
                stats["merge_step"] = ms
            if self._host_node_step is not None:
                hs = self._host_node_step.stats()
                for k in _COUNTERS:
                    stats[k] = stats[k] + hs[k]
                stats["host_node_step"] = hs
            if self._compress is not None:
                es = self._encode_step.stats()
                ccs = self._compressed_combine_step.stats()
                for k in _COUNTERS:
                    stats[k] = stats[k] + es[k] + ccs[k]
                stats["encode_step"] = es
                stats["compressed_combine_step"] = ccs
        return stats

    @property
    def cache_stats(self) -> dict:
        """Aggregate device-batch-cache counters (empty dict when off)."""
        return self._device_cache.stats() if self._device_cache else {}

    @property
    def control_stats(self) -> dict:
        """Control-plane counters (barrier/drift/concurrency; {} when off)."""
        return self.control.stats() if self.control is not None else {}

    def _to_shard(self, tree, shard: int):
        """``tree`` committed to ``shard``'s device (unchanged when every
        shard shares the default device)."""
        if not self._shard_devices:
            return tree
        return jax.device_put(tree, self._shard_devices[shard])

    def _params_on(self, shard: int):
        """The global params on ``shard``'s device: one copy per shard per
        round (``_execute_mesh`` resets the map), shared by every worker
        program and encode step there."""
        if not self._shard_devices:
            return self.params
        if shard not in self._shard_params:
            self._shard_params[shard] = self._to_shard(self.params, shard)
        return self._shard_params[shard]

    def _to_root(self, tree):
        """``tree`` committed to the combine root (unchanged on a
        single-device host): the one cross-device hop of each partial."""
        if self._combine_root is None:
            return tree
        return jax.device_put(tree, self._combine_root)

    def _s_align(self, s_real: int) -> int:
        return s_bucket(s_real, base=self.cfg.s_bucket_base)

    def _cohort(self, t: int) -> list[ClientInfo]:
        if self.cfg.deadline_rho > 0:
            from repro.distributed.elastic import deadline_trim, oversample_cohort
            ids = oversample_cohort(self.sampler, t, rho=self.cfg.deadline_rho)
            clients = [self._client_info(int(c)) for c in ids]
            predict = None
            if isinstance(self.placement, LearningBasedPlacement) and self.placement.models:
                ms = [m for m in self.placement.models.values() if m.ready]
                if ms:
                    predict = ms[0].predict
            return deadline_trim(clients, self.sampler.cohort_size, predict)
        ids = self.sampler.sample(t)
        return [self._client_info(int(c)) for c in ids]

    def _client_info(self, cid: int) -> ClientInfo:
        return ClientInfo(cid=cid, n_batches=self.dataset.n_batches(cid),
                          n_samples=self.dataset.n_samples(cid))

    def _accumulate_loads(self, assignment: Assignment, workers, time_fn
                          ) -> tuple[float, float, list, dict]:
        """Fold ``time_fn(worker, client)`` over the assignment; return
        (makespan, idle_time, rows, loads) with rows = [(type, n_batches,
        t_c)] in iteration order (the order every consumer depends on) and
        loads = per-wid concurrency-scaled totals (the per-worker predicted
        times the mesh path compares measurements against)."""
        by_wid = {w.wid: w for w in workers}
        loads: dict[int, float] = {}
        rows: list = []
        for wid, clients in assignment.per_worker.items():
            w = by_wid[wid]
            total = 0.0
            for c in clients:
                t_c = time_fn(w, c)
                total += t_c
                rows.append((w.type_name, c.n_batches, t_c))
            loads[wid] = total / max(w.concurrency, 1)
        makespan = max(loads.values()) if loads else 0.0
        idle = sum(makespan - v for v in loads.values())
        return makespan, idle, rows, loads

    def _record_telemetry(self, t: int, assignment: Assignment, workers
                          ) -> tuple[float, float, list]:
        """Append per-client times; return (makespan, idle_time, rows).

        With a synthetic source the per-client ground truth reproduces the
        paper's measurement loop; with ``telemetry=None`` we fall back to
        batch counts as the time proxy.  Called from ``_prepare_round`` (the
        producer thread) so that telemetry draws and ``placement.observe``
        happen in strict round order regardless of pipeline depth — the
        simulated times depend only on the assignment, never on device
        results, so prepare-time recording is order-equivalent to the old
        finish-time recording.  ``rows`` — ``[(type, x, t_c)]`` — feeds the
        control plane's drift detector (out-of-sample residuals: the round-t
        fit predates these draws).
        """
        def draw(w, c):
            if self.telemetry is not None:
                return self.telemetry.sample_time(w.type_name, c.n_batches,
                                                  concurrency=w.concurrency)
            return float(c.n_batches) / max(w.speed, 1e-9)

        makespan, idle, rows, _ = self._accumulate_loads(assignment, workers,
                                                         draw)
        if isinstance(self.placement, LearningBasedPlacement):
            for tname, x, t_c in rows:
                self.placement.observe_type(t, tname, x, t_c)
        return makespan, idle, rows

    def _predict_round(self, t: int, assignment: Assignment, workers
                       ) -> tuple[float, float, list, dict]:
        """Measured mode's prepare-time half: PREDICT per-client times (no
        synthetic draws, no ``observe``) and return the attribution shares
        the consumer will spread the measured execution time over, plus the
        per-wid predicted loads (the mesh path's drift reference).

        Falls back to batch-count/speed proxies until the per-type model is
        ready — exactly the warm-up the paper's RR rounds provide.
        """
        models = (self.placement.models
                  if isinstance(self.placement, LearningBasedPlacement)
                  else {})

        def predict(w, c):
            m = models.get(w.type_name)
            if m is not None and m.ready:
                return float(m.predict(float(c.n_batches)))
            return float(c.n_batches) / max(w.speed, 1e-9)

        return self._accumulate_loads(assignment, workers, predict)

    # -- the pipeline stages ---------------------------------------------------
    def _prepare_round(self, t: int) -> _PreparedRound:
        """Host-side producer: sample, place, record telemetry, pack, start
        the H2D transfer.

        Runs on the pipeline's single producer thread for rounds t+1..t+depth
        while the device executes round t.  EVERY host-state mutation lives
        here (pool events, sampler RNG, refit, telemetry, device-cache LRU),
        so the mutation order is the round order whatever the depth — the
        consumer half only touches params, the step cache, device pools and
        the results list.
        """
        tp0 = time.perf_counter()
        tr = self._tracer
        fired = self.pool.advance_to(t)
        ctl = self.control
        stall_s, fallback = 0.0, False
        if ctl is not None:
            if fired:
                ctl.on_pool_events(t, fired)
            # The closed loop's producer half: flush barrier-released
            # measured telemetry into the model (policy "stall" blocks here
            # until round t-2 has finished executing), update drift stats,
            # and apply any pending slot-count move to the pool — all before
            # the snapshot/refit below, all in strict round order.
            with tr.span("prep.barrier", t=t):
                pre = ctl.pre_round(t)
            stall_s, fallback = pre.stall_s, pre.fallback
        workers = self.pool.snapshot()
        if isinstance(self.placement, LearningBasedPlacement):
            # The paper's protocol, literally: the fit for round t runs
            # while earlier rounds train (here: on the pack thread, during
            # the in-flight rounds' device execution) and TrainingTimeModel
            # enforces the data <= t-2 cutoff.  Fitting here — not in the
            # consumer tail — makes the model any assignment sees identical
            # across pipeline depths and across split run() calls.
            with tr.span("prep.refit", t=t):
                self.placement.refit(t)
        with tr.span("prep.sample", t=t):
            clients = self._cohort(t)
        sampler_st = sampler_state(self.sampler)
        place = (ctl.fallback_placement
                 if (fallback and ctl is not None) else self.placement)
        assignment = place.assign(clients, workers)
        mesh_map = None
        n_swaps = 0
        if self._mesh_shards:
            mesh_map = WorkerShardMap.build(workers, self._mesh_shards,
                                            devices=self._shard_devices)
            if self._device_cache is not None:
                # Orphan-shard reclamation: a shard whose last worker died
                # would otherwise strand its capacity_rows/K pool until a
                # matching wid rejoins.  Rebalance redistributes the dead
                # shard's row budget over the survivors (and hands it back
                # on rejoin) — producer-side, in round order, so the LRU
                # consequences are deterministic at any pipeline depth.
                ev = self._device_cache.rebalance(mesh_map.live_shards())
                if ev is not None and ctl is not None:
                    ctl.on_cache_rebalance(t, ev)
            if self.cfg.cache_affinity and self._device_cache is not None:
                # Load-neutral swap pass: move cached clients toward the
                # shard already holding their rows (equal batch count +
                # equal worker type, so every placement metric is
                # preserved; only the cache hit pattern improves).  A
                # shard that lost its last worker to churn is excluded —
                # its stranded entries must not steer swaps toward a
                # shard nothing can execute on (rebalance above already
                # dropped them; the filter below is the belt to that
                # suspender).
                assignment, n_swaps = apply_cache_affinity(
                    assignment, workers, mesh_map.shard_of_wid,
                    self._device_cache.shard_for_client,
                    live_shards=mesh_map.live_shards())
        shares = None
        loads: dict = {}
        if self.cfg.telemetry_mode == "measured":
            makespan, idle, shares, loads = self._predict_round(
                t, assignment, workers)
            time_rows = shares
            if mesh_map is not None:
                # Per-worker programs sync individually: worker times are
                # measured exactly, the round-level predicted-share
                # attribution path is never used (test-enforced).
                shares = None
        else:
            makespan, idle, rows = self._record_telemetry(t, assignment,
                                                          workers)
            time_rows = rows
            if ctl is not None:
                ctl.round_prepared(t, makespan=makespan,
                                   n_clients=len(clients), rows=rows)
        # Deadline-SLO metrics, producer-side in round order: per-client
        # time percentiles from the rows above, plus the online-pool stats
        # the sampler published for THIS round's draw (same thread, read
        # immediately — depth-invariant like every other producer mutation).
        slo_p50, slo_p99 = _slo_percentiles(time_rows)
        pop_stats = getattr(self.sampler, "last_stats", None) or {}
        stale_fraction = float(pop_stats.get("stale_fraction", 0.0))
        online_pool = float(pop_stats.get("online_pool", 0.0))
        # Snapshot the synthetic-telemetry RNG AFTER this round's draws
        # (mirrors the sampler snapshot): the checkpoint for round_idx = t+1
        # must resume the stream exactly where round t left it, regardless
        # of how far ahead the depth-pipelined producer has drawn.
        telemetry_st = (self.telemetry.state_dict()
                        if hasattr(self.telemetry, "state_dict") else None)
        # Control-plane snapshot AFTER every producer-side control mutation
        # of this round (pool events, barrier flush, drift update, slot
        # moves) — adopted at finish time into the checkpoint sidecar so a
        # restore resumes the loop mid-hysteresis instead of re-warming.
        control_st = ctl.state_dict() if ctl is not None else None
        if ctl is not None and tr.enabled:
            # Controller decisions (slot moves, pool fail/join resets,
            # cache rebalances) become instants by diffing the decision
            # log — producer-side, so no ControlPlane API grows tracer
            # awareness and the control path stays byte-identical.  Drift
            # trips surface through the fallback flag below.
            log = ctl.log
            for rnd, kind, detail in log[self._ctl_log_seen:]:
                tr.instant("ctl." + str(kind), round=int(rnd),
                           detail=str(detail))
            self._ctl_log_seen = len(log)
            if fallback:
                tr.instant("ctl.drift_fallback", round=t)
        plan = plan_round(assignment, workers,
                          lanes_per_worker=self.cfg.lanes_per_worker,
                          steps_cap=self.cfg.steps_cap, min_steps=1)
        cache_plan = None
        worker_programs = None
        combine_masks = None
        if mesh_map is not None:
            # Mesh path: one device program per worker.  Masks and (without
            # the cache) content are packed ONCE at full [W, P, S] size and
            # sliced per worker for the per-shard device_puts; the full
            # masks also ship once for the combine program's metrics.
            S = self._s_align(plan.s_real)
            if self.cfg.bucket_mode == "worker":
                # Each worker's program runs at its OWN bucketed stream
                # length: trailing steps beyond it are masked no-ops in
                # bucket_mode="round" (bitwise, via the guarded fold), so
                # truncating them changes padded work only — never values.
                worker_S = [self._s_align(int(s))
                            for s in worker_stream_lengths(plan)]
            else:
                worker_S = [S] * plan.W
            padded = int(sum(worker_S)) * plan.P - plan.n_steps_total
            with tr.span("prep.pack", t=t, S=S, W=plan.W):
                if self._device_cache is not None:
                    arrays = build_round_masks(plan, S,
                                               buffers=self._pack_buffers)
                else:
                    arrays = build_round_arrays(
                        self.dataset, plan=plan,
                        batch_size=self.cfg.batch_size,
                        seq_len=self.cfg.seq_len,
                        s_align=lambda s: S, buffers=self._pack_buffers)
                worker_programs = self._pack_worker_programs(
                    t, plan, worker_S, arrays, assignment, workers,
                    mesh_map, loads)
            pack_s = time.perf_counter() - tp0
            with tr.span("prep.h2d", t=t):
                root = self._combine_root
                combine_masks = (jax.device_put(arrays.step_mask, root),
                                 jax.device_put(arrays.boundary, root),
                                 jax.device_put(arrays.weight, root))
            return _PreparedRound(t=t, clients=clients, workers=workers,
                                  assignment=assignment, arrays=arrays,
                                  device=None, pack_s=pack_s,
                                  makespan=makespan, idle_time=idle,
                                  n_steps_real=plan.n_steps_total,
                                  shares=shares, stall_s=stall_s,
                                  fallback=fallback, sampler_st=sampler_st,
                                  telemetry_st=telemetry_st,
                                  control_st=control_st,
                                  worker_programs=worker_programs,
                                  combine_masks=combine_masks,
                                  affinity_swaps=n_swaps,
                                  padded_steps=padded,
                                  slo_p50=slo_p50, slo_p99=slo_p99,
                                  stale_fraction=stale_fraction,
                                  online_pool=online_pool)
        with tr.span("prep.pack", t=t):
            if self._device_cache is not None:
                # Cache path: no full-size host batch buffer exists at all
                # — masks are built host-side as usual, but content travels
                # as a compact [n_miss, ...] array and the device assembles
                # the round from it (misses + pool hits) in _execute.
                S = self._s_align(plan.s_real)
                cache_plan = self._device_cache.plan(plan, S, t)
                arrays = build_round_masks(plan, S,
                                           buffers=self._pack_buffers)
                host_batches = gather_content_rows(
                    self.dataset, plan, cache_plan.content_mask,
                    cache_plan.n_miss_rows, batch_size=self.cfg.batch_size,
                    seq_len=self.cfg.seq_len, buffers=self._pack_buffers)
            else:
                arrays = build_round_arrays(
                    self.dataset, plan=plan,
                    batch_size=self.cfg.batch_size,
                    seq_len=self.cfg.seq_len,
                    s_align=self._s_align, buffers=self._pack_buffers)
                host_batches = arrays.batches
        pack_s = time.perf_counter() - tp0
        # Explicit async H2D: transfers overlap the in-flight round's compute.
        # (Cache path: host_batches is the compact miss transfer only.)
        with tr.span("prep.h2d", t=t):
            device = (jax.device_put(host_batches),
                      jax.device_put(arrays.step_mask),
                      jax.device_put(arrays.boundary),
                      jax.device_put(arrays.weight))
        return _PreparedRound(t=t, clients=clients, workers=workers,
                              assignment=assignment, arrays=arrays,
                              device=device, pack_s=pack_s,
                              makespan=makespan, idle_time=idle,
                              cache_plan=cache_plan,
                              n_steps_real=plan.n_steps_total,
                              shares=shares, stall_s=stall_s,
                              fallback=fallback, sampler_st=sampler_st,
                              telemetry_st=telemetry_st,
                              control_st=control_st,
                              padded_steps=(arrays.step_mask.size
                                            - plan.n_steps_total),
                              slo_p50=slo_p50, slo_p99=slo_p99,
                              stale_fraction=stale_fraction,
                              online_pool=online_pool)

    def _pack_worker_programs(self, t, plan, worker_S, arrays, assignment,
                              workers, mesh_map, loads):
        """Producer half of the mesh path: one (device-arrays, cache-plan)
        bundle per worker, H2D'd to that worker's shard device.

        ``worker_S[wi]`` is worker ``wi``'s compiled stream length: the
        round's shared bucketed S (``bucket_mode="round"`` — all programs
        compile to ONE executable) or the worker's own bucket
        (``bucket_mode="worker"`` — O(log S) executables, shorter workers
        skip their trailing padded steps).  Arrays are packed once at the
        round's full S and sliced ``[:, :, :S_w]`` per worker (numpy views
        — no copies before the transfer).  With the device cache on, each
        worker's content travels as its own compact miss array planned
        against its shard's pool at that worker's S."""
        order = sorted(workers, key=lambda w: w.wid)
        subplans = (split_plan_by_worker(plan)
                    if self._device_cache is not None else None)
        slot_counts: dict[int, int] = {}
        programs = []
        for wi, w in enumerate(order):
            shard = mesh_map.shard_of(w.wid)
            dev = mesh_map.device_for(w.wid)
            slot = slot_counts.get(shard, 0)
            slot_counts[shard] = slot + 1
            xs_all = [c.n_batches
                      for c in assignment.per_worker.get(w.wid, [])]
            if (self._host_rank is not None
                    and self._host_map.host_of(shard) != self._host_rank):
                # Process-per-host harness: another host owns this shard.
                # The producer stays fully replicated up to here (sampling,
                # placement, packing — all host-state mutations, so every
                # rank's RNG streams agree), but the H2D transfer and the
                # device program are that host's job; keep the positional
                # entry so dispatch bookkeeping stays aligned.
                programs.append((w.wid, w.type_name, shard, None, None,
                                 xs_all, float(loads.get(w.wid, 0.0))))
                continue
            sl = slice(wi, wi + 1)
            S_w = worker_S[wi]
            mask_d = jax.device_put(arrays.step_mask[sl, :, :S_w], dev)
            bnd_d = jax.device_put(arrays.boundary[sl, :, :S_w], dev)
            wt_d = jax.device_put(arrays.weight[sl, :, :S_w], dev)
            if self._device_cache is not None:
                cplan = self._device_cache.plan(subplans[wi], S_w, t,
                                                shard=shard, worker_slot=slot)
                miss = gather_content_rows(
                    self.dataset, subplans[wi], cplan.content_mask,
                    cplan.n_miss_rows, batch_size=self.cfg.batch_size,
                    seq_len=self.cfg.seq_len, buffers=self._pack_buffers)
                batches_d = jax.device_put(miss, dev)
            else:
                cplan = None
                batches_d = jax.device_put(
                    {k: v[sl, :, :S_w] for k, v in arrays.batches.items()},
                    dev)
            xs = [c.n_batches
                  for c in assignment.per_worker.get(w.wid, [])]
            programs.append((w.wid, w.type_name, shard,
                             (batches_d, mask_d, bnd_d, wt_d), cplan,
                             xs, float(loads.get(w.wid, 0.0))))
        return programs

    def _execute_mesh(self, prep: _PreparedRound):
        """Mesh consumer half: dispatch every worker's program (async),
        sync each INDIVIDUALLY — the per-worker wall times MeasuredTelemetry
        needs — then reduce the concatenated partials in one combine
        program (bit-identical to the fused step's internal tail)."""
        dispatched = []
        shard_slots: dict[int, int] = {}
        # One copy of the global params per shard device: each worker
        # program reads the params on its own chip.
        self._shard_params = {}
        for wid, tname, shard, dev_arrays, cplan, xs, pred in \
                prep.worker_programs:
            if dev_arrays is None:
                # Another host's shard (process-per-host harness): its
                # owner executes and ships the merged host partial instead.
                continue
            batches, mask, bnd, wt = dev_arrays
            if self._device_cache is not None and cplan is not None:
                batches = self._device_cache.apply(batches, cplan)
                shard_slots[shard] = max(shard_slots.get(shard, 0),
                                         cplan.worker_slot + 1)
            out = self._worker_step(self._params_on(shard), batches, mask,
                                    bnd, wt)
            dispatched.append((wid, tname, shard, xs, pred, out))
        if self._device_cache is not None:
            # Elastic churn can shrink (or empty) a shard's worker set;
            # retire departed slots' round bases or their full-size device
            # arrays stay resident for the rest of the run.
            for s in range(self._device_cache.n_shards):
                self._device_cache.retire_slots(s, shard_slots.get(s, 0))
        # Per-worker device sync.  Each SHARD's programs serialize on its
        # device group, so a worker's time is the delta from its
        # shard-mate's completion — but different shards run concurrently
        # on a real mesh, so each shard's chain is synced on its OWN
        # thread: blocking on a slow shard from one thread would otherwise
        # charge its wall time to every not-yet-observed worker elsewhere
        # (inflating healthy workers' rows and tripping spurious drift).
        # On a single shared device all programs serialize anyway and the
        # per-shard deltas approximate the target topology.
        t0 = prep.exec_t0
        tr = self._tracer
        by_shard: dict[int, list] = {}
        for i, (wid, _, shard, _, _, out) in enumerate(dispatched):
            by_shard.setdefault(shard, []).append((i, wid, out[2]))
        meas = [0.0] * len(dispatched)

        def sync_shard(chain):
            last = t0
            for i, wid, arr in chain:
                jax.block_until_ready(arr)
                now = time.perf_counter()
                meas[i] = max(now - last, 0.0)
                if tr.enabled:
                    # Retroactive span from the delta already measured for
                    # telemetry — each worker renders as its own lane.
                    tr.add_span("exec.sync", last, now - last,
                                lane=f"worker{wid}", wid=int(wid),
                                t=prep.t)
                last = now

        if len(by_shard) > 1:
            list(self._sync_pool.map(sync_shard, by_shard.values()))
        else:
            for chain in by_shard.values():
                sync_shard(chain)
        prep.worker_times = [
            (wid, tname, xs, pred, meas[i])
            for i, (wid, tname, _, xs, pred, _) in enumerate(dispatched)]
        # Combine wall starts here (closed at the loss sync): the remaining
        # device work after every worker program has completed IS the
        # cross-shard reduction.  perf_counter reads only — no RNG, and the
        # measurement runs with tracing on or off.
        prep.combine_t0 = time.perf_counter()
        # Combine.  Flat mode concatenates every worker's lane partials
        # along W (exact — no arithmetic) and runs the reduction tail as
        # one program: O(K·lanes) partials cross to the combine device.
        # Tree mode (§3.3's hierarchy) first merges each SHARD's partials
        # on that shard — one shard-merge program per device group — so
        # only O(K) merged partials cross, and the cross-shard combine is
        # the same _reduce_partials tail applied to the [K, 1, ...] stack.
        # On a multi-device mesh each partial is committed to the root
        # before the concat (_to_root): flat mode moves every lane
        # partial, tree mode one merged partial per shard.
        _cat = _cat_parts

        if self._merge_step is not None:
            by_group: dict[int, list] = {}
            for d in dispatched:
                by_group.setdefault(d[2], []).append(d[5])
            if self._host_map is not None:
                return self._combine_hosts(prep, by_group)
            if self._compress is not None:
                return self._combine_compressed(prep, by_group)
            parts = []
            for shard in sorted(by_group):
                outs = by_group[shard]
                th = _cat(outs, 0)
                n_s = _cat(outs, 1)
                ls_s = _cat(outs, 2)
                mfn, _ = self._merge_step.lookup(
                    (int(n_s.shape[0]), int(n_s.shape[1])))
                # the cross-shard hop: one merged partial per shard
                parts.append(self._to_root(mfn(th, n_s, ls_s)))
            theta_wp = _cat(parts, 0)
            n_wp = _cat(parts, 1)
            lane_losses = _cat(parts, 2)
            prep.combine_bytes = len(parts) * self._partial_bytes
        else:
            outs = [self._to_root(d[5]) for d in dispatched]
            theta_wp = _cat(outs, 0)
            n_wp = _cat(outs, 1)
            lane_losses = _cat(outs, 2)
            prep.combine_bytes = (int(n_wp.shape[0]) * int(n_wp.shape[1])
                                  * self._partial_bytes)
        step_mask, boundary, weight = prep.combine_masks
        fn, _ = self._combine_step.lookup(
            (int(n_wp.shape[0]), int(n_wp.shape[1]))
            + tuple(step_mask.shape))
        new_params, metrics = fn(self.params, theta_wp, n_wp, lane_losses,
                                 step_mask, boundary, weight)
        self.params = new_params
        return metrics

    def _combine_compressed(self, prep: _PreparedRound, by_group: dict):
        """Compressed cross-shard combine tail (``combine_compress`` =
        ``int8``/``topk``): per shard, merge its lane partials with the same
        shard-merge program the exact tree path uses, DELTA-encode the
        merged partial against the global model through the shard's
        error-feedback residual, ship only the compressed payload to the
        combine root, and fold the K payloads through the fused
        dequant-merge combine program.  ``combine_bytes`` accounts the
        *compressed* wire format; the weight/loss scalars stay exact.

        Residuals commit only after the combine program is dispatched
        without error — a round that dies mid-combine leaves the previous
        round's residual set intact (and a checkpoint restore reloads the
        set matching ``round_idx`` exactly)."""
        efn, _ = self._encode_step.lookup(("encode",))
        payloads, ns, losses = [], [], []
        staged: dict[int, object] = {}
        for shard in sorted(by_group):
            outs = by_group[shard]
            th = _cat_parts(outs, 0)
            n_s = _cat_parts(outs, 1)
            ls_s = _cat_parts(outs, 2)
            mfn, _ = self._merge_step.lookup(
                (int(n_s.shape[0]), int(n_s.shape[1])))
            merged_th, merged_n, merged_ls = mfn(th, n_s, ls_s)
            theta = jax.tree.map(lambda x: x[0, 0], merged_th)
            payload, res = efn(self._params_on(shard), theta,
                               self._to_shard(self._compress.residual(shard),
                                              shard))
            staged[shard] = res
            # the cross-shard hop: only the compressed payload crosses
            payloads.append(self._to_root(payload))
            ns.append(self._to_root(merged_n[0, 0]))
            losses.append(self._to_root(merged_ls[0, 0]))
        payload_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
        n_stack = jnp.stack(ns)
        loss_stack = jnp.stack(losses)
        prep.combine_bytes = len(payloads) * self._compress.payload_bytes
        step_mask, boundary, weight = prep.combine_masks
        cfn, _ = self._compressed_combine_step.lookup(
            (len(payloads),) + tuple(step_mask.shape))
        new_params, metrics = cfn(self.params, payload_stack, n_stack,
                                  loss_stack, step_mask, boundary, weight)
        self.params = new_params
        self._compress.commit(staged)
        prep.residual_norm = self._compress.residual_norm()
        if self.control is not None:
            self.control.on_combine_compressed(
                prep.t, bytes_sent=prep.combine_bytes,
                residual_norm=prep.residual_norm)
        return metrics

    def _combine_hosts(self, prep: _PreparedRound, by_group: dict):
        """Host-hierarchy combine tail (``EngineConfig.hosts >= 1``): merge
        each shard's lane partials as usual, then reduce the K positional
        shard slots through the canonical pairwise tree — host blocks first
        (each an aligned pow2 subtree; dead shards stay as ``None`` holes),
        then the root over ONE partial per host.  ``combine_bytes`` accounts
        the host→root hop: ``live_hosts * partial_bytes`` — O(H), the wire
        win the host level exists for.

        With ``combine_compress`` on, each shard's partial is still encoded
        per shard (payloads and error-feedback residuals identical whatever
        the host count — the H-invariance of the compressed path rests on
        it) and decoded to a dense reconstruction before the pairwise
        nodes; compression rides the shard→host hop, the root hop ships
        dense host partials.

        In the process-per-host harness (``launch/multihost.py``) only the
        own rank's block is resident: its host partial all-gathers through
        ``_host_exchange`` and every rank runs the identical root reduction
        locally — same inputs, same program, bit-identical params on every
        host."""
        hm = self._host_map
        tr = self._tracer
        nfn, _ = self._host_node_step.lookup(("node",))

        def node(a, b):
            return nfn(a[0], a[1], a[2], b[0], b[1], b[2])

        staged: dict[int, object] = {}
        efn = dfn = None
        if self._compress is not None:
            efn, _ = self._encode_step.lookup(("encode",))
            dfn, _ = self._decode_step.lookup(("decode",))
        slots: list = [None] * hm.n_shards
        for shard in sorted(by_group):
            outs = by_group[shard]
            th = _cat_parts(outs, 0)
            n_s = _cat_parts(outs, 1)
            ls_s = _cat_parts(outs, 2)
            mfn, _ = self._merge_step.lookup(
                (int(n_s.shape[0]), int(n_s.shape[1])))
            merged_th, merged_n, merged_ls = mfn(th, n_s, ls_s)
            theta = jax.tree.map(lambda x: x[0, 0], merged_th)
            if self._compress is not None:
                params_s = self._params_on(shard)
                payload, res = efn(params_s, theta, self._to_shard(
                    self._compress.residual(shard), shard))
                staged[shard] = res
                theta = dfn(params_s, payload)
            slots[shard] = (theta, merged_n[0, 0], merged_ls[0, 0])
        own = self._host_rank
        host_parts: list = [None] * hm.n_hosts
        for h in range(hm.n_hosts):
            if own is not None and h != own:
                continue
            # a host block reduces on its first shard's device
            blk = [None if p is None else self._to_shard(p, h * hm.block)
                   for p in slots[h * hm.block:(h + 1) * hm.block]]
            t0h = time.perf_counter()
            part = HostShardMap.pairwise_reduce(blk, node)
            if part is not None and tr.enabled:
                tr.add_span("exec.host_merge", t0h,
                            time.perf_counter() - t0h,
                            lane=f"host{h}", host=h, t=prep.t)
            host_parts[h] = part
        if self._host_exchange is not None:
            gathered = self._host_exchange(
                prep.t, own, _partial_to_numpy(host_parts[own]))
            for h, p in enumerate(gathered):
                if h != own and p is not None:
                    host_parts[h] = p
        live = sum(1 for p in host_parts if p is not None)
        if live == 0:
            raise RuntimeError(
                f"round {prep.t}: no live shard partials reached the host "
                "combine")
        prep.combine_bytes = live * self._partial_bytes
        # the host→root hop: one merged partial per live host
        host_parts = [None if p is None else self._to_root(p)
                      for p in host_parts]
        root = HostShardMap.pairwise_reduce(host_parts, node)
        theta_wp = jax.tree.map(lambda x: jnp.asarray(x)[None, None], root[0])
        n_wp = jnp.asarray(root[1])[None, None]
        lane_losses = jnp.asarray(root[2])[None, None]
        step_mask, boundary, weight = prep.combine_masks
        fn, _ = self._combine_step.lookup((1, 1) + tuple(step_mask.shape))
        new_params, metrics = fn(self.params, theta_wp, n_wp, lane_losses,
                                 step_mask, boundary, weight)
        self.params = new_params
        if self._compress is not None:
            self._compress.commit(staged)
            prep.residual_norm = self._compress.residual_norm()
            if self.control is not None:
                self.control.on_combine_compressed(
                    prep.t, bytes_sent=prep.combine_bytes,
                    residual_norm=prep.residual_norm)
        return metrics

    def _execute(self, prep: _PreparedRound):
        """Dispatch the compiled round step (async); returns metrics."""
        if prep.worker_programs is not None:
            return self._execute_mesh(prep)
        with self._tracer.span("exec.dispatch", t=prep.t):
            batches, step_mask, boundary, weight = prep.device
            if self._device_cache is not None and prep.cache_plan is not None:
                # batches arrived as compact miss rows: one fused device
                # pass scatters them into the persistent round base,
                # recycles inserted clients into the HBM pool, and fills
                # hits from it.
                batches = self._device_cache.apply(batches, prep.cache_plan)
            if self.strategy.associative:
                new_params, metrics = self._round_step(
                    self.params, batches, step_mask, boundary, weight)
                self.params = new_params
            else:
                stacked, ws, metrics = self._gather_step(
                    self.params, batches, step_mask, boundary, weight)
                self.params = self.strategy.reduce(stacked, ws, self.params)
            return metrics

    def _post_execute(self, prep: _PreparedRound, metrics) -> None:
        """Consumer hook at the device sync point: measure round execution
        wall time and — in measured mode — record/attribute it and mark the
        round *finished* for the refit barrier (this is what wakes a
        stalled producer, so it runs before any queue wait)."""
        with self._tracer.span("exec.wait", t=prep.t):
            float(metrics.loss)                # device sync point
        now = time.perf_counter()
        prep.exec_s = now - prep.exec_t0
        if prep.combine_t0 > 0.0:
            # Mesh path: the window from last worker sync to the loss sync
            # is the cross-shard combine's wall time (dispatch + device
            # reduction).  Booked retroactively so the combine renders as
            # one span even though its dispatch is async.
            prep.combine_s = max(now - prep.combine_t0, 0.0)
            if self._tracer.enabled:
                self._tracer.add_span(
                    "exec.combine", prep.combine_t0, prep.combine_s,
                    t=prep.t, mode=self.cfg.combine_mode,
                    compress=self.cfg.combine_compress,
                    bytes=int(prep.combine_bytes))
        if self.control is not None:
            self.control.round_executed(prep.t, prep.exec_s, prep.shares,
                                        prep.n_steps_real,
                                        worker_times=prep.worker_times)

    def _finish(self, prep: _PreparedRound, metrics, t0: float) -> RoundResult:
        """Consumer tail: result bookkeeping and periodic checkpoint.  (The
        time-model refit AND telemetry recording live in ``_prepare_round``.)"""
        t = prep.t
        loss = float(metrics.loss)             # device sync point
        stats = padding_stats(prep.arrays)
        cp = prep.cache_plan
        hit_rate = cp.hit_rate if cp is not None else 0.0
        bytes_saved = cp.bytes_saved if cp is not None else 0
        if cp is None and prep.worker_programs is not None:
            # Mesh path: one cache plan per worker — aggregate them.
            plans = [p[4] for p in prep.worker_programs if p[4] is not None]
            if plans:
                hit = sum(c.hit_steps for c in plans)
                total = hit + sum(c.miss_steps for c in plans)
                hit_rate = hit / total if total else 0.0
                bytes_saved = sum(c.bytes_saved for c in plans)
        result = RoundResult(
            round_idx=t, loss=loss, n_clients=len(prep.clients),
            makespan=prep.makespan, idle_time=prep.idle_time,
            useful_fraction=stats["useful_fraction"],
            wall_time=time.perf_counter() - t0,
            placement=self.placement.name, s_steps=prep.arrays.n_steps,
            pack_time=prep.pack_s,
            overlap_fraction=(prep.overlap_s / prep.pack_s
                              if prep.pack_s > 0 else 0.0),
            recompiles=self._compiles_total,
            cache_hit_rate=hit_rate,
            cache_bytes_saved=bytes_saved,
            exec_time=prep.exec_s, barrier_stall_s=prep.stall_s,
            drift_fallback=prep.fallback,
            affinity_swaps=prep.affinity_swaps,
            padded_steps=prep.padded_steps,
            combine_bytes=prep.combine_bytes,
            residual_norm=prep.residual_norm,
            slo_p50=prep.slo_p50, slo_p99=prep.slo_p99,
            stale_fraction=prep.stale_fraction,
            online_pool=prep.online_pool)
        # Round critique (repro.obs): idle_fraction comes from the
        # deterministic placement simulation, so it is bit-identical across
        # depths and tracer on/off; critical_path is timing-derived (like
        # exec_time) and excluded from bitwise comparisons.
        crit = critique_round(
            round_idx=t, pack_s=prep.pack_s, overlap_s=prep.overlap_s,
            exec_s=prep.exec_s, combine_s=prep.combine_s,
            barrier_stall_s=prep.stall_s, makespan=prep.makespan,
            idle_time=prep.idle_time, n_workers=len(prep.workers),
            worker_meas=([(w[0], w[4]) for w in prep.worker_times]
                         if prep.worker_times else None))
        result.idle_fraction = crit.idle_fraction
        result.critical_path = crit.critical_path
        self.history.append(result)
        self.round_idx = t + 1
        self._sampler_ckpt_state = prep.sampler_st
        self._telemetry_ckpt_state = prep.telemetry_st
        self._control_ckpt_state = prep.control_st
        tr = self._tracer
        if tr.enabled:
            tr.counter("cache_hit_rate", hit_rate)
            tr.counter("online_pool", prep.online_pool)
            tr.counter("combine_bytes", float(prep.combine_bytes))
        if self._metrics is not None:
            m = self._metrics
            m.inc("rounds")
            m.inc("clients", len(prep.clients))
            m.gauge("loss", loss)
            m.gauge("idle_fraction", crit.idle_fraction)
            m.gauge("overlap_fraction", result.overlap_fraction)
            m.inc("critical_path." + crit.critical_path)
            m.observe("round_wall_s", result.wall_time)
            m.observe("pack_s", prep.pack_s)
            m.observe("exec_s", prep.exec_s)
        if self.obs is not None and self.obs.flight is not None:
            self.obs.flight.on_round(t, {
                "loss": loss, "n_clients": len(prep.clients),
                "makespan": prep.makespan, "pack_s": prep.pack_s,
                "exec_s": prep.exec_s, "stall_s": prep.stall_s,
                "critique": crit.as_dict()})

        if self._round_observer is not None:
            # Harness hook (launch/multihost.py): ship this round's
            # control-plane rows — measured worker times, drift evidence,
            # slot decisions — onto the sidecar channel, consumer-side in
            # round order.  Observation only; must not mutate engine state.
            self._round_observer(prep, result)
        if self.ckpt is not None and (t + 1) % self.cfg.rounds_per_checkpoint == 0:
            self.save_checkpoint()
        return result

    # -- the round -------------------------------------------------------------
    def run_round(self) -> RoundResult:
        """One fully synchronous round (also the ``pipeline_depth=0`` path)."""
        t0 = time.perf_counter()
        if self.control is not None:
            self.control.begin_run(self.round_idx)
        try:
            prep = self._prepare_round(self.round_idx)
            prep.exec_t0 = time.perf_counter()
            metrics = self._execute(prep)
            self._post_execute(prep, metrics)
        except BaseException as e:
            # A prep that died between cache.plan and cache.apply left LRU
            # entries whose pool rows were never written — a retry would
            # serve them as bogus hits.
            if self._device_cache is not None:
                self._device_cache.invalidate()
            if self.control is not None:
                self.control.abort()
            self._flight_dump(f"run_round abort: {e!r}")
            raise
        return self._finish(prep, metrics, t0)

    def _run_pipelined(self, n_rounds: int, *, log_every: int = 0) -> list[RoundResult]:
        """Bounded producer/consumer round loop: while round t executes on
        device, a single producer thread prepares rounds t+1 .. t+depth
        (sample → place → telemetry → pack → device_put), at most ``depth``
        ahead.  Every host-state mutation happens on the producer in strict
        round order, so results are bit-identical across depths (and across
        split ``run()`` calls); the consumer only advances params, the
        compile/device caches, and the history.

        Overlap accounting: a prep's hidden fraction is 1 - (consumer stall
        waiting for it) / (its pack time) — at depth 1 this reproduces the
        old min(pack, exec)/pack metric, and it generalizes to preps that
        overlap several rounds' executions.

        If an in-flight prep (or the device step itself) raises, every
        round already executed on device is booked in ``history`` before
        the error surfaces (a retrying caller must not train a round
        twice).  Queued preps are cancelled or stopped at the abort guard
        below, so at most the prep already running consumes host state for
        a round that never executes.  (The failing prep itself may also
        have consumed some; restore from a checkpoint for an exact resume
        after a pipeline error.)"""
        try:
            return self._run_pipelined_inner(n_rounds, log_every=log_every)
        except BaseException as e:
            # Any failure can leave preps that planned cache insertions
            # whose pool rows were never written (plan runs producer-side,
            # apply consumer-side) — a retry would serve them as bogus
            # hits.  Executed rounds were already booked by the inner loop.
            if self._device_cache is not None:
                self._device_cache.invalidate()
            if self.control is not None:
                # Wake a producer stalled at the refit barrier — the round
                # it waits for will never finish now.
                self.control.abort()
            self._flight_dump(f"pipeline abort: {e!r}")
            raise

    def _flight_dump(self, reason: str) -> None:
        """Flight-recorder dump on an engine abort (never raises — the
        recorder guards itself; this must not mask the primary error)."""
        if self.obs is not None and self.obs.flight is not None:
            path = self.obs.flight.dump(reason)
            if path is not None:
                print(f"flight recorder: dumped {path} ({reason})")

    def _run_pipelined_inner(self, n_rounds: int, *,
                             log_every: int = 0) -> list[RoundResult]:
        out: list[RoundResult] = []
        first = self.round_idx
        last = first + n_rounds - 1
        depth = self.cfg.pipeline_depth
        queue: deque = deque()
        aborted = False
        if self.control is not None:
            self.control.begin_run(first)

        def guarded_prep(t):
            # Runs on the single producer thread, strictly in round order:
            # once one prep raises, the flag (set producer-side, before the
            # consumer even observes the failure) stops every later queued
            # prep from mutating host state (RNG, telemetry, cache LRU)
            # for rounds that will never execute.
            nonlocal aborted
            if aborted:
                raise RuntimeError(f"pipeline aborted before round {t} prep")
            try:
                return self._prepare_round(t)
            except BaseException:
                aborted = True
                raise

        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="pollen-pack") as pool:
            prep = self._prepare_round(first)   # nothing to overlap with yet
            next_t = first + 1
            for t in range(first, last + 1):
                t0 = time.perf_counter()
                while next_t <= min(t + depth, last):
                    queue.append(pool.submit(guarded_prep, next_t))
                    next_t += 1
                try:
                    prep.exec_t0 = time.perf_counter()
                    metrics = self._execute(prep)
                    self._post_execute(prep, metrics)   # device sync point;
                    # marks round t finished for the refit barrier BEFORE the
                    # queue wait below — a depth-2 "stall" prep waiting on
                    # round t wakes here, not after we block on its future.
                except BaseException:
                    # Device-step failure: stop the producer too, or rounds
                    # t+1..t+depth would keep consuming sampler RNG and
                    # telemetry for rounds that will never execute.  (The
                    # prep already in flight still completes; queued ones
                    # stop at the guard.)  The abort must land BEFORE the
                    # raise: leaving the with-block joins the producer, and
                    # a prep stalled at the refit barrier would otherwise
                    # hold the shutdown for the full stall timeout.
                    aborted = True
                    if self.control is not None:
                        self.control.abort()
                    for fut in queue:
                        fut.cancel()
                    raise
                next_prep, prep_err = None, None
                if queue:
                    w0 = time.perf_counter()
                    try:
                        next_prep = queue.popleft().result()
                    except Exception as e:     # noqa: BLE001
                        # Round t already executed — book it before raising,
                        # or a retrying caller would train round t twice.
                        prep_err = e
                    wait_s = time.perf_counter() - w0
                    if next_prep is not None:
                        next_prep.overlap_s = min(
                            next_prep.pack_s,
                            max(0.0, next_prep.pack_s - wait_s))
                r = self._finish(prep, metrics, t0)
                out.append(r)
                if prep_err is not None:
                    for fut in queue:
                        fut.cancel()
                    raise prep_err
                if log_every and r.round_idx % log_every == 0:
                    self._log_round(r)
                prep = next_prep
        return out

    def run(self, n_rounds: int, *, log_every: int = 0) -> list[RoundResult]:
        if n_rounds <= 0:
            return []
        if self.cfg.pipeline_depth > 0:
            return self._run_pipelined(n_rounds, log_every=log_every)
        out = []
        for _ in range(n_rounds):
            r = self.run_round()
            out.append(r)
            if log_every and r.round_idx % log_every == 0:
                self._log_round(r)
        return out

    @staticmethod
    def _log_round(r: RoundResult) -> None:
        cache = (f" cache={r.cache_hit_rate:.0%}"
                 if (r.cache_hit_rate or r.cache_bytes_saved) else "")
        print(f"round {r.round_idx:5d} loss={r.loss:.4f} "
              f"clients={r.n_clients} S={r.s_steps} "
              f"useful={r.useful_fraction:.2%} idle={r.idle_time:.1f}s "
              f"pack={r.pack_time * 1e3:.0f}ms "
              f"overlap={r.overlap_fraction:.0%}" + cache)

    # -- fault tolerance -------------------------------------------------------
    def save_checkpoint(self) -> None:
        extra = {"round": self.round_idx}
        if self._sampler_ckpt_state is not None:
            # The per-round snapshot captured at prepare time (producer):
            # at depth >= 1 the live sampler RNG is ahead by the in-flight
            # preps, but this snapshot matches round_idx exactly, so a
            # restore reproduces the workload stream.
            extra["sampler"] = self._sampler_ckpt_state
        elif (st := sampler_state(self.sampler)) is not None:
            extra["sampler"] = st              # pre-first-round checkpoint
        if self._telemetry_ckpt_state is not None:
            # Synthetic-telemetry RNG, snapshotted at prepare time like the
            # sampler's: a resumed synthetic run re-draws the exact times
            # the uninterrupted run would have (ROADMAP follow-on (c)).
            extra["telemetry_rng"] = self._telemetry_ckpt_state
        elif self.telemetry is not None and hasattr(self.telemetry,
                                                    "state_dict"):
            extra["telemetry_rng"] = self.telemetry.state_dict()
        if isinstance(self.placement, LearningBasedPlacement):
            # Only rows of rounds already BOOKED: with pipeline_depth >= 1
            # the producer may have recorded telemetry for in-flight rounds
            # beyond round_idx; those rounds re-run (and re-record) after a
            # restore, so persisting them would duplicate rows and skew the
            # resumed fit.  Rows <= round_idx - 1 are complete and stable by
            # the time the consumer checkpoints.  Snapshot models.items()
            # and each row list once — the producer may concurrently add a
            # model for a newly joined worker type or append newer rows
            # (the round filter excludes the latter).
            extra["telemetry"] = {
                t: [list(r) for r in list(m._xs) if r[0] < self.round_idx]
                for t, m in list(self.placement.models.items())}
        # The aux sidecar nests one subtree per owner since layout "v2"
        # ({"compress": ..., "control": ...}); pre-v2 sidecars held the
        # compress tree at the top level and restore_latest still reads
        # them (the extra["aux_layout"] marker picks the decoder).
        aux_tree = {}
        if self._compress is not None:
            # Error-feedback residuals: consumer-owned, committed for rounds
            # <= round_idx - 1 by checkpoint time, so the aux sidecar matches
            # round_idx exactly.  Without them a resumed compressed run would
            # re-lose every update's quantization error once.
            extra["combine_compress"] = self._compress.state_meta()
            comp_aux = self._compress.state_aux()
            if comp_aux is not None:
                aux_tree["compress"] = comp_aux
        if self._control_ckpt_state is not None:
            # Control-loop state (drift EWMAs, slot trajectory, pending
            # measured rows), snapshotted at prepare time like the sampler
            # RNG so it matches round_idx exactly at any pipeline depth.
            # JSON-encoded to one uint8 leaf: the sidecar stays a flat
            # array container and the state schema can evolve freely.
            payload = np.frombuffer(
                json.dumps(self._control_ckpt_state).encode("utf-8"),
                dtype=np.uint8).copy()
            extra["control"] = {"nbytes": int(payload.size)}
            aux_tree["control"] = payload
        if self._host_map is not None:
            # Host-hierarchy descriptor: the combine-tree family this
            # checkpoint's trajectory (and any compressed residuals) was
            # produced under.  hosts=1 ↔ hosts=H sidecars interchange
            # freely — the canonical pairwise tree makes every H the same
            # arithmetic — but hosts=0 (the legacy fold) is a different
            # family, and restore_latest warns + resets residuals when the
            # families disagree.
            extra["host_layout"] = {"hosts": self._host_map.n_hosts,
                                    "shards": self._host_map.n_shards}
        if aux_tree:
            extra["aux_layout"] = "v2"
        self.ckpt.save(self.round_idx, self.params, extra=extra,
                       aux=aux_tree or None)

    def _restore_aux_entry(self, rnd: int, extra: dict, key: str, like):
        """Load one owner's subtree from the checkpoint aux sidecar.  v2
        sidecars nest per owner; pre-v2 ones hold the compress tree at the
        top level (and had no other owners)."""
        if extra.get("aux_layout") == "v2":
            out = self.ckpt.restore_aux({key: like}, round_idx=rnd)
            return None if out is None else out[key]
        if key != "compress":
            return None
        return self.ckpt.restore_aux(like, round_idx=rnd)

    def restore_latest(self) -> bool:
        if self.ckpt is None or self.ckpt.latest_round() is None:
            return False
        params, rnd, extra = self.ckpt.restore(self.params)
        self.params = self._to_root(params)
        self.round_idx = rnd
        if self._device_cache is not None:
            # Cache state is not checkpointed; entries planned for rounds
            # past the restore point must not survive as hits.
            self._device_cache.invalidate()
        if self.control is not None:
            # Resume the control loop where round ``rnd``'s prep left it
            # (drift EWMAs mid-hysteresis, slot trajectory, pending
            # measured rows) when the checkpoint carries the snapshot;
            # otherwise fall back to the old re-warm (reset drops pending
            # rows for rounds that will re-run and re-record).
            restored_ctl = False
            ctl_meta = extra.get("control")
            if ctl_meta:
                try:
                    arr = self._restore_aux_entry(
                        rnd, extra, "control",
                        np.zeros(int(ctl_meta["nbytes"]), dtype=np.uint8))
                    if arr is not None:
                        state = json.loads(
                            np.asarray(arr, dtype=np.uint8).tobytes())
                        self.control.load_state(state, rnd)
                        # Keep the snapshot: a save before the next round
                        # finishes must not drop the restored loop state.
                        self._control_ckpt_state = state
                        restored_ctl = True
                    else:
                        print("warning: checkpoint lists controller state "
                              "but the .aux.npz sidecar is missing; "
                              "resuming with a re-warmed control loop")
                except (KeyError, ValueError, TypeError) as e:
                    print("warning: checkpoint controller state unusable "
                          f"({e!r}); resuming with a re-warmed control "
                          "loop")
            if not restored_ctl:
                self.control.reset(rnd)
        if "sampler" in extra and extra["sampler"]:
            try:
                self.sampler = restore_sampler(extra["sampler"])
            except (KeyError, ValueError) as e:
                # A damaged snapshot must not silently break workload
                # reproducibility — the whole point of persisting it.
                print("warning: checkpoint sampler state unusable "
                      f"({e!r}); resuming with the configured sampler — "
                      "the workload stream will NOT match the original run")
        if (extra.get("telemetry_rng") and self.telemetry is not None
                and hasattr(self.telemetry, "load_state_dict")):
            try:
                self.telemetry.load_state_dict(extra["telemetry_rng"])
            except (KeyError, ValueError, TypeError) as e:
                print("warning: checkpoint telemetry RNG state unusable "
                      f"({e!r}); resuming with a fresh stream — synthetic "
                      "times will NOT match the uninterrupted run")
        # Host-layout cross-version guard: hosts=0 (the legacy combine fold)
        # and hosts>=1 (the canonical pairwise tree) are different combine
        # arithmetic families; within the hosts>=1 family every H computes
        # the same tree, so hosts=1 ↔ hosts=H sidecars interchange freely.
        try:
            ckpt_hosts = int((extra.get("host_layout") or {}).get("hosts", 0))
        except (AttributeError, TypeError, ValueError):
            ckpt_hosts = 0     # malformed sidecar field: treat as legacy
        cfg_hosts = self._host_map.n_hosts if self._host_map is not None else 0
        host_family_mismatch = (ckpt_hosts >= 1) != (cfg_hosts >= 1)
        if host_family_mismatch:
            print("warning: checkpoint host layout "
                  f"(hosts={ckpt_hosts}) does not match the configured "
                  f"engine (hosts={cfg_hosts}); the combine arithmetic "
                  "families differ, so the resumed trajectory will NOT "
                  "match the uninterrupted run"
                  + ("; resuming with zero error-feedback residuals"
                     if self._compress is not None else ""))
        if self._compress is not None:
            # Drop any residuals from rounds past the restore point, then
            # reload the set the checkpoint captured (if any — a checkpoint
            # written before the first compressed round has none, and a
            # mode/frac mismatch means the snapshot's residuals are in the
            # wrong basis entirely).
            self._compress.reset()
            meta = extra.get("combine_compress")
            if meta and meta.get("shards") and host_family_mismatch:
                meta = None   # warned above; keep zero residuals
            if meta and meta.get("shards"):
                if (meta.get("mode") != self.cfg.combine_compress
                        or meta.get("frac") != self.cfg.combine_topk_frac):
                    print("warning: checkpoint combine_compress state "
                          f"({meta.get('mode')!r}, frac={meta.get('frac')}) "
                          "does not match the configured compressor; "
                          "resuming with zero residuals — the resumed run "
                          "will NOT match the uninterrupted one")
                else:
                    try:
                        aux = self._restore_aux_entry(
                            rnd, extra, "compress",
                            self._compress.aux_like(meta["shards"]))
                        if aux is not None:
                            self._compress.load_state(aux)
                        else:
                            print("warning: checkpoint lists compressed-"
                                  "combine residuals but the .aux.npz "
                                  "sidecar is missing; resuming with zero "
                                  "residuals")
                    except (KeyError, ValueError) as e:
                        print("warning: checkpoint residual state unusable "
                              f"({e!r}); resuming with zero residuals — the "
                              "resumed run will NOT match the uninterrupted "
                              "one")
        if isinstance(self.placement, LearningBasedPlacement) and "telemetry" in extra:
            for tname, rows in extra["telemetry"].items():
                m = self.placement._model(tname)
                m._xs = [tuple(r) for r in rows]
                m._fit_sig = (-1, -1)      # direct _xs swap: force a refit
                m._recent_sig = (-1, -1, -1)
            self.placement.refit(self.round_idx)
        return True
