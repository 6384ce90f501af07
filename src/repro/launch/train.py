"""End-to-end federated training driver (deliverable b's e2e entry point).

Composes the full stack: federated dataset → cohort sampler → placement
(RR / BB / LB) → worker pool (with optional failure injection) → jitted
round step (partial aggregation) → telemetry → time-model refit →
checkpointing.  Works for the paper's four FL tasks and for any assigned
LM architecture (reduced or preset scale for CPU; tests/test_archs.py
traces the full configs' round step).

Examples:
    PYTHONPATH=src python -m repro.launch.train --task sr --rounds 30
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --preset smoke --rounds 10 --placement lb
    PYTHONPATH=src python -m repro.launch.train --task ic --rounds 60 \
        --fail-worker 2:20 --resume --ckpt-dir /tmp/pollen_ic
"""

from __future__ import annotations

import argparse
import json
import signal
from collections import Counter
from contextlib import nullcontext
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.configs import get_arch
from repro.core import (EngineConfig, FederatedEngine, SyntheticTelemetry,
                        UniformSampler, ZipfSampler, make_placement)
from repro.data import make_federated_dataset
from repro.distributed import FailureEvent, WorkerPool
from repro.fl.strategy import FedAvg, FedMedian
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, make_loss_fn
from repro.models.papertasks import TASK_MODELS, make_task_model
from repro.obs import make_observability, profiler_trace
from repro.optim import adam, sgd

__all__ = ["build_engine", "main", "flags_markdown", "PRESETS", "TASK_CLIENTS"]

# LM presets for the CPU driver ("smoke" for tests/examples; "fl100m" is the
# ~100M-param end-to-end config for real runs).
PRESETS = {
    "smoke": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=512, seq_len=32,
                  batch_size=4),
    "fl100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                   head_dim=64, d_ff=2048, vocab_size=32_000, seq_len=256,
                   batch_size=8),
}


def _kanana_data() -> dict:
    from repro.configs import kanana_2_30b_a3b as share
    return dict(vocab_size=share.SHARE.vocab_size, seq_len=share.SEQ_LEN)


# Per-task client setup: the client optimizer (paper A.1: SGD with momentum
# and weight decay for IC/SR/TG, Adam for MLM; FedAvg's ClientUpdate, plain
# SGD, for kanana2-a3b) and, where the task's TaskSpec does not fix them,
# the dataset's vocabulary and sequence length.
TASK_CLIENTS = {
    "ic": dict(optimizer=lambda: sgd(0.05, momentum=0.9, weight_decay=5e-4)),
    "sr": dict(optimizer=lambda: sgd(0.05, momentum=0.9, weight_decay=5e-4)),
    "tg": dict(optimizer=lambda: sgd(0.8, momentum=0.9, weight_decay=5e-4)),
    "mlm": dict(optimizer=lambda: adam(4e-5)),
    "kanana2-a3b": dict(optimizer=lambda: sgd(0.01), data=_kanana_data),
}


class _FrontendDataset:
    """Wrap a token dataset with the modality-stub arrays an arch needs."""

    def __init__(self, base, cfg):
        self.base = base
        self.cfg = cfg

    def __getattr__(self, name):
        return getattr(self.base, name)

    def client_batch(self, cid, batch_idx, *, batch_size=None, seq_len=None):
        out = self.gather_batches(np.asarray([cid]), np.asarray([batch_idx]),
                                  batch_size=batch_size, seq_len=seq_len)
        return {k: v[0] for k, v in out.items()}

    def gather_batches(self, cids, batch_idxs, *, batch_size=None,
                       seq_len=None, fetch_span=None):
        """Bulk fetch (the vectorized packer's fast path): token content from
        the base dataset plus the vmapped frontend-stub arrays."""
        b = self.base.gather_batches(cids, batch_idxs, batch_size=batch_size,
                                     seq_len=seq_len, fetch_span=fetch_span)
        cfg = self.cfg
        if not cfg.frontend:
            return b
        if b["tokens"].shape[0] == 0:
            bs0 = batch_size or self.base.spec.batch_size
            if cfg.frontend == "patch":
                b["patch_embed"] = np.zeros(
                    (0, bs0, cfg.frontend_len, cfg.resolved_frontend_dim),
                    np.float32)
            else:
                b["frames"] = np.zeros(
                    (0, bs0, cfg.frontend_len, cfg.d_model), np.float32)
            return b
        bs = b["tokens"].shape[1]
        folds = (np.asarray(cids, np.int64) * 131 +
                 np.asarray(batch_idxs, np.int64)).astype(np.int32)
        if cfg.frontend == "patch":
            shape = (bs, cfg.frontend_len, cfg.resolved_frontend_dim)
            name = "patch_embed"
        else:
            shape = (bs, cfg.frontend_len, cfg.d_model)
            name = "frames"
        stub = jax.vmap(lambda f: jax.random.normal(
            jax.random.fold_in(jax.random.key(7), f), shape, np.float32))(
                jnp.asarray(folds))
        with fetch_span or nullcontext():
            b[name] = np.asarray(stub)
        return b


def _parse_intervention(kind: str, spec: str):
    """``START:END[:SCALE][:REGION]`` -> Intervention (outage scale is 0)."""
    from repro.population import Intervention

    parts = spec.split(":")
    if len(parts) < 2:
        raise ValueError(f"--population-{kind} needs START:END[:SCALE]"
                         f"[:REGION], got {spec!r}")
    start, end = int(parts[0]), int(parts[1])
    scale = 0.0 if kind == "outage" else 1.5
    region = None
    rest = parts[2:]
    if rest:
        try:
            scale = float(rest[0])
            rest = rest[1:]
        except ValueError:
            pass
    if rest:
        region = rest[0]
    return Intervention(kind, start, end, scale, region=region)


def build_engine(*, task: str | None = None, arch: str | None = None,
                 preset: str = "smoke", placement: str = "lb",
                 cohort: int = 8, population: int | None = None,
                 workers: int = 2, concurrency: int = 2,
                 strategy: str = "fedavg", steps_cap: int = 8,
                 seed: int = 1337, ckpt_dir: str | None = None,
                 deadline_rho: float = 0.0, rounds_per_checkpoint: int = 25,
                 worker_specs=None, pipeline_depth: int = 1,
                 device_cache_batches: int = 0, device_cache_mb: float = 0.0,
                 sampler: str = "uniform", zipf_exponent: float = 1.2,
                 population_period: float = 48.0,
                 population_surge: str | None = None,
                 population_outage: str | None = None,
                 telemetry_mode: str = "synthetic",
                 barrier_policy: str = "reuse", drift_threshold: float = 0.0,
                 adapt_interval: int = 0, adapt_granularity: str = "type",
                 mesh_workers: int = 0, cache_affinity: bool = False,
                 bucket_mode: str = "round", combine_mode: str = "flat",
                 combine_compress: str = "none", topk_frac: float = 0.05,
                 hosts: int = 0,
                 grad_clip: float | None = None,
                 obs=None) -> FederatedEngine:
    """Compose a runnable engine for a paper task or an LM arch preset."""
    key = jax.random.key(seed)
    # The open-world sampler streams from a hash-derived registry: the BASE
    # dataset (content + class tables) stays small regardless of how many
    # clients --population registers — the PopulationDataset wrapper below
    # grafts the registered n_clients/sizes on without any O(N) allocation.
    base_clients = population
    if sampler == "online" and population:
        base_clients = min(population, 4096)
    if arch is not None:
        base_cfg = get_arch(arch)
        p = dict(PRESETS[preset])
        seq_len, batch_size = p.pop("seq_len"), p.pop("batch_size")
        cfg = base_cfg.reduced()
        fields = {k: v for k, v in p.items()
                  if preset != "smoke"}   # smoke == reduced()
        if fields:
            # keep family-specific dims consistent with the preset width
            if cfg.moe:
                fields.setdefault("moe_d_ff", fields.get("d_ff", 128))
            cfg = replace(cfg, **fields)
        if cfg.learned_pos:
            cfg = replace(cfg, max_position=max(cfg.max_position, seq_len))
        ds = make_federated_dataset(
            "lm", seed=seed, vocab_size=cfg.vocab_size, seq_len=seq_len,
            batch_size=batch_size,
            n_clients=base_clients or 4096)
        if cfg.frontend:
            ds = _FrontendDataset(ds, cfg)
        params = init_params(key, cfg)
        loss_fn = make_loss_fn(cfg)
        optimizer = sgd(0.05, momentum=0.9)
        batch_kw = dict(batch_size=batch_size, seq_len=seq_len)
    else:
        task = task or "sr"
        client = TASK_CLIENTS[task]
        params, _ = make_task_model(task, key)
        loss_fn = TASK_MODELS[task].loss_fn     # with the task's counts
        ds = make_federated_dataset(
            task, seed=seed, **client.get("data", dict)(),
            **({"n_clients": base_clients} if base_clients else {}))
        optimizer = client["optimizer"]()
        batch_kw = dict(batch_size=ds.spec.batch_size)

    pool = (WorkerPool.from_specs(worker_specs) if worker_specs
            else WorkerPool.homogeneous(workers, type_name="a40",
                                        concurrency=concurrency))
    strat = FedAvg() if strategy == "fedavg" else FedMedian()
    if sampler == "online":
        from repro.population import (ArrivalIndex, ClientMetadataStore,
                                      OnlinePoolSampler, PopulationDataset)
        registered = population or ds.n_clients
        store = ClientMetadataStore(registered, seed=seed,
                                    batch_size=ds.spec.batch_size)
        interventions = []
        if population_surge:
            interventions.append(
                _parse_intervention("surge", population_surge))
        if population_outage:
            interventions.append(
                _parse_intervention("outage", population_outage))
        index = ArrivalIndex(store, period=population_period,
                             interventions=tuple(interventions))
        ds = PopulationDataset(ds, store)
        sampler_obj = OnlinePoolSampler(index, cohort, seed=seed)
    elif sampler == "zipf":
        sampler_obj = ZipfSampler(ds.n_clients, cohort, a=zipf_exponent,
                                  seed=seed)
    elif sampler == "poc":
        from repro.core.sampling import PowerOfChoiceSampler
        sampler_obj = PowerOfChoiceSampler(ds.n_clients, cohort, seed=seed)
    else:
        sampler_obj = UniformSampler(ds.n_clients, cohort, seed=seed)
    engine = FederatedEngine(
        dataset=ds, loss_fn=loss_fn, init_params=params, optimizer=optimizer,
        placement=make_placement(placement), sampler=sampler_obj,
        pool=pool, telemetry=SyntheticTelemetry(seed=seed), strategy=strat,
        config=EngineConfig(steps_cap=steps_cap, seed=seed,
                            lanes_per_worker=concurrency,
                            grad_clip=grad_clip,
                            deadline_rho=deadline_rho,
                            rounds_per_checkpoint=rounds_per_checkpoint,
                            pipeline_depth=pipeline_depth,
                            device_cache_batches=device_cache_batches,
                            device_cache_bytes=int(device_cache_mb * 2**20),
                            telemetry_mode=telemetry_mode,
                            barrier_policy=barrier_policy,
                            drift_threshold=drift_threshold,
                            adapt_interval=adapt_interval,
                            adapt_granularity=adapt_granularity,
                            mesh_workers=mesh_workers,
                            cache_affinity=cache_affinity,
                            bucket_mode=bucket_mode,
                            combine_mode=combine_mode,
                            combine_compress=combine_compress,
                            combine_topk_frac=topk_frac,
                            hosts=hosts,
                            **batch_kw),
        checkpoint_store=CheckpointStore(ckpt_dir) if ckpt_dir else None,
        obs=obs,
    )
    return engine


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=list(TASK_MODELS), default=None)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", choices=list(PRESETS), default="smoke")
    ap.add_argument("--placement", default="lb", choices=["rr", "bb", "lb"])
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--cohort", type=int, default=8)
    ap.add_argument("--population", type=int, default=None)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--concurrency", type=int, default=2)
    ap.add_argument("--strategy", default="fedavg",
                    choices=["fedavg", "fedmedian"])
    ap.add_argument("--steps-cap", type=int, default=8)
    ap.add_argument("--grad-clip", type=float, default=None,
                    help="global-norm gradient clip (skewed samplers can "
                         "draw rare divergent clients; clipping tames them)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="rounds of host prep in flight ahead of the device")
    ap.add_argument("--device-cache-batches", type=int, default=0,
                    help="HBM rows pinned for hot clients (0 = off)")
    ap.add_argument("--device-cache-mb", type=float, default=0.0,
                    help="HBM cache budget in MiB (0 = off; with "
                         "--device-cache-batches the tighter limit wins)")
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "zipf", "online", "poc"],
                    help="zipf = skewed availability (hot clients recur); "
                         "online = open-world arrival process (diurnal "
                         "region traces, streaming draws from a hash-"
                         "derived registry — see docs/POPULATION.md); "
                         "poc = Power-of-Choice oversampling")
    ap.add_argument("--zipf-exponent", type=float, default=1.2,
                    help="Zipf skew a (P(client k) ~ (k+1)**-a); persisted "
                         "in checkpoint metadata so resumes reproduce the "
                         "workload")
    ap.add_argument("--population-period", type=float, default=48.0,
                    help="rounds per diurnal availability cycle for "
                         "--sampler online (every regional trace is "
                         "rescaled to this period)")
    ap.add_argument("--population-surge", default=None,
                    help="START:END[:SCALE][:REGION] — multiply a region's "
                         "(or every region's) online fraction by SCALE "
                         "(default 1.5) over rounds [START, END)")
    ap.add_argument("--population-outage", default=None,
                    help="START:END[:REGION] — take a region (or all) "
                         "offline over rounds [START, END); clients drawn "
                         "anyway count toward stale_fraction")
    ap.add_argument("--telemetry", default="synthetic",
                    choices=["synthetic", "measured"],
                    help="measured = feed placement from wall-clock round "
                         "times through the depth-aware refit barrier")
    ap.add_argument("--barrier-policy", default="reuse",
                    choices=["reuse", "stall"],
                    help="measured mode: stall preps until the refit-cutoff "
                         "round finished, or reuse the last fit")
    ap.add_argument("--drift-threshold", type=float, default=0.0,
                    help="residual-EWMA drift alarm; while tripped, "
                         "placement falls back to BB (0 = off)")
    ap.add_argument("--adapt-interval", type=int, default=0,
                    help="rounds per adaptive-concurrency hill-climb move "
                         "(0 = off)")
    ap.add_argument("--adapt-granularity", default="type",
                    choices=["type", "worker"],
                    help="hill-climb one slot knob per worker TYPE, or one "
                         "per individual worker (meaningful with "
                         "--mesh-workers, whose per-worker measurements "
                         "justify per-worker knobs)")
    ap.add_argument("--mesh-workers", type=int, default=0,
                    help="mesh shard count: 0/1 = one fused round program; "
                         "K >= 2 = one device program per worker over K "
                         "shards (exact per-worker measured times, "
                         "per-shard device-cache pools)")
    ap.add_argument("--cache-affinity", action="store_true",
                    help="prefer placing a device-cached client on the "
                         "mesh shard already holding its rows (load-"
                         "neutral swaps; needs --mesh-workers >= 2 and a "
                         "device cache)")
    ap.add_argument("--bucket-mode", default="round",
                    choices=["round", "worker"],
                    help="mesh stream-length bucketing: 'round' = every "
                         "worker program shares the round's bucketed S "
                         "(one executable); 'worker' = each worker "
                         "compiles at its own bucketed S (O(log S) "
                         "executables, short workers skip padded steps; "
                         "needs --mesh-workers >= 2)")
    ap.add_argument("--combine-mode", default="flat",
                    choices=["flat", "tree"],
                    help="mesh partial reduction: 'flat' = one global "
                         "combine over all lane partials (bit-identical "
                         "to the fused path); 'tree' = per-shard partial "
                         "merge before the cross-shard combine (paper "
                         "3.3's hierarchy, O(shards) transfer; losses "
                         "match flat to float tolerance; needs "
                         "--mesh-workers >= 2)")
    ap.add_argument("--combine-compress", default="none",
                    choices=["none", "int8", "topk"],
                    help="compress each shard's merged partial before the "
                         "cross-shard combine (delta from the global model "
                         "+ error-feedback residual): 'int8' = per-leaf "
                         "symmetric quantization (~4x smaller, fused "
                         "dequant-merge kernel); 'topk' = largest-|v| "
                         "sparsification (see --topk-frac); 'none' = exact "
                         "(bit-identity matrix preserved); needs "
                         "--combine-mode tree")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="fraction of coordinates topk compression keeps "
                         "per leaf (static: payload shapes depend on it)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="host level above the shard->root combine tree: "
                         "partition the mesh shards into H contiguous host "
                         "groups, pairwise-merge each group's shard "
                         "partials locally, and ship ONE partial per host "
                         "to the root combine (combine_bytes O(shards) -> "
                         "O(hosts)); losses are bit-identical across H "
                         "(hosts=1 is the reference tree), 0 = legacy "
                         "scan-fold combine; needs --combine-mode tree, "
                         "--mesh-workers >= 2, and shards/H a power of "
                         "two; see launch/multihost.py for the "
                         "process-per-host harness")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="take a jax.profiler trace of the run into DIR: "
                         "host and device on one clock, the engine's spans "
                         "(producer prep, generator wait, dispatch, device "
                         "wait) on the host threads; DIR/plugins/profile/"
                         "TIME/ holds the .xplane.pb and a "
                         "perfetto_trace.json.gz that loads at "
                         "ui.perfetto.dev — see docs/OBSERVABILITY.md.  "
                         "Tracing never perturbs results (bit-identity is "
                         "test-enforced)")
    ap.add_argument("--trace-rounds", type=int, default=64,
                    help="rounds of spans each tracer lane retains (ring "
                         "buffer; older spans are dropped, counted, never "
                         "blocked on)")
    ap.add_argument("--flight-rounds", type=int, default=0,
                    help="keep the last N round summaries in memory and "
                         "dump flight.json (spans + metrics + rounds) on "
                         "engine abort, prep failure, or SIGTERM (0 = off)")
    ap.add_argument("--seed", type=int, default=1337)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--deadline-rho", type=float, default=0.0)
    ap.add_argument("--fail-worker", default=None,
                    help="WID:ROUND — inject a worker failure")
    ap.add_argument("--join-worker", default=None, help="WID:ROUND")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--print-flags-md", action="store_true",
                    help="emit this flag reference as a markdown table and "
                         "exit (the README section is generated from it, "
                         "so the two cannot drift — CI checks)")
    return ap


def flags_markdown() -> str:
    """The CLI flag reference as a markdown table, generated from the live
    argparse parser — the single source the README section is built from."""
    rows = ["| flag | default | description |", "| --- | --- | --- |"]
    for a in _build_parser()._actions:
        if not a.option_strings or a.dest == "help":
            continue
        flag = "`" + ", ".join(a.option_strings) + "`"
        if a.choices:
            flag += " " + "\\|".join(str(c) for c in a.choices)
        if isinstance(a, argparse._StoreTrueAction):
            default = "off"
        elif a.default is None:
            default = "—"
        else:
            default = f"`{a.default}`"
        desc = " ".join((a.help or "").split())
        rows.append(f"| {flag} | {default} | {desc} |")
    return "\n".join(rows)


def main() -> int:
    args = _build_parser().parse_args()
    if args.print_flags_md:
        print(flags_markdown())
        return 0
    enable_compile_cache()

    obs = None
    if args.trace_out or args.flight_rounds > 0:
        obs = make_observability(trace_rounds=args.trace_rounds,
                                 flight_rounds=args.flight_rounds)

    engine = build_engine(
        task=args.task, arch=args.arch, preset=args.preset,
        placement=args.placement, cohort=args.cohort,
        population=args.population, workers=args.workers,
        concurrency=args.concurrency, strategy=args.strategy,
        steps_cap=args.steps_cap, seed=args.seed, ckpt_dir=args.ckpt_dir,
        grad_clip=args.grad_clip,
        deadline_rho=args.deadline_rho, pipeline_depth=args.pipeline_depth,
        device_cache_batches=args.device_cache_batches,
        device_cache_mb=args.device_cache_mb, sampler=args.sampler,
        zipf_exponent=args.zipf_exponent,
        population_period=args.population_period,
        population_surge=args.population_surge,
        population_outage=args.population_outage,
        telemetry_mode=args.telemetry,
        barrier_policy=args.barrier_policy,
        drift_threshold=args.drift_threshold,
        adapt_interval=args.adapt_interval,
        adapt_granularity=args.adapt_granularity,
        mesh_workers=args.mesh_workers,
        cache_affinity=args.cache_affinity,
        bucket_mode=args.bucket_mode,
        combine_mode=args.combine_mode,
        combine_compress=args.combine_compress,
        topk_frac=args.topk_frac,
        hosts=args.hosts,
        obs=obs)

    if obs is not None and obs.flight is not None:
        def _on_sigterm(signum, frame):  # last-gasp state dump
            obs.flight.dump("SIGTERM")
            raise SystemExit(128 + signum)
        signal.signal(signal.SIGTERM, _on_sigterm)

    if args.fail_worker:
        wid, rnd = (int(x) for x in args.fail_worker.split(":"))
        engine.pool.schedule(FailureEvent(round_idx=rnd, kind="fail",
                                          wid=wid))
    if args.join_worker:
        wid, rnd = (int(x) for x in args.join_worker.split(":"))
        engine.pool.schedule(FailureEvent(round_idx=rnd, kind="join",
                                          wid=wid, type_name="a40"))
    if args.resume and engine.restore_latest():
        print(f"resumed from round {engine.round_idx}")

    with profiler_trace(args.trace_out) if args.trace_out else nullcontext():
        results = engine.run(args.rounds, log_every=1)
    summary = {
        "rounds": len(results),
        "final_loss": results[-1].loss if results else None,
        "total_idle_s": sum(r.idle_time for r in results),
        "mean_useful_fraction": float(np.mean(
            [r.useful_fraction for r in results])) if results else None,
        "placement": args.placement,
        "pipeline_depth": args.pipeline_depth,
        "mean_overlap_fraction": float(np.mean(
            [r.overlap_fraction for r in results])) if results else None,
        "slo_p50_s": float(np.mean(
            [r.slo_p50 for r in results])) if results else None,
        "slo_p99_s": float(np.mean(
            [r.slo_p99 for r in results])) if results else None,
        "mean_idle_fraction": float(np.mean(
            [r.idle_fraction for r in results])) if results else None,
        "critical_path": dict(Counter(
            r.critical_path for r in results if r.critical_path)),
    }
    if obs is not None:
        summary["tracer"] = obs.tracer.stats()
    if args.sampler == "online":
        summary["population"] = {
            "registered": int(engine.sampler.population),
            "mean_online_pool": float(np.mean(
                [r.online_pool for r in results])) if results else None,
            "mean_stale_fraction": float(np.mean(
                [r.stale_fraction for r in results])) if results else None,
        }
    if args.device_cache_batches or args.device_cache_mb:
        summary["cache_hit_rate"] = float(np.mean(
            [r.cache_hit_rate for r in results])) if results else None
        summary["cache_bytes_saved"] = int(sum(
            r.cache_bytes_saved for r in results))
    if args.mesh_workers >= 2:
        summary["mesh_workers"] = args.mesh_workers
        summary["affinity_swaps"] = int(sum(
            r.affinity_swaps for r in results))
        summary["bucket_mode"] = args.bucket_mode
        summary["combine_mode"] = args.combine_mode
        summary["padded_steps"] = int(sum(
            r.padded_steps for r in results))
        summary["combine_bytes_per_round"] = int(np.mean(
            [r.combine_bytes for r in results])) if results else 0
        if args.hosts >= 1:
            summary["hosts"] = args.hosts
        if args.combine_compress != "none":
            summary["combine_compress"] = args.combine_compress
            summary["final_residual_norm"] = (
                results[-1].residual_norm if results else 0.0)
        if engine.cache_stats.get("per_shard"):
            summary["cache_per_shard"] = engine.cache_stats["per_shard"]
    if engine.control is not None:
        summary["control"] = engine.control_stats
        summary["mean_exec_s"] = float(np.mean(
            [r.exec_time for r in results])) if results else None
        summary["barrier_stall_s"] = float(sum(
            r.barrier_stall_s for r in results))
        summary["fallback_rounds"] = int(sum(
            r.drift_fallback for r in results))
    if args.trace_out:
        print(f"trace: wrote a profiler trace under {args.trace_out}")
    print(json.dumps(summary, indent=1))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"summary": summary,
                       "history": [vars(r) for r in results]}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
