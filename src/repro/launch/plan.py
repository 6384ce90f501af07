"""Per-(arch × shape × mesh) execution planning.

``make_plan`` decides, for one dry-run/training cell:

* FL worker topology: which mesh axes index Pollen workers (W), lanes per
  worker (P), local steps (S), per-step batch (b) — with W·P·S·b equal to the
  assigned global batch;
* sharding policy: 'tp' for architectures whose client copy fits a single
  worker slice (the Pollen regime: many workers, each holding whole clients),
  'fsdp_tp' for archs where one client *is* the whole pod (command-r-104b,
  qwen3-moe-235b, jamba-52b, internvl2-26b) — Pollen's rule that a worker
  must fit its client, scaled up;
* activation sharding constraints (batch→data, seq→model for the large
  archs — Megatron-SP expressed as with_sharding_constraint hooks);
* implementation knobs (chunked attention, scatter MoE, loss chunk size)
  sized from napkin math so no transient exceeds ~1 GB/chip.

``input_specs`` produces ShapeDtypeStruct stand-ins for every model input of
the planned step — weak-type-correct, shardable, no device allocation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ArchConfig, get_arch
from repro.distributed.sharding import make_sharding_rules
from repro.launch.mesh import axis_sizes
from repro.models import init_cache, init_params

__all__ = ["make_plan", "input_specs", "Plan", "LARGE_PARAM_BYTES",
           "param_bytes", "runnable", "skip_reason"]

LARGE_PARAM_BYTES = 16e9      # bf16 bytes; above this one client = one pod


def param_bytes(cfg: ArchConfig) -> int:
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))


def skip_reason(cfg: ArchConfig, shape_name: str) -> str | None:
    """The assignment's declared skips."""
    if shape_name not in SHAPES:
        raise KeyError(f"unknown shape {shape_name!r}")
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return ("full quadratic attention at 524288 tokens — skipped per "
                "assignment; runs only for ssm/hybrid families")
    if SHAPES[shape_name].is_serve and not cfg.servable:
        return ("no serving cache for latent attention or leading dense "
                "layers — the model is trained, not served")
    return None


def runnable(cfg: ArchConfig, shape_name: str) -> bool:
    return skip_reason(cfg, shape_name) is None


@dataclass(frozen=True)
class Plan:
    arch: str
    shape: str
    kind: str                  # 'train' | 'prefill' | 'decode'
    policy: str                # 'tp' | 'fsdp_tp'
    worker_axes: tuple         # mesh axes indexing FL workers (train only)
    W: int
    P: int
    S: int
    b: int
    batch_axes: tuple          # per-step batch dim sharding
    seq_axes: tuple            # activation sequence sharding (SP)
    seq_len: int
    global_batch: int
    cfg: ArchConfig            # knobs + hooks injected
    large: bool

    @property
    def worker_spmd_axes(self):
        if not self.worker_axes:
            return None
        return self.worker_axes if len(self.worker_axes) > 1 \
            else self.worker_axes[0]


def _mk_act_shard(mesh, batch_axes, seq_axes):
    if not batch_axes and not seq_axes:
        return lambda x: x
    spec = P(batch_axes or None, seq_axes or None, None)
    ns = NamedSharding(mesh, spec)

    def hook(x):
        return jax.lax.with_sharding_constraint(x, ns)

    return hook


def _mk_logits_shard(mesh, batch_axes):
    spec = P(batch_axes or None, None, "model")
    ns = NamedSharding(mesh, spec)

    def hook(x):
        return jax.lax.with_sharding_constraint(x, ns)

    return hook


def _mk_moe_shard(mesh):
    n_model = axis_sizes(mesh).get("model", 1)

    def hook(x):
        # [E, C, ...] expert capacity buffers: shard experts over the model
        # axis when the count divides (EP); otherwise shard the capacity dim
        # (granite's 40 experts on a 16-way axis) — either way the buffer
        # never materializes replicated.
        if x.shape[0] % n_model == 0:
            spec = P(*(("model",) + (None,) * (x.ndim - 1)))
        elif x.ndim > 1 and x.shape[1] % n_model == 0:
            spec = P(*((None, "model") + (None,) * (x.ndim - 2)))
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return hook


def make_plan(arch: str | ArchConfig, shape_name: str, mesh,
              overrides: dict | None = None) -> Plan:
    """``overrides``: hillclimb knobs — plan fields (W/P/S/b/worker_axes/
    batch_axes/seq_axes/policy) and/or ArchConfig knob fields (attn_impl,
    moe_seq_chunk, loss_chunk, …) applied on top of the default plan."""
    cfg = get_arch(arch) if isinstance(arch, str) else arch
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape_name)
    if reason:
        raise ValueError(f"{cfg.name} × {shape_name} skipped: {reason}")
    ax = axis_sizes(mesh)
    has_pod = "pod" in ax
    large = param_bytes(cfg) > LARGE_PARAM_BYTES
    gb, seq = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        if large:
            worker_axes = ("pod",) if has_pod else ()
            W = ax.get("pod", 1) if has_pod else 1
            # S=8 local steps cut the per-step microbatch to 32 — the
            # remat-saved residuals and attention transients scale with b,
            # and b=128 puts the 52-104B archs ~10-20 GiB over the HBM
            # budget.  Same global batch; longer client streams.
            Pl, S = 1, 8
            batch_axes, seq_axes = ("data",), ("model",)
        else:
            # §Perf A2: when the full client state (θ + momentum + partial +
            # grads ≈ 4.5× params) fits one chip, the Pollen-natural layout
            # is one FL worker PER CHIP: params replicated, zero intra-layer
            # collectives, only the Eq. 1 partial all-reduce remains
            # (3.8× on qwen3-0.6b, 6.2× on whisper-base, single-pod).
            n_dev = math.prod(ax.values())
            per_chip = (4.5 * param_bytes(cfg) < 10 * 2 ** 30
                        and gb % n_dev == 0 and gb // n_dev <= 8)
            if per_chip:
                worker_axes = tuple(ax)          # every mesh axis
                W = n_dev
                Pl, S = 1, gb // n_dev
            else:
                worker_axes = ("pod", "data") if has_pod else ("data",)
                W = math.prod(ax[a] for a in worker_axes)
                # P=1 lane: two lanes double the per-chip client state
                # (params, momentum, partial, saved activations) — at
                # 16 GiB/chip one lane with a longer stream fits.
                Pl, S = 1, 4
            batch_axes, seq_axes = (), ()
        b = gb // (W * Pl * S)
        while b == 0 and Pl > 1:
            Pl //= 2
            b = gb // (W * Pl * S)
        while b == 0 and S > 1:
            S //= 2
            b = gb // (W * Pl * S)
        if W * Pl * S * b != gb:
            raise ValueError(f"{cfg.name}×{shape_name}: cannot factor "
                             f"global_batch {gb} as W{W}·P{Pl}·S{S}·b{b}")
    else:
        worker_axes, W, Pl, S = (), 1, 1, 1
        b = gb
        batch_axes = tuple(a for a in ("pod", "data") if a in ax and gb > 1)
        seq_axes = ("model",) if large else ()

    # ---- knobs sized by napkin math (≤ ~1 GB/chip transients) -------------
    knobs: dict = {}
    if cfg.n_heads:
        if shape.kind == "train" or shape.kind == "prefill":
            # §Perf iteration A1: with TP-sharded heads the dense scores are
            # ~270 MB/chip and the chunked scan's stacking/copy plumbing is
            # the memory bottleneck (2x) — use dense whenever heads shard
            # evenly; chunked with 512-blocks otherwise (C2: 256-blocks cost
            # ~35% more HBM traffic in loop plumbing).
            tp = 1 if "model" in worker_axes else ax.get("model", 1)
            if shape.kind == "train" and not large \
                    and cfg.n_heads % tp == 0:
                # per-chip workers (tp==1) always qualify; TP workers only
                # when heads shard evenly
                knobs["attn_impl"] = "dense"
            else:
                knobs["attn_impl"] = "chunked"
                knobs["attn_q_chunk"] = 512
            knobs["attn_repeat_kv"] = large   # even TP head sharding
    if cfg.moe:
        knobs["moe_impl"] = "scatter"
        # cap dispatch buffers: per-chunk capacity C = cf·k·(b·sc)/E keeps
        # the [E, C, D] buffers ≤ ~0.5 GiB/chip at prefill-scale tokens
        knobs["moe_seq_chunk"] = 512
    if shape.kind == "train":
        # without per-period remat, the chunked-attention softmax residuals
        # saved for backward regrow to O(s²) — remat everywhere for training
        knobs["remat"] = True
        # C2: 512-token loss chunks — half the LM-head re-reads of 256 at a
        # still-bounded ~130 MB/chip logits transient.  Per-chip workers
        # (b=1) afford 1024 (A2: ~620 MB f32 transient).
        if "model" in worker_axes:
            knobs["loss_chunk"] = 1024
        else:
            knobs["loss_chunk"] = 512 if cfg.vocab_size >= 100_000 else 1024
        if cfg.ssm_state and large:
            # SSD intra-chunk matrices scale with chunk Q; at 52B scale the
            # backward-saved stacks need the smaller block
            knobs["ssd_chunk"] = 64
    if cfg.learned_pos:
        knobs["max_position"] = max(cfg.max_position, seq)
    # ---- hillclimb overrides ----------------------------------------------
    plan_fields = {}
    for k, v in (overrides or {}).items():
        if k in ("worker_axes", "batch_axes", "seq_axes"):
            plan_fields[k] = tuple(v) if v else ()
        elif k in ("W", "P", "S", "b", "policy"):
            plan_fields[k] = v
        else:
            knobs[k] = v
    if plan_fields:
        worker_axes = plan_fields.get("worker_axes", worker_axes)
        batch_axes = plan_fields.get("batch_axes", batch_axes)
        seq_axes = plan_fields.get("seq_axes", seq_axes)
        W = plan_fields.get("W", math.prod(ax[a] for a in worker_axes)
                            if worker_axes else 1)
        Pl = plan_fields.get("P", Pl if shape.kind == "train" else 1)
        S = plan_fields.get("S", S if shape.kind == "train" else 1)
        b = plan_fields.get("b", gb // max(W * Pl * S, 1))
        if shape.kind == "train" and W * Pl * S * b != gb:
            raise ValueError(f"override does not factor {gb}: "
                             f"{W}·{Pl}·{S}·{b}")
    hooks = {
        "act_shard": _mk_act_shard(mesh, batch_axes, seq_axes),
    }
    if "model" not in worker_axes:
        hooks["act_shard_logits"] = _mk_logits_shard(mesh, batch_axes)
    if seq_axes:
        # SP archs: gather seq (keep batch sharded) at block entry so the
        # qkv/mlp dots contract against TP-sharded weights — otherwise XLA
        # resolves the model-axis conflict by all-gathering the WEIGHTS
        # (1.5 GiB f32 full [D,F] copies observed in command-r's HLO).
        hooks["act_gather"] = _mk_act_shard(mesh, batch_axes, ())
    if cfg.moe:
        hooks["act_shard_moe"] = _mk_moe_shard(mesh)
        # §Perf B3: manual EP dispatch (shard_map) — zero-token-motion
        # expert parallelism.  Usable wherever the round path has no vmap
        # wrapper: serve steps always; train only on the single-worker
        # (W=P=1) fast path.  Requires experts to divide the model axis.
        n_model = ax.get("model", 1)
        vmapped_train = shape.kind == "train" and not (
            W == 1 and Pl == 1)
        if large and cfg.n_experts % n_model == 0 and not vmapped_train:
            from repro.distributed.ep_dispatch import make_ep_dispatch
            # wide experts (jamba's 14336) need the seq-chunked manual path
            chunk = 2048 if cfg.moe_d_ff >= 4096 else 0
            hooks["moe_dispatch"] = make_ep_dispatch(
                mesh, batch_axes=batch_axes or (),
                model_axis="model",
                fsdp_axis=("data" if "data" not in worker_axes else None),
                seq_chunk=chunk)
    cfg2 = replace(cfg, **knobs, **hooks)

    policy = (overrides or {}).get("policy",
                                   "fsdp_tp" if large else "tp")
    return Plan(arch=cfg.name, shape=shape_name, kind=shape.kind,
                policy=policy,
                worker_axes=worker_axes, W=W, P=Pl, S=S, b=b,
                batch_axes=batch_axes, seq_axes=seq_axes, seq_len=seq,
                global_batch=gb, cfg=cfg2, large=large)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins) + shardings
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(plan: Plan) -> dict:
    """ShapeDtypeStructs for every input of the planned step."""
    cfg = plan.cfg
    if plan.kind == "train":
        lead = (plan.W, plan.P, plan.S, plan.b)
        seq_text = plan.seq_len
        batches = {}
        if cfg.frontend == "patch":
            seq_text = plan.seq_len - cfg.frontend_len
            batches["patch_embed"] = _sds(
                lead + (cfg.frontend_len, cfg.resolved_frontend_dim),
                jnp.bfloat16)
        if cfg.frontend == "audio":
            batches["frames"] = _sds(
                lead + (cfg.frontend_len, cfg.d_model), jnp.bfloat16)
        batches["tokens"] = _sds(lead + (seq_text,), jnp.int32)
        m = _sds((plan.W, plan.P, plan.S), jnp.float32)
        return {"batches": batches, "step_mask": m, "boundary": m,
                "weight": m}
    if plan.kind == "prefill":
        seq_text = plan.seq_len
        batch = {}
        if cfg.frontend == "patch":
            seq_text = plan.seq_len - cfg.frontend_len
            batch["patch_embed"] = _sds(
                (plan.b, cfg.frontend_len, cfg.resolved_frontend_dim),
                jnp.bfloat16)
        if cfg.frontend == "audio":
            batch["frames"] = _sds((plan.b, cfg.frontend_len, cfg.d_model),
                                   jnp.bfloat16)
        batch["tokens"] = _sds((plan.b, seq_text), jnp.int32)
        return {"batch": batch}
    # decode: one new token against a cache of seq_len
    cache = jax.eval_shape(
        lambda: init_cache(cfg, plan.b, plan.seq_len))
    return {
        "cache": cache,
        "tokens": _sds((plan.b, 1), jnp.int32),
        "pos": _sds((), jnp.int32),
    }


def _filter_spec(spec: P, shape, ax: dict) -> P:
    """Drop mesh axes from dims they do not evenly divide (batch=1 cells,
    whisper's 1500-frame encoder length, …) — sharding must follow shape."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        size = shape[i]
        for a in axes:
            n = ax.get(a, 1)
            if size % n == 0 and n > 1:
                keep.append(a)
                size //= n
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep
                                                      else None))
    return P(*out)


def _filtered_ns(mesh, spec_tree, shape_tree):
    ax = axis_sizes(mesh)
    return jax.tree.map(
        lambda s, x: NamedSharding(mesh, _filter_spec(s, x.shape, ax)),
        spec_tree, shape_tree,
        is_leaf=lambda v: isinstance(v, P))


def sharding_specs(plan: Plan, mesh) -> dict:
    """NamedShardings for params and for each input group of the step."""
    rules = make_sharding_rules(plan.policy, mesh, fl_axes=plan.worker_axes)
    params_shapes = jax.eval_shape(lambda k: init_params(k, plan.cfg),
                                   jax.random.key(0))
    pspec = rules["params"].tree_specs(params_shapes)
    params_ns = _filtered_ns(mesh, pspec, params_shapes)
    ax = axis_sizes(mesh)

    out = {"params": params_ns, "rules": rules,
           "params_shapes": params_shapes}
    fl = plan.worker_axes or None
    if plan.kind == "train":
        def arr_spec(x):
            # [W, P, S, b, ...]: W over worker axes, b over batch axes
            spec = [fl, None, None, plan.batch_axes or None]
            spec += [None] * (len(x.shape) - 4)
            return NamedSharding(mesh, _filter_spec(P(*spec), x.shape, ax))

        specs = input_specs(plan)
        out["batches"] = jax.tree.map(arr_spec, specs["batches"])
        mspec = NamedSharding(mesh, P(fl, None, None))
        out["masks"] = mspec
    elif plan.kind == "prefill":
        specs = input_specs(plan)
        ba = plan.batch_axes or None

        def b_spec(x):
            spec = P(*([ba] + [None] * (len(x.shape) - 1)))
            return NamedSharding(mesh, _filter_spec(spec, x.shape, ax))

        out["batch"] = jax.tree.map(b_spec, specs["batch"])
        kv_rules = rules["kv"]
        cache_shapes = jax.eval_shape(
            lambda: init_cache(plan.cfg, plan.b, plan.seq_len))
        cspec = kv_rules.tree_specs(cache_shapes)
        out["cache"] = _filtered_ns(mesh, cspec, cache_shapes)
    else:
        specs = input_specs(plan)
        kv_rules = rules["kv"]
        cspec = kv_rules.tree_specs(specs["cache"])
        out["cache"] = _filtered_ns(mesh, cspec, specs["cache"])
        ba = plan.batch_axes or None
        out["tokens"] = NamedSharding(
            mesh, _filter_spec(P(ba, None), (plan.b, 1), ax))
        out["logits"] = NamedSharding(
            mesh, _filter_spec(P(ba, "model"),
                               (plan.b, plan.cfg.padded_vocab), ax))
    return out
