"""Unified LM stack covering all assigned architecture families.

Every architecture is expressed as: embedding (+ modality stub) → a
*period-structured* stack of blocks → final norm → LM head.  A *period* is
the smallest repeating pattern of layer kinds (dense archs: 1; jamba: 8 =
lcm(attention-every-8, moe-every-2)); parameters are stacked per
period-position with a leading ``n_periods`` dim and the stack runs as a
``lax.scan`` over periods, so HLO size is O(period), not O(n_layers) — this
is what keeps the trace of the 94-layer qwen3-moe tractable.

Block kinds (cfg-driven):
    mixer: 'attn' (GQA + RoPE [+qk-norm] [+cross-attn]) | 'mla' (latent
           attention, DeepSeek-V3) | 'mamba' (SSD) | none
    mlp  : 'swiglu' | 'relu2' | 'gelu' | 'moe' | none
    command-r style ``parallel_block``: shared input norm, attn+mlp outputs
    added to the residual together.

Leading dense layers (``cfg.first_dense_layers``, DeepSeek-V3's
``first_k_dense_replace``) form a stack of their own, ``params["dense"]``,
run ahead of the periodic stack.  An MoE block with sigmoid routing runs
the dropless expert layer (``layers.moe_dropless``) beside an optional
shared expert, and reports its row counts instead of an auxiliary loss.

Entry points:
    init_params(key, cfg)                     -> params pytree
    forward(params, batch, cfg)               -> logits [b,s,V]
    loss_fn(params, batch, cfg)               -> scalar (seq-chunked CE)
    init_cache(cfg, batch, max_len)           -> cache pytree
    prefill(params, batch, cfg)               -> (logits_last, cache)
    decode_step(params, cache, tokens, pos, cfg) -> (logits, cache)

All functions are pure and jit/vmap/scan-safe.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import ssd as ssdlib
from repro.models.layers import (decode_attention, dense_init, gqa_attention,
                                 mla_attention, moe_dropless, moe_layer_3d,
                                 rms_norm, rope)

__all__ = ["init_params", "forward", "loss_fn", "loss_and_counts",
           "init_cache", "prefill", "decode_step", "layer_plan", "LayerKind",
           "param_count"]


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerKind:
    mixer: str          # 'attn' | 'mla' | 'mamba' | 'none'
    mlp: str            # 'swiglu' | 'relu2' | 'gelu' | 'moe' | 'none'
    cross: bool = False # decoder cross-attention (whisper)


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def layer_plan(cfg: ArchConfig, *, decoder: bool = True) -> list[LayerKind]:
    """The repeating period of layer kinds for this architecture."""
    period = 1
    if cfg.attn_every > 1:
        period = _lcm(period, cfg.attn_every)
    if cfg.moe and cfg.moe_every > 1:
        period = _lcm(period, cfg.moe_every)
    n_layers = cfg.n_layers - cfg.first_dense_layers
    if n_layers % period:
        raise ValueError(f"{cfg.name}: n_layers {n_layers} not divisible by "
                         f"period {period}")
    plan = []
    for l in range(period):
        if cfg.family == "ssm":
            mixer = "mamba"
        elif cfg.attn_every > 1:
            mixer = "attn" if l % cfg.attn_every == cfg.attn_offset else "mamba"
        else:
            mixer = "mla" if cfg.mla else "attn"
        if cfg.moe and l % cfg.moe_every == cfg.moe_offset:
            mlp = "moe"
        elif cfg.d_ff > 0:
            mlp = cfg.mlp_act
        else:
            mlp = "none"
        cross = decoder and cfg.enc_layers > 0 and mixer == "attn"
        plan.append(LayerKind(mixer=mixer, mlp=mlp, cross=cross))
    return plan


def dense_plan(cfg: ArchConfig) -> list[LayerKind]:
    """The leading dense layers' kind (empty without any)."""
    if not cfg.first_dense_layers:
        return []
    return [LayerKind(mixer="mla" if cfg.mla else "attn", mlp=cfg.mlp_act)]


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------
def _attn_shapes(cfg: ArchConfig) -> dict:
    hd = cfg.resolved_head_dim
    D = cfg.d_model
    sh = {
        "attn_norm": (D,),
        "wq": (D, cfg.n_heads * hd),
        "wk": (D, cfg.n_kv_heads * hd),
        "wv": (D, cfg.n_kv_heads * hd),
        "wo": (cfg.n_heads * hd, D),
    }
    if cfg.qk_norm:
        sh["q_norm"] = (hd,)
        sh["k_norm"] = (hd,)
    if cfg.use_bias:
        sh.update({"bq": (cfg.n_heads * hd,), "bk": (cfg.n_kv_heads * hd,),
                   "bv": (cfg.n_kv_heads * hd,), "bo": (D,)})
    return sh


def _mla_shapes(cfg: ArchConfig) -> dict:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "attn_norm": (D,),
        "wq": (D, H * (nope + rd)),
        "wkv_a": (D, r + rd),
        "kv_norm": (r,),
        "wkv_b": (r, H * (nope + vd)),
        "wo": (H * vd, D),
    }


def _cross_shapes(cfg: ArchConfig) -> dict:
    hd = cfg.resolved_head_dim
    D = cfg.d_model
    sh = {
        "xattn_norm": (D,),
        "xwq": (D, cfg.n_heads * hd),
        "xwk": (D, cfg.n_kv_heads * hd),
        "xwv": (D, cfg.n_kv_heads * hd),
        "xwo": (cfg.n_heads * hd, D),
    }
    if cfg.use_bias:
        sh.update({"xbq": (cfg.n_heads * hd,), "xbk": (cfg.n_kv_heads * hd,),
                   "xbv": (cfg.n_kv_heads * hd,), "xbo": (D,)})
    return sh


def _mlp_shapes(cfg: ArchConfig, kind: str) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    if kind == "swiglu":
        sh = {"mlp_norm": (D,), "w_gate": (D, F), "w_up": (D, F),
              "w_down": (F, D)}
    elif kind in ("relu2", "gelu"):
        sh = {"mlp_norm": (D,), "w_up": (D, F), "w_down": (F, D)}
        if cfg.use_bias:
            sh.update({"b_up": (F,), "b_down": (D,)})
    elif kind == "moe":
        E, H, Fm = cfg.n_experts, cfg.n_experts_held, cfg.moe_d_ff
        sh = {"mlp_norm": (D,), "router": (D, E),
              "moe_gate": (H, D, Fm), "moe_up": (H, D, Fm),
              "moe_down": (H, Fm, D)}
        if cfg.moe_shared_d_ff:
            Fs = cfg.moe_shared_d_ff
            sh.update({"shared_gate": (D, Fs), "shared_up": (D, Fs),
                       "shared_down": (Fs, D)})
    else:
        sh = {}
    return sh


def _mamba_shapes(cfg: ArchConfig) -> dict:
    return ssdlib.mamba_param_shapes(
        cfg.d_model, d_inner=cfg.d_inner, head_dim=cfg.ssm_head_dim,
        n_groups=cfg.ssm_groups, d_state=cfg.ssm_state, conv_k=cfg.ssm_conv)


def _block_shapes(cfg: ArchConfig, kind: LayerKind) -> dict:
    sh = {}
    if kind.mixer == "attn":
        sh.update(_attn_shapes(cfg))
    elif kind.mixer == "mla":
        sh.update(_mla_shapes(cfg))
    elif kind.mixer == "mamba":
        sh.update(_mamba_shapes(cfg))
    if kind.cross:
        sh.update(_cross_shapes(cfg))
    sh.update(_mlp_shapes(cfg, kind.mlp))
    if cfg.parallel_block and "mlp_norm" in sh:
        del sh["mlp_norm"]          # shared input norm (command-r style)
    return sh


def _init_leaf(key, name: str, shape, dtype):
    if "norm" in name or name == "mamba_gnorm":
        return jnp.ones(shape, jnp.float32)
    if name.startswith(("b", "xb")) and len(shape) == 1:
        return jnp.zeros(shape, dtype)
    if name == "mamba_A":
        # A_log init: A in [1, 16) -> log; per-head, tiled over the stack dim
        row = jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).copy()
    if name == "mamba_dt_bias":
        # softplus^-1 of dt in [1e-3, 1e-1], log-spaced per head
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), shape[-1]))
        row = jnp.log(jnp.expm1(dt)).astype(jnp.float32)
        return jnp.broadcast_to(row, shape).copy()
    if name == "mamba_D":
        return jnp.ones(shape, jnp.float32)
    return dense_init(key, shape, dtype)


def _init_stack(key, cfg: ArchConfig, plan, n_periods: int, dtype):
    stack = {}
    for i, kind in enumerate(plan):
        shapes = _block_shapes(cfg, kind)
        pos = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            leaf = _init_leaf(k, name, (n_periods,) + tuple(shape), dtype)
            pos[name] = leaf
        stack[f"p{i}"] = pos
    return stack


def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.dtype)
    plan = layer_plan(cfg)
    n_periods = (cfg.n_layers - cfg.first_dense_layers) // len(plan)
    k_embed, k_stack, k_head, k_enc, k_extra = jax.random.split(key, 5)
    params = {
        "embed": dense_init(k_embed, (cfg.padded_vocab, cfg.d_model), dtype,
                            scale=0.02),
        "stack": _init_stack(k_stack, cfg, plan, n_periods, dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.first_dense_layers:
        params["dense"] = _init_stack(jax.random.fold_in(k_stack, 1), cfg,
                                      dense_plan(cfg), cfg.first_dense_layers,
                                      dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model,
                                                cfg.padded_vocab), dtype)
    if cfg.frontend == "patch":
        params["patch_proj"] = dense_init(
            k_extra, (cfg.frontend_dim, cfg.d_model), dtype)
    if cfg.enc_layers > 0:
        enc_cfg = cfg.encoder_cfg()
        enc_plan = layer_plan(enc_cfg, decoder=False)
        params["enc"] = {
            "stack": _init_stack(jax.random.fold_in(k_enc, 1), enc_cfg,
                                 enc_plan, enc_cfg.n_layers // len(enc_plan),
                                 dtype),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "pos_embed": dense_init(jax.random.fold_in(k_enc, 2),
                                    (cfg.frontend_len, cfg.d_model), dtype,
                                    scale=0.02),
        }
    if cfg.learned_pos:
        params["pos_embed"] = dense_init(
            jax.random.fold_in(k_extra, 3), (cfg.max_position, cfg.d_model),
            dtype, scale=0.02)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------
def _project_qkv(p, h, cfg: ArchConfig, *, prefix: str = ""):
    hd = cfg.resolved_head_dim
    b, s, _ = h.shape
    wq, wk, wv = p[prefix + "wq"], p[prefix + "wk"], p[prefix + "wv"]
    q = h @ wq
    k = h @ wk
    v = h @ wv
    if cfg.use_bias:
        q = q + p[prefix + "bq"]
        k = k + p[prefix + "bk"]
        v = v + p[prefix + "bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm and not prefix:
        q = rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    return q, k, v


def _attn_out(p, attn, cfg: ArchConfig, *, prefix: str = ""):
    b, s = attn.shape[:2]
    out = attn.reshape(b, s, cfg.n_heads * cfg.resolved_head_dim) \
        @ p[prefix + "wo"]
    if cfg.use_bias:
        out = out + p[prefix + "bo"]
    return out


def _attn_body(p, x, cfg: ArchConfig, *, causal: bool, positions=None,
               norm_key: str = "attn_norm"):
    """Full-sequence attention sub-block (training / prefill / encoder)."""
    h = rms_norm(x, p[norm_key], eps=cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg)
    if cfg.rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q = rope(q, positions, theta=cfg.rope_theta)
        k = rope(k, positions, theta=cfg.rope_theta)
    attn = gqa_attention(q, k, v, causal=causal, impl=cfg.attn_impl,
                         q_chunk=cfg.attn_q_chunk)
    return _attn_out(p, attn, cfg), (k, v)


def _cross_body(p, x, enc_out, cfg: ArchConfig):
    """Cross-attention against the encoder output (per-layer k/v proj)."""
    h = rms_norm(x, p["xattn_norm"], eps=cfg.norm_eps)
    b, s, _ = h.shape
    hd = cfg.resolved_head_dim
    q = h @ p["xwq"]
    if cfg.use_bias:
        q = q + p["xbq"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k, v = _encode_cross_kv(p, enc_out, cfg)
    attn = gqa_attention(q, k, v, causal=False, impl=cfg.attn_impl)
    return _attn_out(p, attn, cfg, prefix="x")


def _encode_cross_kv(p, enc_out, cfg: ArchConfig):
    b, t, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = enc_out @ p["xwk"]
    v = enc_out @ p["xwv"]
    if cfg.use_bias:
        k = k + p["xbk"]
        v = v + p["xbv"]
    return (k.reshape(b, t, cfg.n_kv_heads, hd),
            v.reshape(b, t, cfg.n_kv_heads, hd))


def _mlp_body(p, x, cfg: ArchConfig, kind: str, *, norm_key: str = "mlp_norm"):
    """Returns (out, stats): ``stats`` holds the block's auxiliary loss
    (``aux``, capacity-routed MoE) or its expert row counts (dropless)."""
    h = rms_norm(x, p[norm_key], eps=cfg.norm_eps) if norm_key else x
    if kind == "swiglu":
        z = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
        return z @ p["w_down"], {}
    if kind in ("relu2", "gelu"):
        z = h @ p["w_up"]
        if cfg.use_bias:
            z = z + p["b_up"]
        z = jnp.square(jax.nn.relu(z)) if kind == "relu2" else jax.nn.gelu(z)
        out = z @ p["w_down"]
        if cfg.use_bias:
            out = out + p["b_down"]
        return out, {}
    if kind == "moe":
        if cfg.moe_score == "sigmoid":     # DeepSeek-V3: dropless, aux-free
            b, s, D = h.shape
            out, counts = moe_dropless(
                h.reshape(b * s, D), p["router"], p["moe_gate"], p["moe_up"],
                p["moe_down"], top_k=cfg.top_k, scale=cfg.moe_routed_scale)
            out = out.reshape(b, s, D)
            if cfg.moe_shared_d_ff:
                out = out + (jax.nn.silu(h @ p["shared_gate"])
                             * (h @ p["shared_up"])) @ p["shared_down"]
            return out, counts
        out, aux = moe_layer_3d(h, p["router"], p["moe_gate"], p["moe_up"],
                                p["moe_down"], top_k=cfg.top_k,
                                capacity_factor=cfg.capacity_factor,
                                impl=cfg.moe_impl)
        return out, {"aux": aux}
    raise ValueError(kind)


def _mamba_body(p, x, cfg: ArchConfig, *, return_state: bool = False):
    h = rms_norm(x, p["mamba_norm"], eps=cfg.norm_eps)
    return ssdlib.mamba2_mixer(
        p, h, head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
        d_state=cfg.ssm_state, chunk=cfg.ssd_chunk, impl=cfg.ssd_impl,
        return_state=return_state)


def _apply_block(p, x, cfg: ArchConfig, kind: LayerKind, *, causal: bool,
                 positions=None, enc_out=None, collect_kv: bool):
    """One block; returns (x, stats, cache_contrib_or_None)."""
    contrib = None
    aux = {}
    if kind.mixer == "mla":
        if collect_kv:
            raise NotImplementedError("no serving cache for latent attention")
        h = rms_norm(x, p["attn_norm"], eps=cfg.norm_eps)
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        attn = mla_attention(
            p, h, positions, n_heads=cfg.n_heads, nope=cfg.qk_nope_head_dim,
            rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
            rank=cfg.kv_lora_rank, theta=cfg.rope_theta, eps=cfg.norm_eps,
            causal=causal, impl=cfg.attn_impl, q_chunk=cfg.attn_q_chunk)
        x = x + attn @ p["wo"]
        if kind.mlp != "none":
            mlp_out, aux = _mlp_body(p, x, cfg, kind.mlp)
            x = x + mlp_out
    elif cfg.parallel_block and kind.mixer == "attn" and kind.mlp != "none":
        # command-r: shared norm, attn & mlp in parallel
        attn_out, kv = _attn_body(p, x, cfg, causal=causal,
                                  positions=positions)
        mlp_out, aux = _mlp_body(p, x, cfg, kind.mlp, norm_key="attn_norm")
        x = x + attn_out + mlp_out
        if collect_kv:
            contrib = {"k": kv[0], "v": kv[1]}
    else:
        if kind.mixer == "attn":
            attn_out, kv = _attn_body(p, x, cfg, causal=causal,
                                      positions=positions)
            x = x + attn_out
            if collect_kv:
                contrib = {"k": kv[0], "v": kv[1]}
        elif kind.mixer == "mamba":
            if collect_kv:
                y, (conv_tail, ssm_state) = _mamba_body(p, x, cfg,
                                                        return_state=True)
                contrib = {"conv": conv_tail, "ssm": ssm_state}
            else:
                y = _mamba_body(p, x, cfg)
            x = x + y
        if kind.cross and enc_out is not None:
            x = x + _cross_body(p, x, enc_out, cfg)
        if kind.mlp != "none":
            mlp_out, aux = _mlp_body(p, x, cfg, kind.mlp)
            x = x + mlp_out
    return x, aux, contrib


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------
def _add_stats(a: dict, b: dict) -> dict:
    # Sorted, not a set's order: that follows the process's string hashes,
    # and the order of the adds is part of the program (and of its key in
    # the persistent compilation cache).
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in sorted(a.keys() | b.keys())}


def _run_stack(stack, x, cfg: ArchConfig, plan, *, causal: bool,
               positions=None, enc_out=None, collect_kv: bool = False):
    """scan over periods; returns (x, stats summed over the stack,
    cache_stack_or_None).  ``stats``: ``aux`` (the auxiliary loss of
    capacity-routed MoE blocks) and the dropless blocks' row counts."""

    def period_body(carry, pparams):
        x = carry
        stats = {}
        kvs = {}
        for i, kind in enumerate(plan):
            x, st, contrib = _apply_block(
                pparams[f"p{i}"], x, cfg, kind, causal=causal,
                positions=positions, enc_out=enc_out, collect_kv=collect_kv)
            stats = _add_stats(stats, st)
            if collect_kv and contrib is not None:
                kvs[f"p{i}"] = contrib
        stats = {k: jnp.asarray(v, jnp.float32) for k, v in stats.items()}
        return x, (stats, kvs)

    body = period_body
    if cfg.remat:
        body = jax.checkpoint(period_body,
                              prevent_cse=False)
    x, (stats, kv_stack) = jax.lax.scan(body, x, stack)
    return (x, {k: v.sum() for k, v in stats.items()},
            (kv_stack if collect_kv else None))


def _embed_inputs(params, batch, cfg: ArchConfig):
    """tokens (+ modality stub) -> (x [b,s,D], loss_mask [b,s], positions)."""
    tokens = batch["tokens"]
    x = params["embed"][tokens]                     # [b, s_text, D]
    loss_mask = jnp.ones(tokens.shape, jnp.float32)
    if cfg.frontend == "patch" and "patch_embed" in batch:
        patches = batch["patch_embed"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
        loss_mask = jnp.concatenate(
            [jnp.zeros(patches.shape[:2], jnp.float32), loss_mask], axis=1)
    if cfg.learned_pos:
        s = x.shape[1]
        x = x + params["pos_embed"][:s][None]
    positions = jnp.arange(x.shape[1])[None, :]
    return x, loss_mask, positions


def _run_encoder(params, batch, cfg: ArchConfig):
    enc_cfg = cfg.encoder_cfg()
    frames = batch["frames"].astype(jnp.dtype(cfg.dtype))   # [b, T, D] stub
    enc = params["enc"]
    x = frames + enc["pos_embed"][:frames.shape[1]][None]
    plan = layer_plan(enc_cfg, decoder=False)
    x, _, _ = _run_stack(enc["stack"], x, enc_cfg, plan, causal=False)
    return rms_norm(x, enc["final_norm"], eps=cfg.norm_eps)


def _lm_head(params, h, cfg: ArchConfig):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w,
                      preferred_element_type=jnp.float32)


def _hidden(params, batch, cfg: ArchConfig, *, collect_kv: bool = False):
    x, loss_mask, positions = _embed_inputs(params, batch, cfg)
    enc_out = None
    if cfg.enc_layers > 0:
        enc_out = _run_encoder(params, batch, cfg)
    stats = {}
    if cfg.first_dense_layers:
        x, stats, _ = _run_stack(params["dense"], x, cfg, dense_plan(cfg),
                                 causal=True, positions=positions)
    plan = layer_plan(cfg)
    x, st, kv = _run_stack(
        params["stack"], x, cfg, plan, causal=True, positions=positions,
        enc_out=enc_out, collect_kv=collect_kv)
    h = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    return h, loss_mask, _add_stats(stats, st), kv


def forward(params, batch, cfg: ArchConfig):
    """Full-sequence logits (smoke tests / eval); pad columns sliced off."""
    h, _, _, _ = _hidden(params, batch, cfg)
    return _lm_head(params, h, cfg)[..., :cfg.vocab_size]


def loss_fn(params, batch, cfg: ArchConfig):
    """Next-token CE, chunked over the sequence so the [b,s,V] logits tensor
    is never materialized (vocab up to 256k × seq 4k would be 0.5TB)."""
    return loss_and_counts(params, batch, cfg)[0]


def loss_and_counts(params, batch, cfg: ArchConfig):
    """``loss_fn`` and the step's counts: the dropless expert layers' row
    counts summed over the layers (``moe_routed_rows``,
    ``moe_buffer_rows``); empty for a model without them."""
    h, loss_mask, stats, _ = _hidden(params, batch, cfg)
    tokens = batch["tokens"]
    b, s_tot, D = h.shape
    s_text = tokens.shape[1]
    # predictions for text positions: h at position i predicts token i+1.
    h_pred = h[:, s_tot - s_text:, :][:, :-1]       # [b, s_text-1, D]
    labels = tokens[:, 1:]                          # [b, s_text-1]
    mask = loss_mask[:, s_tot - s_text + 1:]        # mask of label positions
    n = labels.shape[1]
    chunk = min(cfg.loss_chunk, n) if cfg.loss_chunk else n
    pad = (-n) % chunk
    if pad:
        h_pred = jnp.pad(h_pred, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = (n + pad) // chunk
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    # checkpoint: without it the scan saves every chunk's [b,chunk,V] logits
    # for backward — exactly the full-logits tensor chunking exists to avoid.
    @jax.checkpoint
    def chunk_loss(carry, inp):
        hc, lc, mc = inp                            # [b,chunk,D],[b,chunk]
        logits = jnp.einsum("bsd,dv->bsv", hc, w,
                            preferred_element_type=jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:      # mask vocab padding
            vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(vmask, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - gold) * mc), None

    xs = (jnp.moveaxis(h_pred.reshape(b, nc, chunk, D), 1, 0),
          jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0),
          jnp.moveaxis(mask.reshape(b, nc, chunk), 1, 0))
    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), xs)
    denom = jnp.maximum(mask.sum(), 1.0)
    counts = {k: v for k, v in stats.items() if k != "aux"}
    return total / denom + cfg.moe_aux_weight * stats.get("aux", 0.0), counts


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------
def _check_servable(cfg: ArchConfig) -> None:
    if not cfg.servable:
        raise NotImplementedError(
            f"{cfg.name}: no serving cache for latent attention or leading "
            "dense layers (the model is trained, not served, here)")


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Allocate the serving cache for a batch of sequences of ≤ max_len."""
    _check_servable(cfg)
    plan = layer_plan(cfg)
    n_periods = cfg.n_layers // len(plan)
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    cache = {}
    for i, kind in enumerate(plan):
        c = {}
        if kind.mixer == "attn":
            c["k"] = jnp.zeros((n_periods, batch, max_len, cfg.n_kv_heads, hd),
                               dtype)
            c["v"] = jnp.zeros((n_periods, batch, max_len, cfg.n_kv_heads, hd),
                               dtype)
        elif kind.mixer == "mamba":
            mc = ssdlib.mamba2_init_cache(
                batch, d_inner=cfg.d_inner, head_dim=cfg.ssm_head_dim,
                n_groups=cfg.ssm_groups, d_state=cfg.ssm_state,
                conv_k=cfg.ssm_conv, dtype=dtype)
            c["conv"] = jnp.broadcast_to(
                mc.conv[None], (n_periods,) + mc.conv.shape).copy()
            c["ssm"] = jnp.broadcast_to(
                mc.ssm[None], (n_periods,) + mc.ssm.shape).copy()
        if kind.cross:
            c["xk"] = jnp.zeros((n_periods, batch, cfg.frontend_len,
                                 cfg.n_kv_heads, hd), dtype)
            c["xv"] = jnp.zeros((n_periods, batch, cfg.frontend_len,
                                 cfg.n_kv_heads, hd), dtype)
        cache[f"p{i}"] = c
    return cache


def prefill(params, batch, cfg: ArchConfig, *, max_len: int | None = None):
    """Process the full prompt; return (last-position logits, cache).

    Fills attention k/v (first ``s`` slots), mamba conv/ssm states, and the
    whisper cross-attention k/v, so ``decode_step`` can continue at pos=s.
    """
    _check_servable(cfg)
    h, _, _, kv = _hidden(params, batch, cfg, collect_kv=True)
    logits = _lm_head(params, h[:, -1:, :], cfg)[:, 0]
    if cfg.padded_vocab != cfg.vocab_size:
        vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(vmask, logits, -1e30)
    s = h.shape[1]
    max_len = max_len or s
    plan = layer_plan(cfg)
    cache = init_cache(cfg, h.shape[0], max_len)
    for i, kind in enumerate(plan):
        key = f"p{i}"
        if kv is None or key not in kv:
            continue
        if kind.mixer == "attn":
            cache[key]["k"] = jax.lax.dynamic_update_slice(
                cache[key]["k"], kv[key]["k"].astype(cache[key]["k"].dtype),
                (0, 0, 0, 0, 0))
            cache[key]["v"] = jax.lax.dynamic_update_slice(
                cache[key]["v"], kv[key]["v"].astype(cache[key]["v"].dtype),
                (0, 0, 0, 0, 0))
        elif kind.mixer == "mamba":
            cache[key]["conv"] = kv[key]["conv"].astype(
                cache[key]["conv"].dtype)
            cache[key]["ssm"] = kv[key]["ssm"].astype(cache[key]["ssm"].dtype)
    if cfg.enc_layers > 0:
        enc_out = _run_encoder(params, batch, cfg)
        for i, kind in enumerate(plan):
            if kind.cross:
                stk = params["stack"][f"p{i}"]
                xkeys = {n: stk[n] for n in stk
                         if n.startswith("xw") or n.startswith("xb")}
                k, v = jax.vmap(lambda p: _encode_cross_kv(p, enc_out, cfg))(
                    xkeys)
                cache[f"p{i}"]["xk"] = k.astype(cache[f"p{i}"]["xk"].dtype)
                cache[f"p{i}"]["xv"] = v.astype(cache[f"p{i}"]["xv"].dtype)
    return logits, cache


def _decode_attn_block(p, x_t, c, cfg: ArchConfig, pos):
    """x_t [b,1,D]; c holds k/v [b,T,Hkv,hd]; returns (out, new_c)."""
    h = rms_norm(x_t, p["attn_norm"], eps=cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg)
    if cfg.rope:
        posb = jnp.full((x_t.shape[0], 1), pos)
        q = rope(q, posb, theta=cfg.rope_theta)
        k = rope(k, posb, theta=cfg.rope_theta)
    kc = jax.lax.dynamic_update_slice(c["k"], k.astype(c["k"].dtype),
                                      (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(c["v"], v.astype(c["v"].dtype),
                                      (0, pos, 0, 0))
    mask = (jnp.arange(kc.shape[1]) <= pos).astype(jnp.float32)
    attn = decode_attention(q, kc, vc, mask)
    out = _attn_out(p, attn, cfg)
    return out, {"k": kc, "v": vc}


def _decode_cross_block(p, x_t, c, cfg: ArchConfig):
    h = rms_norm(x_t, p["xattn_norm"], eps=cfg.norm_eps)
    b = x_t.shape[0]
    hd = cfg.resolved_head_dim
    q = h @ p["xwq"]
    if cfg.use_bias:
        q = q + p["xbq"]
    q = q.reshape(b, 1, cfg.n_heads, hd)
    attn = decode_attention(q, c["xk"], c["xv"], None)
    return _attn_out(p, attn, cfg, prefix="x")


def decode_step(params, cache, tokens, pos, cfg: ArchConfig):
    """One-token decode. tokens [b,1] int32; pos scalar int32 (next slot).

    Returns (logits [b,V], new_cache).
    """
    _check_servable(cfg)
    from dataclasses import replace as _replace
    # Decode batches are tiny (T = b tokens); run MoE droppless by setting
    # capacity to the worst case C = T (capacity_factor = E/k) — capacity
    # dropping at C≈1 would otherwise zero out most tokens.
    if cfg.moe:
        cfg = _replace(cfg, capacity_factor=cfg.n_experts / cfg.top_k)
    x = params["embed"][tokens]                     # [b,1,D]
    if cfg.learned_pos:
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1)[None]
    plan = layer_plan(cfg)

    def period_body(carry, inp):
        x = carry
        pparams, pcache = inp
        new_cache = {}
        for i, kind in enumerate(plan):
            p = pparams[f"p{i}"]
            c = pcache.get(f"p{i}", {})
            nc = dict(c)
            if cfg.parallel_block and kind.mixer == "attn" \
                    and kind.mlp != "none":
                attn_out, upd = _decode_attn_block(p, x, c, cfg, pos)
                mlp_out, _ = _mlp_body(p, x, cfg, kind.mlp,
                                       norm_key="attn_norm")
                x = x + attn_out + mlp_out
                nc.update(upd)
            else:
                if kind.mixer == "attn":
                    attn_out, upd = _decode_attn_block(p, x, c, cfg, pos)
                    x = x + attn_out
                    nc.update(upd)
                elif kind.mixer == "mamba":
                    h = rms_norm(x, p["mamba_norm"], eps=cfg.norm_eps)
                    y, mcache = ssdlib.mamba2_decode_step(
                        p, h[:, 0], ssdlib.MambaCache(conv=c["conv"],
                                                      ssm=c["ssm"]),
                        head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                        d_state=cfg.ssm_state)
                    x = x + y[:, None, :]
                    nc.update({"conv": mcache.conv, "ssm": mcache.ssm})
                if kind.cross:
                    x = x + _decode_cross_block(p, x, c, cfg)
                if kind.mlp != "none":
                    mlp_out, _ = _mlp_body(p, x, cfg, kind.mlp)
                    x = x + mlp_out
            new_cache[f"p{i}"] = nc
        return x, new_cache

    x, new_cache = jax.lax.scan(period_body, x, (params["stack"], cache))
    h = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    logits = _lm_head(params, h, cfg)[:, 0]
    if cfg.padded_vocab != cfg.vocab_size:
        # mask (not slice): slicing a TP-sharded vocab dim forces a reshard
        vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(vmask, logits, -1e30)
    return logits, new_cache
