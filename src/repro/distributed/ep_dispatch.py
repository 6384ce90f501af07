"""Manual expert-parallel MoE dispatch (shard_map) — §Perf B3.

Why: under auto-SPMD, the capacity-scatter dispatch makes XLA emit f32
all-to-alls + buffer all-gathers totalling ~200× the ideal wire bytes
(EXPERIMENTS.md §Perf B0).  The structural observation that fixes it: with
activations replicated over the `model` axis (the Megatron-SP gather point)
and experts sharded over `model`, **expert-parallel dispatch needs no token
communication at all** — chip (d, m) already holds both its `data`-shard of
tokens and its `model`-shard of experts:

  1. each chip routes its local tokens, keeps only slots targeting its
     local experts, and builds [E_loc, C, D] capacity buckets — all local;
  2. expert GEMMs run on FSDP-gathered weights (one all-gather of
     [E_loc, D, F] over `data` — the standard per-layer FSDP unshard);
  3. each chip scatter-adds its experts' outputs back to its local token
     frame [T_loc, D]; a single psum over `model` sums the k expert
     contributions that live on different chips.

Per-layer wire bytes: psum 2·T_loc·D + FSDP gather — vs the auto-SPMD
scatter's hundreds of MB × thousands of sites.

Capacity is per (data-shard, expert): C = ceil(cf·k·T_loc/E) — the same
local-capacity semantics as per-chunk dispatch (F7), so drop behaviour
matches `moe_seq_chunk`-style dispatch, not global routing.

Differentiable (shard_map + psum/all_gather have transposes); used by the
planner for large MoE archs on the non-vmapped (W=P=1) round path and the
serve paths.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["make_ep_dispatch"]


def _local_moe(x, router_w, gate_w, up_w, down_w, *, top_k: int,
               capacity_factor: float, n_experts: int, model_axis: str,
               fsdp_axis: str | None, model_size: int):
    """Per-chip body. x [T_loc, D]; gate/up [E_loc, D_loc, F]; down
    [E_loc, F, D_loc]."""
    T, D = x.shape
    E, E_loc = n_experts, gate_w.shape[0]
    m_idx = jax.lax.axis_index(model_axis)
    e0 = m_idx * E_loc                                  # first local expert

    # ---- routing (local tokens, global experts) ---------------------------
    logits = (x @ router_w).astype(jnp.float32)         # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)   # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    C = max(1, int(capacity_factor * top_k * T / E))

    # position of each (t, k) slot within its expert's local bucket
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # [T, k, E]
    flat_oh = onehot.reshape(T * top_k, E)
    pos = jnp.cumsum(flat_oh, axis=0) * flat_oh - 1            # [T*k, E]
    flat_e = gate_idx.reshape(-1)                              # [T*k]
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]
    local = (flat_e >= e0) & (flat_e < e0 + E_loc)
    ok = local & (flat_pos >= 0) & (flat_pos < C)
    slot = jnp.where(ok, (flat_e - e0) * C + flat_pos, E_loc * C)

    # ---- bucket build (local scatter-add) ----------------------------------
    buf = jnp.zeros((E_loc * C + 1, D), x.dtype).at[slot].add(
        jnp.repeat(x, top_k, axis=0), mode="drop", unique_indices=True)
    expert_in = buf[:-1].reshape(E_loc, C, D)

    # ---- expert GEMMs on FSDP-gathered weights -----------------------------
    if fsdp_axis is not None:
        gate_w = jax.lax.all_gather(gate_w, fsdp_axis, axis=1, tiled=True)
        up_w = jax.lax.all_gather(up_w, fsdp_axis, axis=1, tiled=True)
        down_w = jax.lax.all_gather(down_w, fsdp_axis, axis=2, tiled=True)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, gate_w))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, up_w)
    expert_out = jnp.einsum("ecf,efd->ecd", h, down_w)
    expert_out = jnp.concatenate(
        [expert_out.reshape(E_loc * C, D), jnp.zeros((1, D), x.dtype)], 0)

    # ---- combine: local gather + psum over the expert axis -----------------
    gathered = expert_out[jnp.where(ok, slot, E_loc * C)]       # [T*k, D]
    out = (gathered.reshape(T, top_k, D)
           * gate_vals[..., None].astype(x.dtype)).sum(axis=1)  # [T, D]
    out = jax.lax.psum(out, model_axis)

    # Switch aux loss ingredients (psum'd so every shard agrees)
    density = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E,
                                      dtype=jnp.float32), 0)
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))
    return out, aux


def make_ep_dispatch(mesh, *, batch_axes=("data",), model_axis="model",
                     fsdp_axis="data", seq_chunk: int = 0):
    """Build the cfg.moe_dispatch hook: (x3 [b,s,D], router, gate, up, down,
    top_k, capacity_factor) -> (out [b,s,D], aux).

    ``seq_chunk`` > 0 scans the dispatch over sequence blocks (F7's buffer
    cap applied to the manual path — jamba's 14336-wide experts need it)."""
    bspec = tuple(batch_axes) if batch_axes else None

    def dispatch(x3, router_w, gate_w, up_w, down_w, *, top_k,
                 capacity_factor):
        b, s_tot, D = x3.shape
        E = router_w.shape[-1]
        n_model = mesh.shape[model_axis]

        def run(x_blk):
            s = x_blk.shape[1]

            def body(xl, rw, gw, uw, dw):
                bl = xl.shape[0]
                out, aux = _local_moe(
                    xl.reshape(bl * s, D), rw, gw, uw, dw, top_k=top_k,
                    capacity_factor=capacity_factor, n_experts=E,
                    model_axis=model_axis, fsdp_axis=fsdp_axis,
                    model_size=n_model)
                # mean aux over data shards so the scalar is replicated
                for a in batch_axes:
                    aux = jax.lax.pmean(aux, a)
                return out.reshape(bl, s, D), aux

            fn = jax.shard_map(
                body, mesh=mesh, check_vma=False,
                in_specs=(P(bspec, None, None),            # x: batch sharded
                          P(None, None),                   # router replicated
                          P(model_axis, fsdp_axis, None),  # gate [E, D, F]
                          P(model_axis, fsdp_axis, None),  # up
                          P(model_axis, None, fsdp_axis)),  # down [E, F, D]
                out_specs=(P(bspec, None, None), P()))
            return fn(x_blk, router_w, gate_w, up_w, down_w)

        if not seq_chunk or s_tot <= seq_chunk:
            return run(x3)
        pad = (-s_tot) % seq_chunk
        if pad:
            x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
        nc = x3.shape[1] // seq_chunk
        xs = jnp.moveaxis(x3.reshape(b, nc, seq_chunk, D), 1, 0)

        def scan_body(carry, xc):
            out, aux = run(xc)
            return carry + aux, out

        aux, outs = jax.lax.scan(scan_body, jnp.zeros((), jnp.float32), xs)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, nc * seq_chunk, D)[:, :s_tot]
        return out, aux / nc

    return dispatch
