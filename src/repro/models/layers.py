"""Shared layer library for the architecture zoo.

Everything is a pure function over explicit parameter dicts (no flax), with
layer-stacked parameters (leading L dim) consumed by ``lax.scan`` so HLO size
stays O(1) in depth.

Parameter names: ``wq/wk/wv/wo``, ``w_gate/w_up/w_down``,
``moe_gate/moe_up/moe_down``, ``router``, ``mamba_*``, ``*norm*``.

Attention supports three implementations (the §Perf knob):
  * 'dense'   — materialized scores (baseline; XLA cost model sees it all)
  * 'chunked' — online-softmax scan over query blocks (flash-style in pure
                JAX; memory term drops at long sequence)
  * 'pallas'  — repro.kernels flash kernel (real TPU path; interpret-validated)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm", "rope", "rope_interleaved", "gqa_attention", "mla_attention",
    "swiglu", "gelu_mlp", "moe_layer_3d", "grouped_matmul", "route_topk",
    "moe_dropless", "masked_tied_xent", "xent_chunk_rows",
    "dense_init", "norm_init", "causal_scores_mask", "decode_attention",
]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------
def dense_init(key, shape, dtype, *, scale: float | None = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * s).astype(dtype)


def norm_init(shape, dtype):
    return jnp.ones(shape, dtype)


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------
def rms_norm(x, scale, *, eps: float = 1e-6, impl: str = "xla"):
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, scale, eps=eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    # scale in f32, output in x.dtype (keeps bf16 residual streams bf16)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------
def _rope_angles(positions, head_dim: int, theta: float):
    # positions: [...]; returns cos/sin of shape [..., head_dim//2]
    freqs = jnp.exp(-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim * jnp.log(theta))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, positions, *, theta: float = 10_000.0):
    """Apply rotary embedding. x: [..., seq, heads, head_dim]; positions
    broadcastable to [..., seq]."""
    hd = x.shape[-1]
    cos, sin = _rope_angles(positions, hd, theta)   # [..., s, hd/2]
    cos = cos[..., None, :]                          # [..., s, 1, hd/2]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_interleaved(x, positions, *, theta: float = 10_000.0):
    """RoPE over interleaved pairs ``(x0, x1), (x2, x3), ...`` (DeepSeek-V3's
    ``rope_interleave``): de-interleave to ``[x0, x2, ..., x1, x3, ...]``,
    then rotate halves.  The output keeps the de-interleaved order; applied
    to q and k alike it leaves every q·k product as the pairwise rotation
    gives it."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope(x, positions, theta=theta)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def causal_scores_mask(scores, q_pos, k_pos):
    """Mask via broadcasted position comparison (never materializes [S,S]
    beyond the scores tensor itself — fused by XLA)."""
    mask = q_pos[..., :, None] >= k_pos[..., None, :]
    return jnp.where(mask, scores, jnp.finfo(scores.dtype).min)


def _dense_attention(q, k, v, *, causal: bool, q_offset: int = 0):
    """q: [b,s,Hq,hd]; k,v: [b,t,Hkv,hd] (GQA grouping internal)."""
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * scale
    if causal:
        q_pos = jnp.arange(s) + q_offset
        k_pos = jnp.arange(t)
        scores = causal_scores_mask(scores, q_pos, k_pos)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hq, v.shape[-1])


def _chunked_attention(q, k, v, *, causal: bool, q_chunk: int = 512,
                       q_offset: int = 0):
    """Online-softmax over query chunks: flash-attention dataflow in pure
    JAX.  Memory O(s·q_chunk) instead of O(s²)."""
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, s)
    n_chunks = (s + q_chunk - 1) // q_chunk
    pad = n_chunks * q_chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = q.reshape(b, n_chunks, q_chunk, hkv, g, hd)
    k_pos = jnp.arange(t)

    def chunk_fn(carry, inp):
        qi, ci = inp
        scores = jnp.einsum("bskgd,btkd->bkgst", qi, k).astype(jnp.float32) * scale
        if causal:
            q_pos = ci * q_chunk + jnp.arange(q_chunk) + q_offset
            scores = causal_scores_mask(scores, q_pos, k_pos)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype), v)
        denom = jnp.transpose(l, (0, 3, 1, 2, 4))  # [b,s,k,g,1]
        return carry, (o / jnp.maximum(denom, 1e-30).astype(o.dtype))

    _, outs = jax.lax.scan(chunk_fn, (),
                           (jnp.moveaxis(qs, 1, 0), jnp.arange(n_chunks)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, n_chunks * q_chunk, hq,
                                           v.shape[-1])
    return out[:, :s]


def gqa_attention(q, k, v, *, causal: bool = True, impl: str = "dense",
                  q_offset: int = 0, q_chunk: int = 512):
    if impl == "dense":
        return _dense_attention(q, k, v, causal=causal, q_offset=q_offset)
    if impl == "chunked":
        return _chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                                  q_chunk=q_chunk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")


def mla_attention(p, h, positions, *, n_heads: int, nope: int, rope_dim: int,
                  v_dim: int, rank: int, theta: float, eps: float,
                  causal: bool = True, impl: str = "dense", q_chunk: int = 512):
    """Multi-head latent attention without a query LoRA (DeepSeek-V2/V3).

    h [b, s, D] (normed input).  Per head, q = [q_nope | q_rope] from
    ``wq``; the keys and values come from a shared latent: ``wkv_a`` gives
    ``[c | k_rope]`` (rank + rope_dim), ``c`` passes its own RMS norm
    (``kv_norm``) and ``wkv_b`` expands it to per-head ``[k_nope | v]``;
    ``k_rope`` is one head shared by all heads; RoPE rotates interleaved
    pairs (``rope_interleaved``).  Scores are scaled
    by ``(nope + rope_dim) ** -0.5``.  Returns [b, s, n_heads * v_dim]."""
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, n_heads, nope + rope_dim)
    kv = h @ p["wkv_a"]
    c, k_rot = kv[..., :rank], kv[..., rank:]
    kvb = (rms_norm(c, p["kv_norm"], eps=eps) @ p["wkv_b"]).reshape(
        b, s, n_heads, nope + v_dim)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_rot = rope_interleaved(q[..., nope:], positions, theta=theta)
    k_rot = rope_interleaved(k_rot[:, :, None, :], positions, theta=theta)
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rot, (b, s, n_heads, rope_dim))], axis=-1)
    out = gqa_attention(q, k, v, causal=causal, impl=impl, q_chunk=q_chunk)
    return out.reshape(b, s, n_heads * v_dim)


def decode_attention(q, k_cache, v_cache, kv_len_mask):
    """Single-token decode: q [b,1,Hq,hd], caches [b,T,Hkv,hd], mask [T] or
    [b,T] marking valid cache slots.  Reductions over the (sharded) T dim
    lower to the flash-decode partial-softmax combine under SPMD."""
    b, _, hq, hd = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache).astype(jnp.float32) * scale
    if kv_len_mask is not None:
        m = kv_len_mask if kv_len_mask.ndim == 2 else kv_len_mask[None, :]
        scores = jnp.where(m[:, None, None, :] > 0, scores,
                           jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(q.dtype), v_cache)
    return out.reshape(b, 1, hq, hd)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(x @ w_up + b_up)
    return h @ w_down + b_down


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------
def moe_layer_3d(x3, router_w, moe_gate, moe_up, moe_down, *, top_k: int,
                 capacity_factor: float = 1.25, impl: str = "einsum"):
    """Capacity-routed top-k MoE over the b·s tokens of x3 [b, s, D]; a
    token past its expert's capacity C = cf·k·T/E gets no output from it.

    router_w: [D, E]; moe_gate/up: [E, D, F]; moe_down: [E, F, D].
    impl='einsum' — Mesh-TF style one-hot dispatch/combine einsums (baseline).
    impl='scatter' — gather/scatter dispatch (beyond-paper optimization: the
    dispatch flops drop from O(T·E·C·D) to O(T·k·D)).
    Returns (out [b, s, D], aux) with aux = load-balancing loss ingredients.
    """
    b, s, D = x3.shape
    x = x3.reshape(b * s, D)
    T = b * s
    E = router_w.shape[-1]
    logits = (x @ router_w).astype(jnp.float32)            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)            # renormalize top-k
    C = max(1, int(capacity_factor * top_k * T / E))

    # position of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)       # [T, k, E]
    flat_onehot = onehot.reshape(T * top_k, E)
    pos_in_expert = jnp.cumsum(flat_onehot, axis=0) * flat_onehot - 1
    pos_in_expert = pos_in_expert.reshape(T, top_k, E)
    within_cap = (pos_in_expert >= 0) & (pos_in_expert < C)

    if impl == "einsum":
        cap_oh = jax.nn.one_hot(jnp.where(within_cap, pos_in_expert, -1), C,
                                dtype=x.dtype)                  # [T,k,E,C]
        dispatch = cap_oh                                        # bool-ish
        combine = cap_oh * gate_vals[..., None, None].astype(x.dtype)
        expert_in = jnp.einsum("tkec,td->ecd", dispatch, x)      # [E,C,D]
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, moe_gate))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in, moe_up)
        expert_out = jnp.einsum("ecf,efd->ecd", h, moe_down)     # [E,C,D]
        out = jnp.einsum("tkec,ecd->td", combine, expert_out)
    elif impl == "scatter":
        # Scatter tokens into [E, C, D] buffers, batched expert matmul,
        # gather back.  No T·E·C einsums.  scatter-ADD, not set: slots are
        # unique so the math is identical, but add's transpose is a plain
        # gather — scatter-set under vmap+AD lowers to a select-based
        # emulation with element-granular index tensors (observed 10-100x
        # memory blowup on granite).
        flat_expert = gate_idx.reshape(-1)                       # [T*k]
        flat_pos = jnp.take_along_axis(
            pos_in_expert.reshape(T * top_k, E),
            flat_expert[:, None], axis=1)[:, 0]                  # [T*k]
        ok = (flat_pos >= 0) & (flat_pos < C)
        slot = jnp.where(ok, flat_expert * C + flat_pos, E * C)  # overflow row
        buf = jnp.zeros((E * C + 1, D), x.dtype).at[slot].add(
            jnp.repeat(x, top_k, axis=0), mode="drop",
            unique_indices=True)
        expert_in = buf[:-1].reshape(E, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, moe_gate))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in, moe_up)
        expert_out = jnp.einsum("ecf,efd->ecd", h, moe_down)
        expert_out = expert_out.reshape(E * C, D)
        expert_out = jnp.concatenate(
            [expert_out, jnp.zeros((1, D), x.dtype)], axis=0)
        gathered = expert_out[jnp.where(ok, slot, E * C)]        # [T*k, D]
        out = (gathered.reshape(T, top_k, D)
               * gate_vals[..., None].astype(x.dtype)).sum(axis=1)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")

    # Aux loss ingredients (Switch-style load balance).
    density = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), 0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * density_proxy)
    return out.astype(x.dtype).reshape(b, s, D), aux


_WGRAD_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])), lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[])


def _map_rule(fn):
    """vmap rule: run ``fn`` once per batch element (``lax.map``).  JAX's own
    batching of ``ragged_dot`` handles only operands all batched on axis 0
    and fails when nested (the lane grid is a vmap over workers of a vmap
    over lanes)."""

    def rule(axis_size, in_batched, *args):
        full = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return jax.lax.map(lambda xs: fn(*xs), tuple(full)), True

    return rule


@jax.custom_batching.custom_vmap
def _gmm(x, w, sizes):
    """[M, K] x [G, K, N] -> [M, N]: rows of group g times w[g], in order;
    rows past the groups are zero.  (The TPU's grouped-matmul kernel visits
    only the tiles that hold group rows and leaves the rest of its output
    unwritten, so those rows are zeroed here.)"""
    rows = jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(rows, jax.lax.ragged_dot(x, w, sizes), 0)


@jax.custom_batching.custom_vmap
def _gmm_wgrad(x, dy, sizes):
    """[M, K], [M, N] -> [G, K, N]: per group, its rows of x^T dy (zero for
    an empty group, which the TPU kernel leaves unwritten)."""
    dw = jax.lax.ragged_dot_general(x, dy, sizes, _WGRAD_DIMS)
    return jnp.where(sizes[:, None, None] > 0, dw, 0)


_gmm.def_vmap(_map_rule(_gmm))
_gmm_wgrad.def_vmap(_map_rule(_gmm_wgrad))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """Grouped product over contiguous row groups (``jax.lax.ragged_dot``),
    differentiable and batchable at any vmap depth."""
    return _gmm(x, w, sizes)


def _grouped_fwd(x, w, sizes):
    return _gmm(x, w, sizes), (x, w, sizes)


def _grouped_bwd(res, dy):
    x, w, sizes = res
    return (_gmm(dy, jnp.swapaxes(w, 1, 2), sizes).astype(x.dtype),
            _gmm_wgrad(x, dy, sizes).astype(w.dtype), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def route_topk(x, router_w, *, top_k: int, scale: float = 1.0):
    """Sigmoid routing over every expert (DeepSeek-V3 ``noaux_tc`` with one
    group and the correction bias at zero), in float32 at ``highest``
    precision, so the top-k choice matches a float32 reference except at
    true ties: the top-k sigmoid scores are chosen, renormalised to sum to
    1 (``norm_topk_prob``) and multiplied by ``scale``.  x [T, D], router_w
    [D, E].  Returns (weights [T, k] float32, expert ids [T, k] int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * scale, idx


def moe_dropless(x, router_w, moe_gate, moe_up, moe_down, *, top_k: int,
                 scale: float = 1.0):
    """Dropless top-k SwiGLU experts over the experts held here.

    The router scores all E experts (``router_w`` [D, E]); ``moe_gate``/
    ``moe_up`` [H, D, F] and ``moe_down`` [H, F, D] hold experts 0 .. H - 1
    (one chip's share under expert parallelism; H == E holds them all).  Every
    (token, choice) pair routed to a held expert is computed, none dropped:
    the T·k pairs are sorted by expert, pairs of experts held elsewhere last,
    and a grouped product (``grouped_matmul``) gives each held expert
    exactly its rows; rows past the held groups are zero.  What the absent
    experts add is left out, as on the chip that holds this share.

    Returns (out [T, D], counts): ``moe_routed_rows``, the pairs routed to
    held experts, and ``moe_buffer_rows``, the rows the grouped products
    are given (T·k)."""
    T, D = x.shape
    H = moe_gate.shape[0]
    w, idx = route_topk(x, router_w, top_k=top_k, scale=scale)
    flat = idx.reshape(-1)
    held = flat < H
    group = jnp.where(held, flat, H)
    order = jnp.argsort(group, stable=True)
    tok = order // top_k
    sizes = jnp.bincount(group, length=H + 1)[:H].astype(jnp.int32)
    xs = x[tok]
    hid = (jax.nn.silu(grouped_matmul(xs, moe_gate, sizes))
           * grouped_matmul(xs, moe_up, sizes))
    ys = grouped_matmul(hid, moe_down, sizes)
    ws = jnp.where(held, w.reshape(-1), 0.0)[order].astype(ys.dtype)
    out = jnp.zeros((T, D), ys.dtype).at[tok].add(ys * ws[:, None])
    counts = {"moe_routed_rows": held.sum().astype(jnp.float32),
              "moe_buffer_rows": jnp.float32(T * top_k)}
    return out.astype(x.dtype), counts


# --------------------------------------------------------------------------
# tied-head cross-entropy over the masked rows
# --------------------------------------------------------------------------
def xent_chunk_rows(tokens: int, rate: float) -> int:
    """Rows one trip of ``masked_tied_xent`` takes: the multiple of 128 at
    or above 1.25 x the expected masked rows, at most ``tokens`` (512 for
    20 x 128 tokens at rate 0.15)."""
    return min(tokens, 128 * max(1, math.ceil(1.25 * rate * tokens / 128)))


def _xent_trips(n, C):
    """Trips over the compacted rows; the first is taken even with none."""
    return jnp.maximum(1, (n + C - 1) // C)


def _over_trips(trip, n, C, carry):
    """``carry = trip(k, *carry)`` for each trip k.  Trip 0 runs straight
    and the loop takes the rest (none on a batch of at most C masked rows),
    so the common case pays no loop-carried select under the lane vmap."""
    carry = trip(0, *carry)
    return jax.lax.while_loop(
        lambda c: c[0] < _xent_trips(n, C),
        lambda c: (c[0] + 1,) + trip(c[0], *c[1:]),
        (jnp.ones((), n.dtype),) + carry)[1:]


def _xent_chunk(h, embed, labels, rows, k, C):
    """Trip ``k``: its rows of h, their logits through the tied head (the
    backend's default precision) and their labels."""
    r = jax.lax.dynamic_slice_in_dim(rows, k * C, C)
    hk = h[r]
    return hk, hk @ embed.T, labels[r]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _compact_xent(h, embed, labels, mask, rows, n, C):
    return _compact_xent_fwd(h, embed, labels, mask, rows, n, C)[0]


def _compact_xent_fwd(h, embed, labels, mask, rows, n, C):
    Tp = rows.shape[0]
    valid = jnp.arange(Tp) < n

    def trip(k, nll, lse_buf):
        _, logits, lab = _xent_chunk(h, embed, labels, rows, k, C)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
        v = jax.lax.dynamic_slice_in_dim(valid, k * C, C)
        nll = nll + jnp.where(v, lse - gold, 0.0).sum()
        return nll, jax.lax.dynamic_update_slice_in_dim(lse_buf, lse, k * C, 0)

    nll, lse_buf = _over_trips(trip, n, C, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((Tp,), jnp.float32)))
    loss = nll / jnp.maximum(n, 1).astype(jnp.float32)
    return loss, (h, embed, labels, mask, rows, n, lse_buf)


def _compact_xent_bwd(C, res, g):
    h, embed, labels, mask, rows, n, lse_buf = res
    Tp = rows.shape[0]
    V = embed.shape[0]
    scale = jnp.where(jnp.arange(Tp) < n,
                      g / jnp.maximum(n, 1).astype(jnp.float32), 0.0)

    def trip(k, dE, dh_c):
        hk, logits, lab = _xent_chunk(h, embed, labels, rows, k, C)
        lse = jax.lax.dynamic_slice_in_dim(lse_buf, k * C, C)
        s = jax.lax.dynamic_slice_in_dim(scale, k * C, C)
        # (softmax - onehot) * g / n, zero on the buffer's padding rows
        dlog = (jnp.exp(logits - lse[:, None])
                - (jnp.arange(V)[None, :] == lab[:, None])) * s[:, None]
        dE = dE + jnp.einsum("cv,cd->vd", dlog, hk)
        return dE, jax.lax.dynamic_update_slice_in_dim(dh_c, dlog @ embed,
                                                       k * C, 0)

    dE, dh_c = _over_trips(trip, n, C, (jnp.zeros_like(embed),
                                        jnp.zeros((Tp,) + h.shape[1:], h.dtype)))
    # Back to token order: row t is the (cumsum(mask)[t] - 1)-th compacted.
    pos = jnp.maximum(jnp.cumsum(mask) - 1, 0)
    dh = jnp.where(mask[:, None], dh_c[pos], 0.0).astype(h.dtype)
    return dh, dE.astype(embed.dtype), None, None, None, None


_compact_xent.defvjp(_compact_xent_fwd, _compact_xent_bwd)


def masked_tied_xent(h, embed, labels, mask, *, rate: float):
    """Mean cross-entropy of ``labels`` at the masked positions, through the
    tied head ``embed`` [V, D], computed on the masked rows alone.

    h [..., D] hidden states, labels and mask [...] over the same positions.
    The masked rows are compacted, in order, into a buffer and taken ``C``
    at a time (``xent_chunk_rows`` of the token count and the mask's
    expected ``rate``), ``max(1, ceil(count / C))`` trips; no row is ever
    dropped.  An unmasked row's cotangent through the softmax is exactly
    zero, so this is the full-logits masked mean, up to the order of the
    sum over rows.  The backward recomputes each trip's logits.

    Returns (loss, counts): ``head_rows``, the masked rows the loss reads,
    and ``head_buffer_rows``, trips x C, the rows whose logits were
    computed."""
    D = h.shape[-1]
    h, labels, mask = h.reshape(-1, D), labels.reshape(-1), mask.reshape(-1)
    T = labels.shape[0]
    C = xent_chunk_rows(T, rate)
    n = mask.sum(dtype=jnp.int32)
    (rows,) = jnp.nonzero(mask, size=-(-T // C) * C, fill_value=0)
    loss = _compact_xent(h, embed, labels, mask, rows, n, C)
    counts = {"head_rows": n.astype(jnp.float32),
              "head_buffer_rows": (_xent_trips(n, C) * C).astype(jnp.float32)}
    return loss, counts
