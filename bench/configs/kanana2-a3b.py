"""Plain reference of the ``kanana2-a3b`` configuration (``kanana2-a3b.json``
beside it): one chip's share of kanana-2-30b-a3b (DeepSeek-V3 layout).

Written out in ``jax.numpy`` from the published layer equations: token
embedding; per layer an RMS norm, multi-head latent attention without a
query LoRA, a residual, an RMS norm and the MLP, a residual; a final RMS
norm and the untied head.  Latent attention: per head q = [q_nope | q_rope]
(``wq``); ``wkv_a`` gives the latent c and one shared rope key, c passes its
own RMS norm (``kv_norm``) and ``wkv_b`` expands it to per-head [k_nope | v];
RoPE rotates interleaved pairs (x0, x1), (x2, x3), ...; causal softmax
scaled by (nope + rope) ** -0.5.  Layer 0's MLP is a dense SwiGLU; each
later layer routes over every expert of the deployment: sigmoid scores of
the router, the top-k of score + correction bias (0), their scores
renormalised and scaled; the experts held here are computed one by one, each
on every token weighted by its routing weight (zero where not chosen), and
the shared experts (one SwiGLU) are added.  The experts held elsewhere are
left out, as the chip holding this share leaves them out.  The loss is the
causal next-token cross-entropy over the vocabulary slice.  Client data,
client sizes and initial weights follow the configuration's dataset and
init rules from the seed alone.  Nothing here imports the program under
test; the parameter tree is laid out as the program lays it out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _dense(key, shape, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * s


def _dims(cfg):
    return dict(D=cfg["hidden_size"], H=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rd=cfg["qk_rope_head_dim"],
                vd=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
                F=cfg["intermediate_size"], Fm=cfg["moe_intermediate_size"],
                Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                E=cfg["deployment"]["n_routed_experts"],
                held=cfg["n_routed_experts"], V=cfg["vocab_size"],
                n_dense=cfg["first_k_dense_replace"],
                n_moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def _attn_init(key, n, d):
    ks = jax.random.split(key, 5)
    D, H = d["D"], d["H"]
    return {
        "attn_norm": jnp.ones((n, D), jnp.float32),
        "kv_norm": jnp.ones((n, d["r"]), jnp.float32),
        "mlp_norm": jnp.ones((n, D), jnp.float32),
        "wq": _dense(ks[0], (n, D, H * (d["nope"] + d["rd"]))),
        "wkv_a": _dense(ks[1], (n, D, d["r"] + d["rd"])),
        "wkv_b": _dense(ks[2], (n, d["r"], H * (d["nope"] + d["vd"]))),
        "wo": _dense(ks[3], (n, H * d["vd"], D)),
    }


def init(key, cfg):
    d = _dims(cfg)
    D, L0, L = d["D"], d["n_dense"], d["n_moe"]
    ks = jax.random.split(key, 8)
    dense = _attn_init(ks[0], L0, d)
    dense.update({"w_gate": _dense(ks[1], (L0, D, d["F"])),
                  "w_up": _dense(ks[2], (L0, D, d["F"])),
                  "w_down": _dense(ks[3], (L0, d["F"], D))})
    moe = _attn_init(ks[4], L, d)
    km = jax.random.split(ks[5], 7)
    moe.update({
        "router": _dense(km[0], (L, D, d["E"])),
        "moe_gate": _dense(km[1], (L, d["held"], D, d["Fm"])),
        "moe_up": _dense(km[2], (L, d["held"], D, d["Fm"])),
        "moe_down": _dense(km[3], (L, d["held"], d["Fm"], D)),
        "shared_gate": _dense(km[4], (L, D, d["Fs"])),
        "shared_up": _dense(km[5], (L, D, d["Fs"])),
        "shared_down": _dense(km[6], (L, d["Fs"], D)),
    })
    ke = jax.random.split(ks[6], 2)
    return {
        "embed": _dense(ke[0], (d["V"], D), scale=0.02),
        "lm_head": _dense(ke[1], (D, d["V"])),
        "final_norm": jnp.ones((D,), jnp.float32),
        "dense": {"p0": dense},
        "stack": {"p0": moe},
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """Rotate each interleaved pair (x[2i], x[2i+1]) of x [b, s, h, dim] by
    position x theta ** (-2i / dim)."""
    s, dim = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], -1).reshape(x.shape)


def mla(p, h, cfg):
    """Latent attention of one layer: h [b, s, D] (normed) -> [b, s, D]."""
    d = _dims(cfg)
    b, s, _ = h.shape
    H, nope, rd, vd, r = d["H"], d["nope"], d["rd"], d["vd"], d["r"]
    q = (h @ p["wq"]).reshape(b, s, H, nope + rd)
    kv = h @ p["wkv_a"]
    c = _rms(kv[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    kvb = (c @ p["wkv_b"]).reshape(b, s, H, nope + vd)
    k_rope = _rope_pairs(kv[..., None, r:], cfg["rope_theta"])     # one head
    q_rope = _rope_pairs(q[..., nope:], cfg["rope_theta"])
    scores = (jnp.einsum("bshd,bthd->bhst", q[..., :nope], kvb[..., :nope])
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope[:, :, 0]))
    scores = scores / math.sqrt(nope + rd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("bhst,bthd->bshd", att, kvb[..., nope:]).reshape(b, s, H * vd)
    return out @ p["wo"]


def route(x, router, cfg, bias=None):
    """x [T, D] -> (weights [T, k], expert ids [T, k]) over all E experts."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST))
    choice = scores if bias is None else scores + bias
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], idx


def _swiglu(x, g, u, dn):
    return (jax.nn.silu(x @ g) * (x @ u)) @ dn


def routed_experts(p, x, cfg, bias=None):
    """The held experts' part of the routed output, x [T, D] -> [T, D]: a
    loop over the held experts (a scan), each on every token."""
    w, idx = route(x, p["router"], cfg, bias)

    def expert(out, e):
        g, u, dn, i = e
        we = jnp.sum(jnp.where(idx == i, w, 0.0), -1)
        return out + we[:, None].astype(x.dtype) * _swiglu(x, g, u, dn), None

    held = p["moe_gate"].shape[0]
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (p["moe_gate"], p["moe_up"], p["moe_down"], jnp.arange(held)))
    return out


def moe_mlp(p, x, cfg):
    """Routed (held) plus shared experts, x [T, D] -> [T, D]."""
    return (routed_experts(p, x, cfg)
            + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"]))


def _layer(p, x, cfg, moe):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p, _rms(x, p["attn_norm"], eps), cfg)
    h = _rms(x, p["mlp_norm"], eps)
    if moe:
        b, s, D = h.shape
        return x + moe_mlp(p, h.reshape(b * s, D), cfg).reshape(b, s, D)
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def logits(p, tokens, cfg):
    x = p["embed"][tokens]
    # A loop over each stack's layers (a scan), each layer recomputed in the
    # backward pass: the reference holds one layer's activations at a time.
    for stack, moe in ((p["dense"]["p0"], False), (p["stack"]["p0"], True)):
        layer = jax.checkpoint(lambda x, lp, moe=moe: (_layer(lp, x, cfg, moe), None))
        x, _ = jax.lax.scan(layer, x, stack)
    return _rms(x, p["final_norm"], cfg["rms_norm_eps"]) @ p["lm_head"]


def loss(p, batch, cfg):
    """Causal next-token cross-entropy, the mean over every predicted token."""
    toks = batch["tokens"]
    logp = jax.nn.log_softmax(logits(p, toks[:, :-1], cfg), -1)
    gold = jnp.take_along_axis(logp, toks[:, 1:, None], -1)[..., 0]
    return -gold.mean()


def population(seed, cfg):
    """Client sample counts, drawn from the seed as the dataset states."""
    d = cfg["data"]
    rng = np.random.default_rng(seed)
    sizes = rng.zipf(a=d["zipf_a"], size=d["n_clients"]).astype(np.float64)
    sizes = np.clip(sizes, d["size_min"], d["size_max"]).astype(np.int64)
    return np.maximum(sizes, d["batch_size"]), None


def side(cids, table, cfg):
    """Per-client vocabulary offset of the non-IID token skew."""
    quarter = max(cfg["vocab_size"] // 4, 1)
    return ((np.asarray(cids, np.int64) * 2_654_435_761) % quarter).astype(np.int32)


def batches(seed, cid32, bi32, extra, cfg):
    """``[N]`` (client, batch index) pairs -> ``{"tokens": [N, b, s]}``."""
    V = cfg["vocab_size"]
    bs, sl = cfg["data"]["batch_size"], cfg["data"]["seq_len"]

    def one(c, i, off):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), c), i)
        k1, _ = jax.random.split(key)
        base = jax.random.randint(k1, (bs, sl), 0, V)
        return {"tokens": ((base // 4 + off) % V).astype(jnp.int32)}

    return jax.vmap(one)(cid32, bi32, extra)


def macs_per_token(cfg):
    """Multiply-accumulates per token of one forward pass.  The routed
    experts count at their expected share: each token picks k of the E
    experts, so k x held / E expert MLPs a token run here (6 x 8 / 128 =
    0.375).  Attention counts every key position, as the encoder's does."""
    d = _dims(cfg)
    D, H, s = d["D"], d["H"], cfg["data"]["seq_len"]
    qk, layers = d["nope"] + d["rd"], d["n_dense"] + d["n_moe"]
    attn = (D * H * qk + D * (d["r"] + d["rd"]) + d["r"] * H * (d["nope"] + d["vd"])
            + H * d["vd"] * D + s * H * (qk + d["vd"]))
    routed = cfg["num_experts_per_tok"] * d["held"] / d["E"] * 3 * D * d["Fm"]
    moe = D * d["E"] + 3 * D * d["Fs"] + routed
    return layers * attn + d["n_dense"] * 3 * D * d["F"] + d["n_moe"] * moe + D * d["V"]


def flops_per_client_step(cfg):
    """Forward and backward of one local step: 6 FLOPs per MAC per token."""
    tokens = cfg["data"]["batch_size"] * cfg["data"]["seq_len"]
    return 6.0 * tokens * macs_per_token(cfg)
