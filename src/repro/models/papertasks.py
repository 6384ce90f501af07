"""The paper's four FL-task models (§5.1), sized to the paper's tasks but
operating on the synthetic federated datasets of ``repro.data.federated``,
and one public LM client architecture:

* IC  — Image Classification: ShuffleNet-style MLP-mixer over feature
        vectors, 596 classes (OpenImage).
* SR  — Speech Recognition: ResNet-style residual MLP over audio features,
        35 classes (Google Speech Commands).
* TG  — Text Generation: two-cell LSTM language model (Shakespeare / LEAF).
* MLM — Masked Language Modelling: RoBERTa-style bidirectional transformer
        encoder with a masked-token objective (Reddit).
* kanana2-a3b — one chip's share of kanana-2-30b-a3b (DeepSeek-V3-style
        latent attention and dropless sigmoid-routed experts, the unified LM
        stack of ``repro.models.lm``) with a causal next-token loss, over the
        MLM population (``repro.configs.kanana_2_30b_a3b.SHARE``).

The paper treats these as opaque client workloads; what matters for Pollen is
their *training-time* and *model-size* profiles (Table 6: TG 3.28MB,
IC 26.45MB, MLM 60.37MB, SR 85.14MB).  ``TASK_MODELS[task].target_bytes``
records the paper's sizes; our synthetic-feature variants keep the relative
ordering so communication/aggregation benchmarks reproduce the paper's
scaling.  All models are pure param-dict functions, jit/vmap/scan-safe, and
run under the federated round step.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, masked_tied_xent

__all__ = ["TaskModel", "TASK_MODELS", "make_task_model"]


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    gold = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -gold.mean()


# ---------------------------------------------------------------------------
# IC — ShuffleNet-style grouped blocks over feature vectors
# ---------------------------------------------------------------------------
def ic_init(key, *, input_dim=64, width=256, n_blocks=4, n_classes=596,
            groups=4, dtype=jnp.float32):
    ks = jax.random.split(key, 2 * n_blocks + 2)
    p = {"stem": dense_init(ks[0], (input_dim, width), dtype)}
    for i in range(n_blocks):
        # grouped pointwise convs (the ShuffleNetV2 motif on vector features)
        p[f"g1_{i}"] = dense_init(ks[2 * i + 1],
                                  (groups, width // groups, width // groups),
                                  dtype)
        p[f"g2_{i}"] = dense_init(ks[2 * i + 2],
                                  (groups, width // groups, width // groups),
                                  dtype)
    p["head"] = dense_init(ks[-1], (width, n_classes), dtype)
    return p


def _channel_shuffle(x, groups):
    b, w = x.shape
    return x.reshape(b, groups, w // groups).swapaxes(1, 2).reshape(b, w)


def ic_forward(p, x, *, groups=4):
    h = jax.nn.relu(x @ p["stem"])
    n_blocks = sum(1 for k in p if k.startswith("g1_"))
    for i in range(n_blocks):
        b, w = h.shape
        hg = h.reshape(b, groups, w // groups)
        hg = jax.nn.relu(jnp.einsum("bgi,gio->bgo", hg, p[f"g1_{i}"]))
        hg = jnp.einsum("bgi,gio->bgo", hg, p[f"g2_{i}"])
        h = jax.nn.relu(h + _channel_shuffle(hg.reshape(b, w), groups))
    return h @ p["head"]


# ---------------------------------------------------------------------------
# SR — ResNet-34-style residual MLP
# ---------------------------------------------------------------------------
def sr_init(key, *, input_dim=64, width=512, n_blocks=8, n_classes=35,
            dtype=jnp.float32):
    ks = jax.random.split(key, 2 * n_blocks + 2)
    p = {"stem": dense_init(ks[0], (input_dim, width), dtype)}
    for i in range(n_blocks):
        p[f"w1_{i}"] = dense_init(ks[2 * i + 1], (width, width), dtype)
        p[f"w2_{i}"] = dense_init(ks[2 * i + 2], (width, width), dtype)
    p["head"] = dense_init(ks[-1], (width, n_classes), dtype)
    return p


def sr_forward(p, x):
    h = jax.nn.relu(x @ p["stem"])
    n_blocks = sum(1 for k in p if k.startswith("w1_"))
    for i in range(n_blocks):
        z = jax.nn.relu(h @ p[f"w1_{i}"]) @ p[f"w2_{i}"]
        h = jax.nn.relu(h + z)
    return h @ p["head"]


# ---------------------------------------------------------------------------
# TG — two-cell LSTM LM (LEAF Shakespeare)
# ---------------------------------------------------------------------------
def tg_init(key, *, vocab=90, embed=8, hidden=256, n_cells=2,
            dtype=jnp.float32):
    ks = jax.random.split(key, 2 * n_cells + 2)
    p = {"embed": dense_init(ks[0], (vocab, embed), dtype, scale=0.05)}
    d_in = embed
    for i in range(n_cells):
        p[f"wx_{i}"] = dense_init(ks[2 * i + 1], (d_in, 4 * hidden), dtype)
        p[f"wh_{i}"] = dense_init(ks[2 * i + 2], (hidden, 4 * hidden), dtype)
        p[f"b_{i}"] = jnp.zeros((4 * hidden,), dtype)
        d_in = hidden
    p["head"] = dense_init(ks[-1], (hidden, vocab), dtype)
    return p


def _lstm_cell(p, i, xs):
    """xs [b, s, d_in] -> hs [b, s, hidden]."""
    hidden = p[f"wh_{i}"].shape[0]
    b = xs.shape[0]

    def step(carry, x_t):
        h, c = carry
        gates = x_t @ p[f"wx_{i}"] + h @ p[f"wh_{i}"] + p[f"b_{i}"]
        ii, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(ii) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    init = (jnp.zeros((b, hidden), xs.dtype), jnp.zeros((b, hidden), xs.dtype))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


def tg_forward(p, tokens):
    x = p["embed"][tokens]
    n_cells = sum(1 for k in p if k.startswith("wx_"))
    for i in range(n_cells):
        x = _lstm_cell(p, i, x)
    return x @ p["head"]


# ---------------------------------------------------------------------------
# MLM — RoBERTa-style bidirectional encoder with masked-token loss
# ---------------------------------------------------------------------------
def mlm_init(key, *, vocab=30_000, d_model=256, n_layers=4, n_heads=4,
             d_ff=1024, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    L = n_layers
    p = {
        "embed": dense_init(ks[0], (vocab, d_model), dtype, scale=0.02),
        "wq": dense_init(ks[1], (L, d_model, d_model), dtype),
        "wk": dense_init(ks[2], (L, d_model, d_model), dtype),
        "wv": dense_init(ks[3], (L, d_model, d_model), dtype),
        "wo": dense_init(ks[4], (L, d_model, d_model), dtype),
        "w_up": dense_init(ks[5], (L, d_model, d_ff), dtype),
        "w_down": dense_init(ks[6], (L, d_ff, d_model), dtype),
        "ln1": jnp.ones((L, d_model), dtype),
        "ln2": jnp.ones((L, d_model), dtype),
    }
    return p


def mlm_encode(p, tokens, *, n_heads: int = 4):
    """The encoder: tokens [b, s] -> hidden states [b, s, d], before the
    tied head."""
    x = p["embed"][tokens]
    b, s, d = x.shape
    nh = n_heads
    hd = d // nh

    def layer(x, lp):
        wq, wk, wv, wo, wu, wd, l1, l2 = lp
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * l1
        q = (h @ wq).reshape(b, s, nh, hd)
        k = (h @ wk).reshape(b, s, nh, hd)
        v = (h @ wv).reshape(b, s, nh, hd)
        sc = jnp.einsum("bsnd,btnd->bnst", q, k) / jnp.sqrt(hd)
        a = jax.nn.softmax(sc, -1)
        o = jnp.einsum("bnst,btnd->bsnd", a, v).reshape(b, s, d)
        x = x + o @ wo
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * l2
        x = x + jax.nn.gelu(h @ wu) @ wd
        return x, None

    x, _ = jax.lax.scan(layer, x, (p["wq"], p["wk"], p["wv"], p["wo"],
                                   p["w_up"], p["w_down"], p["ln1"], p["ln2"]))
    return x


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TaskModel:
    name: str
    init: Callable
    loss_fn: Callable            # (params, batch) -> scalar, or
    #                              (scalar, counts) for mlm and kanana2-a3b
    target_bytes: float          # paper Table 6 model size (MB -> bytes)
    kind: str                    # 'labelled' | 'tokens'


def _ic_loss(p, batch):
    return _xent(ic_forward(p, batch["x"]), batch["y"])


def _sr_loss(p, batch):
    return _xent(sr_forward(p, batch["x"]), batch["y"])


def _tg_loss(p, batch):
    toks = batch["tokens"]
    logits = tg_forward(p, toks[:, :-1])
    return _xent(logits, toks[:, 1:])


@functools.partial(jax.jit, static_argnames=("mask_rate", "mask_token"))
def _mlm_loss(p, batch, *, mask_rate=0.15, mask_token=3):
    """(loss, the tied head's row counts): the cross-entropy at the masked
    positions, the head run on those rows alone (``masked_tied_xent``).

    Jitted so that each S bucket's round-step program reuses one trace of
    it (the encoder's layer scan, the head's custom VJP and its loops)
    instead of tracing and batching it anew; XLA inlines the call."""
    toks = batch["tokens"]
    # deterministic pseudo-mask from token content (no rng plumbing needed)
    mask = (toks * 2_654_435 % 100) < int(mask_rate * 100)
    h = mlm_encode(p, jnp.where(mask, mask_token, toks))
    return masked_tied_xent(h, p["embed"], toks, mask, rate=mask_rate)


def _kanana_init(key):
    from repro.configs.kanana_2_30b_a3b import SHARE
    from repro.models.lm import init_params
    # One program: op-by-op, every leaf's draw would compile on its own.
    return jax.jit(lambda k: init_params(k, SHARE))(key)


def _kanana_loss(p, batch):
    """(loss, the dropless expert layers' row counts)."""
    from repro.configs.kanana_2_30b_a3b import SHARE
    from repro.models.lm import loss_and_counts
    return loss_and_counts(p, batch, SHARE)


TASK_MODELS = {
    "ic": TaskModel("ic", ic_init, _ic_loss, 26.45e6, "labelled"),
    "sr": TaskModel("sr", sr_init, _sr_loss, 85.14e6, "labelled"),
    "tg": TaskModel("tg", tg_init, _tg_loss, 3.28e6, "tokens"),
    "mlm": TaskModel("mlm", mlm_init, _mlm_loss, 60.37e6, "tokens"),
    # 424,960,512 float32 parameters
    "kanana2-a3b": TaskModel("kanana2-a3b", _kanana_init, _kanana_loss,
                             1_699_842_048.0, "tokens"),
}


def _loss_only(loss_fn):
    def f(params, batch):
        out = loss_fn(params, batch)
        return out[0] if isinstance(out, tuple) else out

    return f


def make_task_model(task: str, key, **kw):
    """Returns (params, loss_fn) for a task of ``TASK_MODELS``, ``loss_fn``
    the scalar loss (the round engine takes ``TaskModel.loss_fn``, with
    the task's counts)."""
    tm = TASK_MODELS[task]
    if task == "tg":
        kw.setdefault("vocab", 32_000)
    if task == "mlm":
        kw.setdefault("vocab", 32_000)
    params = tm.init(key, **kw)
    return params, _loss_only(tm.loss_fn)
