"""jit'd public wrappers around the Pallas kernels.

These adapt model-layer layouts to kernel layouts (transpose/pad), pick
block sizes the TPU compiler accepts, and fall back to interpret mode
off-TPU so the same call sites work in tests (CPU) and on real hardware.

Row blocks follow the TPU tiling rule: a block's second-minor dim is a
multiple of the dtype's sublane tile (8 rows of f32; 32 of int8), or equals
the whole array's.  A leaf with at most ``block_rows`` rows is one block;
a larger one is zero-padded up to a multiple of ``block_rows`` (itself a
tile multiple) and the padding is sliced off the output.  Every kernel here
is row-wise, so padding never changes the real rows' values.

    fedavg_accum(acc, theta, n_old, n_k)        — any-shape pytree leaf
    dequant_merge(acc, q, g, scale, n_old, n_k) — any-shape pytree leaf
    rmsnorm(x, scale)                           — [..., D]
    flash_attention(q, k, v, causal=...)        — [b, s, h, d] model layout
    ssd(x, dt, A_log, B, C, D, chunk=...)       — [b, s, h, p] model layout
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dequant_merge as _dm
from repro.kernels import fedavg_accum as _fa
from repro.kernels import flash_attention as _fl
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd as _ssd

__all__ = ["fedavg_accum", "dequant_merge", "rmsnorm", "flash_attention",
           "ssd", "on_tpu", "INTERPRET"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Tests may flip this; by default interpret unless a real TPU is attached.
INTERPRET = not on_tpu()


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _row_block(rows: int, block_rows: int, tile: int) -> tuple[int, int]:
    """(block, padded_rows) for a row-blocked kernel: the whole array when
    it fits one block, else ``block_rows`` rounded down to the sublane
    ``tile`` with ``rows`` padded up to a multiple of it."""
    if rows <= block_rows:
        return rows, rows
    block = max(tile, block_rows // tile * tile)
    return block, _round_up(rows, block)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def fedavg_accum(acc, theta, n_old, n_k, *, block_rows: int = 256):
    """Streaming Eq. 1 update on one pytree leaf of any shape."""
    shape, dtype = acc.shape, acc.dtype
    flat_a = acc.reshape(-1)
    flat_t = theta.astype(dtype).reshape(-1)
    n = flat_a.size
    lanes = _fa.LANES
    block, rows = _row_block(max(1, _round_up(n, lanes) // lanes),
                             block_rows, 8)
    pad = rows * lanes - n
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_t = jnp.pad(flat_t, (0, pad))
    out = _fa.fedavg_accum_2d(flat_a.reshape(rows, lanes),
                              flat_t.reshape(rows, lanes),
                              n_old, n_k, block_rows=block,
                              interpret=INTERPRET)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def dequant_merge(acc, q, g, scale, n_old, n_k, *, block_rows: int = 256):
    """Fused compressed-combine fold on one pytree leaf of any shape:
    theta = g + q*scale (int8 dequant), out = Eq. 1 blend of theta into acc
    — one HBM pass, no dense theta materialization."""
    shape, dtype = acc.shape, acc.dtype
    flat_a = acc.reshape(-1)
    flat_q = q.reshape(-1)
    flat_g = g.astype(dtype).reshape(-1)
    n = flat_a.size
    lanes = _dm.LANES
    # int8 q packs 32 rows per sublane tile
    block, rows = _row_block(max(1, _round_up(n, lanes) // lanes),
                             block_rows, 32)
    pad = rows * lanes - n
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_q = jnp.pad(flat_q, (0, pad))
        flat_g = jnp.pad(flat_g, (0, pad))
    out = _dm.dequant_merge_2d(flat_a.reshape(rows, lanes),
                               flat_q.reshape(rows, lanes),
                               flat_g.reshape(rows, lanes),
                               scale, n_old, n_k, block_rows=block,
                               interpret=INTERPRET)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 128):
    shape = x.shape
    d = shape[-1]
    rows = max(1, x.size // d)
    x2 = x.reshape(rows, d)
    block, padded = _row_block(rows, block_rows, 8)
    if padded != rows:
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))
    out = _rn.rmsnorm_2d(x2, scale, eps=eps, block_rows=block,
                         interpret=INTERPRET)
    return out[:rows].reshape(shape)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256):
    """Model layout [b, s, h, d] in/out; pads s/t to block multiples."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    bq = min(block_q, _round_up(s, 128))
    bk = min(block_k, _round_up(t, 128))
    sp = _round_up(s, bq)
    tp = _round_up(t, bk)
    qt = jnp.moveaxis(q, 2, 1)                       # [b, h, s, d]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sp != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    if tp != t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        # padded keys must not attend: causal masking handles the tail when
        # sp >= tp; for non-causal we mask via a large-negative key trick.
        if not causal:
            raise NotImplementedError("non-causal padding unsupported; pad "
                                      "t to a block multiple upstream")
    out = _fl.flash_attention_bhsd(qt, kt, vt, causal=causal, block_q=bq,
                                   block_k=bk, interpret=INTERPRET)
    return jnp.moveaxis(out[:, :, :s, :], 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A_log, B, C, D, *, chunk: int = 128):
    """Model layout: x [b,s,h,p]; dt [b,s,h]; B/C [b,s,g,n] in/out [b,s,h,p]."""
    b, s, h, p = x.shape
    ck = min(chunk, _round_up(s, 8))
    sp = _round_up(s, ck)
    xt = jnp.moveaxis(x, 2, 1)                       # [b,h,s,p]
    dtt = jnp.moveaxis(dt, 2, 1)                     # [b,h,s]
    Bt = jnp.moveaxis(B, 2, 1)                       # [b,g,s,n]
    Ct = jnp.moveaxis(C, 2, 1)
    if sp != s:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, sp - s)))
        Bt = jnp.pad(Bt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
        Ct = jnp.pad(Ct, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    out = _ssd.ssd_bhsp(xt, dtt, A_log, Bt, Ct, D, chunk=ck,
                        interpret=INTERPRET)
    return jnp.moveaxis(out[:, :, :s, :], 1, 2)
