"""Fused chunked-SSD (Mamba-2) Pallas TPU kernel.

One kernel fuses the whole per-(batch, head) SSD pipeline that the pure-JAX
path (``repro.models.ssd.ssd_chunked``) spreads over five einsums and a
``lax.scan``:

  grid = (batch, heads, chunks)   — chunks innermost (sequential),

with the inter-chunk SSM state [p, n] carried in VMEM scratch across chunk
steps — the state never round-trips to HBM (the scan-based version writes
[b, nc, h, p, n] states out of the loop).  Per chunk step:

  1. la = cumsum(dt * A)  as a masked row sum         (decay prefix, VPU)
  2. y_intra = ((C Bᵀ) ⊙ L) (dt ⊙ x)                  (MXU, [Q,Q]@[Q,p])
  3. y_inter = exp(la) ⊙ (C @ stateᵀ)                 (MXU, [Q,n]@[n,p])
  4. state  = exp(la_Q) state + Bᵀ(decay ⊙ dt ⊙ x)    (MXU, [n,Q]@[Q,p])
  5. y += D x (skip)                                   (VPU)

VMEM per step (Q=128, p=64, n=128, f32): x/y 32 KB, B/C 64 KB, L 64 KB,
state 32 KB — ~0.3 MB total, deeply pipelineable against the HBM streams.

GQA-style B/C groups are handled in the index maps (head h reads group
``h // (H/G)``), like the flash kernel's kv heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_bhsp"]


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref, state_ref, *,
            chunk: int):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # [Q, p]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [Q, 1]
    A = -jnp.exp(jnp.full((1, 1), a_ref[ih]))       # this head's decay
    B = b_ref[0, 0].astype(jnp.float32)              # [Q, n]
    C = c_ref[0, 0].astype(jnp.float32)              # [Q, n]
    D = d_ref[ih]                                    # scalar skip weight

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = ii >= jj
    # decay prefix la_i = sum_{j<=i} dt_j A as a masked row sum (Mosaic has
    # no cumsum)
    la = jnp.sum(jnp.where(mask, (dt * A).reshape(1, chunk), 0.0), axis=1,
                 keepdims=True)                      # [Q, 1]
    xbar = x * dt                                    # [Q, p]

    # intra-chunk: ((C B^T) ⊙ L) @ xbar
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    ldiff = la - la.reshape(1, chunk)                # la_i - la_j
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, ldiff, 0.0)), 0.0)
    y = jax.lax.dot_general(cb * decay, xbar, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, p]

    # inter-chunk: exp(la) ⊙ (C @ state^T);  state [p, n]
    st = state_ref[...]
    y = y + jnp.exp(la) * jax.lax.dot_general(
        C, st, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: exp(la_Q) * state + (sdec ⊙ xbar)^T-contracted with B
    la_last = la[chunk - 1]
    sdec = jnp.exp(la_last - la)                     # [Q, 1]
    new_state = jax.lax.dot_general(
        sdec * xbar, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [p, n]
    state_ref[...] = st * jnp.exp(la_last) + new_state

    o_ref[0, 0] = (y + x * D).astype(o_ref.dtype)


def ssd_bhsp(x, dt, A_log, B, C, D, *, chunk: int = 128,
             interpret: bool = False):
    """x: [b, h, s, p]; dt: [b, h, s]; A_log/D: [h]; B/C: [b, g, s, n].

    Returns y [b, h, s, p].  ``s`` must divide ``chunk`` (wrapper pads).
    """
    b, h, s, p = x.shape
    g, n = B.shape[1], B.shape[3]
    if h % g:
        raise ValueError(f"heads {h} not divisible by groups {g}")
    hpg = h // g
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError("seq must divide chunk (pad in wrapper)")
    nc = s // chunk
    grid = (b, h, nc)
    dt3 = dt[..., None]                              # [b, h, s, 1]

    x_spec = pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0))
    dt_spec = pl.BlockSpec((1, 1, chunk, 1),
                           lambda ib, ih, ic: (ib, ih, ic, 0))
    bc_spec = pl.BlockSpec((1, 1, chunk, n),
                           lambda ib, ih, ic: (ib, ih // hpg, ic, 0))
    # Per-head scalars live whole in SMEM: a (1,)-block of an [h] vector
    # breaks the TPU's 128-lane tiling rule for rank-1 blocks.
    h_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    kern = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec, dt_spec, h_spec, bc_spec, bc_spec, h_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt3, A_log.astype(jnp.float32), B, C, D.astype(jnp.float32))
