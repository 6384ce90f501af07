"""The federated round as a single pure function (paper Fig. 5b on TPU).

``make_round_step(loss_fn, optimizer)`` builds::

    round_step(global_params, arrays) -> (new_global_params, RoundMetrics)

where ``arrays`` is a :class:`repro.data.batching.RoundArrays`-shaped pytree
of device arrays with leaves [W, P, S, ...]:

* the (W, P) lane grid is vmapped — on the production mesh the W dim is
  sharded over the FL worker axes (``data`` and/or ``pod``), so every worker
  trains its lanes in parallel, exactly Pollen's concurrent worker processes;
* the S dim is a ``lax.scan`` — the lane's sequential client stream;
* at a client's *boundary* step, the trained parameters are folded into the
  lane's running partial aggregate (Eq. 1; zero-weight ⇒ exact no-op) and the
  lane resets to the global parameters (the paper's §3.4 in-place model
  restore — here a ``jnp.where`` select that XLA fuses in place thanks to
  buffer donation);
* after the scan, lane partials are combined with a weighted mean over the
  sharded (W, P) grid — XLA lowers this to the hierarchical node→server
  reduction of §3.3 (per-pod reduce, cross-pod all-reduce).

Masked (padded) steps contribute zero gradient and zero weight; they are the
idle time the placement model minimizes.

Per-worker device programs (the mesh-sharded execution path,
``EngineConfig.mesh_workers >= 2``): the same round decomposes into one
:func:`make_worker_round_step` program per FL worker — the lane scans for
that worker's ``[1, P, S, ...]`` block, returning its *unreduced* lane
partials — plus one :func:`make_combine_step` program that concatenates
every worker's partials along W and applies exactly the reduction tail of
the fused step.  Because each lane's math is independent of the vmap batch
it runs in and the combine reduces tensors of the same shapes the fused
program reduces internally, the decomposition is bit-identical to the
single-program path (test-enforced across shard counts); what it buys is a
*per-worker* device sync — exact per-worker wall times for the control
plane — and per-shard placement of each worker's program on a multi-device
mesh.

Two hierarchy refinements ride on the decomposition:

* **per-worker S buckets** (``EngineConfig.bucket_mode="worker"``): each
  worker program compiles at its OWN pow2-bucketed stream length instead
  of the round's global one — a short worker stops burning padded steps
  waiting on the longest lane, at the cost of O(log S) cached executables
  instead of one.  Bit-identity across bucket modes rests on masked
  trailing steps being *bitwise* no-ops on the scan carry (the guarded
  fold in :func:`_make_lane_scan`).
* **shard-local combine trees** (``EngineConfig.combine_mode="tree"``):
  a per-shard :func:`make_shard_merge_step` partial-merge runs before the
  cross-shard combine, matching §3.3's node→server hierarchy and cutting
  the cross-shard transfer from O(K·lanes) to O(K) partials.
"""

from __future__ import annotations

import warnings
from collections import OrderedDict
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import (PartialAggregate, partial_init,
                                    partial_merge, partial_update,
                                    tree_weighted_mean)
from repro.optim.optimizers import apply_updates

__all__ = ["make_round_step", "make_worker_round_step", "make_combine_step",
           "make_shard_merge_step", "make_compressed_combine_step",
           "make_host_node_merge_step", "make_payload_decode_step",
           "make_gather_round_step", "RoundMetrics", "StepCompileCache",
           "round_shape_key"]


class RoundMetrics(NamedTuple):
    loss: Any            # masked mean loss over all real steps
    steps: Any           # number of real local steps executed
    clients: Any         # number of clients folded
    total_weight: Any    # sum of aggregation weights
    counts: Any = None   # the model's per-step counts (a loss_fn that
    #                      returns (loss, counts)) summed over the round's
    #                      real steps; fused round step only


def _with_counts(loss_fn):
    """``loss_fn`` may return a scalar loss or ``(loss, counts)``, a dict
    of scalar counts (the dropless expert layer's row counts); the round
    programs take ``(loss, counts)`` either way."""

    def f(params, batch):
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    return f


def _tree_select(flag, a, b):
    """Elementwise pytree select; ``flag`` is a scalar traced bool/float."""
    return jax.tree.map(lambda x, y: jnp.where(flag, x.astype(y.dtype), y), a, b)


def _make_lane_scan(loss_fn, optimizer, *, agg_impl: str = "xla",
                    grad_clip: float | None = None):
    """One lane's sequential client stream: scan over S local steps, folding
    each client into the lane's running partial at its boundary.  Shared by
    the fused round step and the per-worker mesh programs — the per-lane
    math is what the decomposition invariance rests on."""

    grad_fn = jax.value_and_grad(_with_counts(loss_fn), has_aux=True)

    def lane_scan(global_params, lane_batches, mask, boundary, weight):
        opt0 = optimizer.init(global_params)
        partial0 = partial_init(global_params)

        def step(carry, inp):
            theta, opt_state, partial, loss_sum = carry
            batch, m, bnd, w = inp
            (loss, counts), grads = grad_fn(theta, batch)
            if grad_clip is not None:
                from repro.optim.optimizers import clip_by_global_norm
                grads, _ = clip_by_global_norm(grads, grad_clip)
            updates, new_opt = optimizer.update(grads, opt_state, theta)
            # mask cast per-leaf: bf16 * f32-mask would promote a whole
            # param-shaped temporary to f32 (observed in the lowered HLO)
            theta = apply_updates(
                theta, jax.tree.map(lambda u: u * m.astype(u.dtype), updates))
            # Masked steps keep the old optimizer state (exact no-op).
            opt_state = _tree_select(m > 0, new_opt, opt_state)
            # Fold the trained client at its boundary.  The fold must be a
            # BITWISE no-op at masked/padded steps, not merely a numeric
            # one: Eq. 1 rescales the accumulator by N/(N+0), and
            # fl(fl(acc*N)/N) can flip the last bit for non-pow2 weights
            # (measured: ~10% of f32 values round differently).  Per-worker
            # S bucketing (``bucket_mode="worker"``) truncates a short
            # worker's trailing masked steps entirely, so a fold that
            # perturbed the partial would break bit-identity between bucket
            # modes — the select keeps the old partial bit-exactly.
            nk = w * bnd
            folded = partial_update(partial, theta, nk, impl=agg_impl)
            partial = _tree_select(nk > 0, folded, partial)
            # Reset lane to the global model for the next client.
            theta = _tree_select(bnd > 0, global_params, theta)
            opt_state = _tree_select(bnd > 0, opt0, opt_state)
            # The lane's loss total accumulates IN the scan carry: the
            # association order is s = 0..S-1 by construction, in every
            # program that embeds this scan — an XLA reduce over the
            # per-step losses instead may tile (and round) differently in
            # the fused round step vs the mesh path's combine program.
            # Counts are integer-valued, so their sum is exact in any order.
            return ((theta, opt_state, partial, loss_sum + loss * m),
                    jax.tree.map(lambda c: c * m, counts))

        (_, _, partial, loss_sum), counts = jax.lax.scan(
            step, (global_params, opt0, partial0, jnp.zeros(())),
            (lane_batches, mask, boundary, weight))
        return partial, loss_sum, jax.tree.map(jnp.sum, counts)

    return lane_scan


def make_round_step(loss_fn, optimizer, *, agg_impl: str = "xla",
                    grad_clip: float | None = None):
    """Build the jittable federated round function.

    loss_fn(params, batch) -> scalar loss (batch is a dict of arrays for one
    local step).  optimizer is a repro.optim.Optimizer.
    """
    lane_scan = _make_lane_scan(loss_fn, optimizer, agg_impl=agg_impl,
                                grad_clip=grad_clip)

    def round_step(global_params, batches, step_mask, boundary, weight):
        W, Pn = step_mask.shape[:2]
        if W == 1 and Pn == 1:
            # single-worker fast path: the lane scan runs without vmap
            # wrappers.  kanana2-c8 (W1 x P1) runs here; whether the vmap
            # branch below runs it as fast is unmeasured (ROADMAP D15).
            squeezed = jax.tree.map(lambda x: x[0, 0], batches)
            partial, loss1, counts = lane_scan(global_params, squeezed,
                                               step_mask[0, 0], boundary[0, 0],
                                               weight[0, 0])
            partials = jax.tree.map(lambda x: x[None, None], partial)
            lane_losses = loss1[None, None]
        else:
            # vmap lanes over P then workers over W; every lane starts from
            # the same global params (broadcast, in_axes None).
            per_lane = jax.vmap(lane_scan, in_axes=(None, 0, 0, 0, 0))
            per_worker = jax.vmap(per_lane, in_axes=(None, 0, 0, 0, 0))
            partials, lane_losses, counts = per_worker(
                global_params, batches, step_mask, boundary, weight)
            counts = jax.tree.map(jnp.sum, counts)
        theta_wp, n_wp = partials                     # leaves [W,P,...], [W,P]
        new_global, metrics = _reduce_partials(
            global_params, theta_wp, n_wp, lane_losses, step_mask, boundary,
            weight)
        return new_global, metrics._replace(counts=counts)

    return round_step


def _ordered_sum(v):
    """Strict left-to-right scalar sum via ``lax.scan``: the association
    order is fixed by construction, so every program embedding it rounds
    identically — a plain XLA full-reduce may pick different partial-sum
    tilings in different fusion contexts (observed: ``losses.sum()`` over
    ``[4, 1, 64]`` disagreed between the fused round step and the mesh
    combine program in the last bit)."""
    flat = v.reshape(-1)
    return jax.lax.scan(lambda c, x: (c + x, None),
                        jnp.zeros((), flat.dtype), flat)[0]


def _reduce_partials(global_params, theta_wp, n_wp, lane_losses, step_mask,
                     boundary, weight):
    """The round's reduction tail: weighted mean of lane partials + metrics.

    Shared verbatim by the fused round step (inlined after its vmaps) and
    the standalone combine program of the mesh path.  ``lane_losses`` is
    the ``[W, P]`` per-lane loss totals (scan-carried, order-fixed); the
    cross-lane metric sum uses :func:`_ordered_sum` so the two program
    contexts cannot re-associate it differently.  The remaining reduces
    are order-insensitive: mask/boundary sums add exact 0/1 floats, and
    client weights are integer-valued."""
    flat_w = n_wp.reshape(-1)
    flat_theta = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                              theta_wp)
    total_w = flat_w.sum()
    mean = tree_weighted_mean(flat_theta, flat_w)
    # If the round somehow folded nothing, keep the old global model.
    new_global = jax.tree.map(
        lambda m_, g: jnp.where(total_w > 0, m_.astype(g.dtype), g),
        mean, global_params)
    n_steps = step_mask.sum()
    metrics = RoundMetrics(
        loss=_ordered_sum(lane_losses) / jnp.maximum(n_steps, 1.0),
        steps=n_steps,
        clients=boundary.sum(),
        total_weight=total_w,
    )
    return new_global, metrics


def make_worker_round_step(loss_fn, optimizer, *, agg_impl: str = "xla",
                           grad_clip: float | None = None):
    """One FL worker's half of the round: lane scans over that worker's
    ``[W_k, P, S, ...]`` block, returning *unreduced* lane partials.

    Returns ``worker_step(global_params, batches, step_mask, boundary,
    weight) -> (theta_wp, n_wp, lane_losses)`` with leaves ``[W_k, P, ...]``,
    ``[W_k, P]`` and ``[W_k, P]``.  The engine dispatches one such
    program per worker (``W_k == 1``; the compiled executable is shared —
    every worker has the same shapes) and syncs each individually: the sync
    is what turns "one fused step, one round-level time" into exact
    per-worker wall-clock measurements.  Reduction across workers happens
    in :func:`make_combine_step` on the concatenated partials.
    """
    lane_scan = _make_lane_scan(loss_fn, optimizer, agg_impl=agg_impl,
                                grad_clip=grad_clip)

    def worker_step(global_params, batches, step_mask, boundary, weight):
        # Always the vmap form, even at W_k == P == 1: the fused step only
        # takes its no-vmap fast path when the WHOLE round is one worker x
        # one lane, and per-lane results are vmap-batch-size independent —
        # so matching the fused vmap path keeps the decomposition
        # bit-identical for every multi-worker round.
        per_lane = jax.vmap(lane_scan, in_axes=(None, 0, 0, 0, 0))
        per_worker = jax.vmap(per_lane, in_axes=(None, 0, 0, 0, 0))
        partials, lane_losses, _ = per_worker(global_params, batches,
                                              step_mask, boundary, weight)
        theta_wp, n_wp = partials
        return theta_wp, n_wp, lane_losses

    return worker_step


def make_combine_step():
    """The round's server half for the mesh path: reduce the concatenated
    per-worker lane partials into the new global model + metrics.

    ``combine(global_params, theta_wp, n_wp, lane_losses, step_mask,
    boundary, weight) -> (new_global, metrics)`` — exactly the fused step's
    tail (:func:`_reduce_partials`) as its own donated program, dispatched
    once per round after every worker program has been synced."""

    def combine(global_params, theta_wp, n_wp, lane_losses, step_mask,
                boundary, weight):
        return _reduce_partials(global_params, theta_wp, n_wp, lane_losses,
                                step_mask, boundary, weight)

    return combine


def make_shard_merge_step():
    """One mesh *shard's* half of the hierarchical combine (§3.3's per-node
    partial merge, ``EngineConfig.combine_mode="tree"``).

    ``merge(theta_wp, n_wp, lane_losses) -> (theta, n, loss)`` folds a
    shard's ``[W_s, P, ...]`` lane partials into ONE ``[1, 1, ...]``-shaped
    partial via :func:`~repro.core.aggregation.partial_merge` (a
    ``lax.scan`` left fold in dispatch order — deterministic association)
    and a scan-carried loss total.  The shard merge runs on the shard's own
    device group, so only O(1) partial per shard crosses to the cross-shard
    combine — O(K) transfer instead of the flat path's O(K·lanes) — and the
    cross-shard combine is exactly :func:`_reduce_partials` applied to the
    ``[K, 1, ...]`` stacked shard partials.

    The merged partial stays in running-mean form (Eq. 1), so re-weighting
    it by its weight in the final :func:`tree_weighted_mean` is the same
    hierarchy the paper's node→server reduction applies.  Numerics note:
    the per-shard grouping re-associates the cross-lane weighted mean, so
    tree-combined losses agree with the flat combine to float tolerance,
    not bitwise (the flat path stays the default and the bit-identity
    reference); the tree path itself is deterministic and bit-identical
    across pipeline depths and bucket modes.
    """

    def merge(theta_wp, n_wp, lane_losses):
        flat_theta = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                                  theta_wp)
        flat_n = n_wp.reshape(-1)
        flat_loss = lane_losses.reshape(-1)
        like = jax.tree.map(lambda x: x[0], flat_theta)
        init = (partial_init(like), jnp.zeros((), flat_loss.dtype))

        def fold(carry, inp):
            acc, loss_sum = carry
            theta_i, n_i, loss_i = inp
            acc = partial_merge(acc, PartialAggregate(theta_i, n_i))
            return (acc, loss_sum + loss_i), None

        (acc, loss_sum), _ = jax.lax.scan(
            fold, init, (flat_theta, flat_n, flat_loss))
        theta = jax.tree.map(lambda x: x[None, None], acc.theta)
        return theta, acc.weight[None, None], loss_sum[None, None]

    return merge


def make_host_node_merge_step():
    """One node of the canonical pairwise combine tree (the host-hierarchy
    path, ``EngineConfig.hosts >= 1``; see
    :class:`~repro.distributed.sharding.HostShardMap`).

    ``node(theta_a, n_a, loss_a, theta_b, n_b, loss_b) -> (theta, n, loss)``
    merges two partial aggregates (plain params-shaped trees + scalar
    weights, no ``[1, 1]`` lane dims) via Eq. 1's weighted mean and sums
    their scan-carried loss totals.  Every node of the tree — the per-host
    shard merges AND the root's merge over host partials — runs this ONE
    2-ary program, which is what makes the reduction's bits a function of
    the tree shape alone: grouping K shards into H aligned pow2 blocks
    computes the same nodes in the same order whatever H is.
    """

    def node(theta_a, n_a, loss_a, theta_b, n_b, loss_b):
        merged = partial_merge(PartialAggregate(theta_a, n_a),
                               PartialAggregate(theta_b, n_b))
        return merged.theta, merged.weight, loss_a + loss_b

    return node


def make_payload_decode_step(mode: str):
    """Per-shard payload reconstruction for the host-hierarchy combine
    (``hosts >= 1`` with ``combine_compress != "none"``).

    ``decode(global_params, payload) -> dense f32 params tree`` rebuilds the
    shard's partial ``g + dequant(payload)`` — the same arithmetic the
    legacy compressed-combine fold applies inside its scan — as a dense
    tree the canonical pairwise nodes can merge.  Encoding stays strictly
    per-shard (payloads and error-feedback residuals are identical whatever
    the host count), so compression rides the shard→host hop; the host→root
    hop ships one DENSE merged partial per host.
    """
    if mode not in ("int8", "topk"):
        raise ValueError(f"no decode step for mode {mode!r}")

    def decode(global_params, payload):
        gf = jax.tree.map(lambda g: g.astype(jnp.float32), global_params)
        if mode == "int8":
            q, scales = payload
            return jax.tree.map(
                lambda g, qq, s: g + qq.astype(jnp.float32) * s,
                gf, q, scales)
        flat_p, tdef = jax.tree_util.tree_flatten(
            payload, is_leaf=lambda x: isinstance(x, tuple))
        flat_g = tdef.flatten_up_to(gf)
        out = []
        for (idx, vals), g in zip(flat_p, flat_g):
            delta = (jnp.zeros(g.size, jnp.float32).at[idx].set(vals)
                     .reshape(g.shape))
            out.append(g + delta)
        return tdef.unflatten(out)

    return decode


def make_compressed_combine_step(mode: str, *, agg_impl: str = "xla"):
    """The cross-shard combine over COMPRESSED shard partials
    (``EngineConfig.combine_compress = "int8" | "topk"``).

    ``combine(global_params, payload, n_stack, loss_stack, step_mask,
    boundary, weight) -> (new_global, metrics)`` — a ``lax.scan`` left fold
    over the K shard payloads (dispatch order: deterministic association,
    bit-identical across pipeline depths and bucket modes), where each fold
    step reconstructs the shard's partial as ``g + dequant(payload_k)`` and
    blends it into the running Eq. 1 accumulator:

        acc <- (acc*N + (g + dequant(payload_k))*n_k) / (N + n_k)

    With ``mode="int8"`` and ``agg_impl="pallas"`` the dequant + blend is
    the fused one-HBM-pass :func:`repro.kernels.ops.dequant_merge` kernel —
    the int8 payload never materializes as a dense float tree.  ``topk``
    payloads scatter their (idx, vals) pairs inside the same jitted fold
    (sparse → dense is already one fused XLA scatter; there is no separate
    dense temporary to eliminate).

    ``payload``: leaves stacked [K, ...] across shards — ``(int8 tree,
    scales tree)`` for int8, a tree of ``(idx, vals)`` per leaf for topk.
    ``n_stack``/``loss_stack``: [K] per-shard weight / scan-carried loss
    totals (exact — scalars never compress, so the loss metric matches the
    uncompressed tree combine bitwise).  Weight/loss/steps metrics mirror
    :func:`_reduce_partials`; only the parameter average is approximate,
    and error feedback (see :mod:`repro.compress.combine`) re-sends the
    quantization error in later rounds."""
    if mode not in ("int8", "topk"):
        raise ValueError(f"combine_compress mode must be int8|topk, got {mode!r}")

    def _blend(acc, theta, n_old, n_k):
        # Eq. 1 with the zero-weight guard (all f32 here).
        n_new = n_old + n_k
        denom = jnp.where(n_new > 0, n_new, 1.0)
        out = (acc * n_old + theta * n_k) / denom
        return jnp.where(n_new > 0, out, acc)

    def combine(global_params, payload, n_stack, loss_stack, step_mask,
                boundary, weight):
        gf = jax.tree.map(lambda g: g.astype(jnp.float32), global_params)

        def fold(carry, xs):
            acc, n_old = carry
            payload_k, n_k = xs
            if mode == "int8":
                q_k, s_k = payload_k
                if agg_impl == "pallas":
                    from repro.kernels import ops as kops
                    new_acc = jax.tree.map(
                        lambda a, q, g, s: kops.dequant_merge(
                            a, q, g, s, n_old, n_k),
                        acc, q_k, gf, s_k)
                else:
                    new_acc = jax.tree.map(
                        lambda a, q, g, s: _blend(
                            a, g + q.astype(jnp.float32) * s, n_old, n_k),
                        acc, q_k, gf, s_k)
            else:
                flat_p, tdef = jax.tree_util.tree_flatten(
                    payload_k, is_leaf=lambda x: isinstance(x, tuple))
                flat_g = tdef.flatten_up_to(gf)
                flat_a = tdef.flatten_up_to(acc)
                new_leaves = []
                for (idx, vals), g, a in zip(flat_p, flat_g, flat_a):
                    delta = (jnp.zeros(g.size, jnp.float32).at[idx].set(vals)
                             .reshape(g.shape))
                    new_leaves.append(_blend(a, g + delta, n_old, n_k))
                new_acc = tdef.unflatten(new_leaves)
            return (new_acc, n_old + n_k), None

        init = (jax.tree.map(jnp.zeros_like, gf), jnp.zeros((), jnp.float32))
        (acc, total_w), _ = jax.lax.scan(fold, init, (payload, n_stack))
        new_global = jax.tree.map(
            lambda m_, g: jnp.where(total_w > 0, m_.astype(g.dtype), g),
            acc, global_params)
        n_steps = step_mask.sum()
        metrics = RoundMetrics(
            loss=_ordered_sum(loss_stack) / jnp.maximum(n_steps, 1.0),
            steps=n_steps,
            clients=boundary.sum(),
            total_weight=total_w,
        )
        return new_global, metrics

    return combine


def make_gather_round_step(loss_fn, optimizer, *, grad_clip: float | None = None):
    """Round step for NON-associative strategies (paper §3.3 last paragraph):
    workers return every trained client model; the server reduces in one shot
    (e.g. FedMedian).  Requires one client per lane (the engine enforces it).

    Returns ``round_step(global_params, ...) -> (stacked_client_params [W*P,...],
    weights [W*P], metrics)``; the caller applies the strategy's reduce.
    """

    grad_fn = jax.value_and_grad(_with_counts(loss_fn), has_aux=True)

    def lane_scan(global_params, lane_batches, mask, boundary, weight):
        opt0 = optimizer.init(global_params)

        def step(carry, inp):
            theta, opt_state = carry
            batch, m = inp
            (loss, _), grads = grad_fn(theta, batch)
            if grad_clip is not None:
                from repro.optim.optimizers import clip_by_global_norm
                grads, _ = clip_by_global_norm(grads, grad_clip)
            updates, new_opt = optimizer.update(grads, opt_state, theta)
            theta = apply_updates(theta, jax.tree.map(lambda u: u * m, updates))
            opt_state = _tree_select(m > 0, new_opt, opt_state)
            return (theta, opt_state), loss * m

        (theta, _), losses = jax.lax.scan(step, (global_params, opt0),
                                          (lane_batches, mask))
        return theta, (boundary * weight).sum(), losses

    def round_step(global_params, batches, step_mask, boundary, weight):
        per_lane = jax.vmap(lane_scan, in_axes=(None, 0, 0, 0, 0))
        per_worker = jax.vmap(per_lane, in_axes=(None, 0, 0, 0, 0))
        thetas, ws, losses = per_worker(global_params, batches, step_mask,
                                        boundary, weight)
        flat_theta = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), thetas)
        flat_w = ws.reshape(-1)
        n_steps = step_mask.sum()
        metrics = RoundMetrics(loss=losses.sum() / jnp.maximum(n_steps, 1.0),
                               steps=n_steps, clients=boundary.sum(),
                               total_weight=flat_w.sum())
        return flat_theta, flat_w, metrics

    return round_step


def round_shape_key(batches, step_mask) -> tuple:
    """Compile-cache key of a round's input signature: (W, P, S) plus every
    batch leaf's trailing shape/dtype.  Params shapes are engine-constant, so
    they stay out of the key."""
    W, P, S = step_mask.shape
    leaves = tuple(sorted((name, tuple(a.shape[3:]), str(a.dtype))
                          for name, a in batches.items()))
    return (W, P, S) + leaves


class StepCompileCache:
    """Explicit LRU of jitted round-step executables.

    ``jax.jit`` keeps an unbounded, invisible cache per wrapper; the engine
    instead threads every call through this cache so (a) recompiles are a
    *counted, observable* event (the telemetry the S-bucketing optimization
    is judged by), (b) old executables for shapes that stopped occurring are
    evicted (bounded device/host memory), and (c) buffer donation is applied
    uniformly.

    ``donate``: 'all' donates params + batches + masks (params update in
    place; batch/mask device buffers are freed at consumption), 'params'
    donates only argument 0, 'none' disables donation (the gather path,
    whose caller still needs ``global_params`` after the step).

    ``donate_argnums``: explicit argnums overriding the ``donate`` presets —
    the cache then works for *any* function signature, not just the 5-arg
    round step (the device batch cache keys its scatter/insert programs
    through this same counted LRU via :meth:`lookup`).
    """

    def __init__(self, factory, *, capacity: int = 8, donate: str = "all",
                 donate_argnums: tuple | None = None):
        if donate not in ("all", "params", "none"):
            raise ValueError(f"donate must be all|params|none, got {donate!r}")
        self._factory = factory          # () -> python round_step fn
        self.capacity = max(1, int(capacity))
        self.donate = donate
        self.donate_argnums = donate_argnums
        self._entries: OrderedDict[tuple, Any] = OrderedDict()
        self.compiles = 0
        self.evictions = 0
        self.hits = 0
        # Optional observability hook (repro.obs): when the engine attaches
        # a tracer, every fresh lowering books an instant labelled with the
        # cache's role — compiles become visible events on the trace
        # timeline, not just a counter.
        self.tracer = None
        self.trace_label = "step"

    def _jit(self):
        if self.donate_argnums is not None:
            donate_argnums = self.donate_argnums
        else:
            donate_argnums = {"all": (0, 1, 2, 3, 4), "params": (0,),
                              "none": ()}[self.donate]
        return jax.jit(self._factory(), donate_argnums=donate_argnums)

    def lookup(self, key: tuple):
        """The jitted fn for ``key`` (compiling + evicting as needed).

        Returns (fn, fresh): ``fresh`` is True when this key will compile on
        its first invocation."""
        fn = self._entries.get(key)
        fresh = fn is None
        if fresh:
            self.compiles += 1
            if self.tracer is not None:
                self.tracer.instant("compile", cache=self.trace_label,
                                    key=str(key))
            fn = self._jit()
            self._entries[key] = fn
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
        else:
            self.hits += 1
            self._entries.move_to_end(key)
        return fn, fresh

    def __call__(self, params, batches, step_mask, boundary, weight):
        fn, fresh = self.lookup(round_shape_key(batches, step_mask))
        if not fresh:
            return fn(params, batches, step_mask, boundary, weight)
        # Donated batch/mask buffers cannot alias the (params-shaped)
        # outputs; XLA reports that once, at compile.  Expected, not
        # actionable — suppress it for the compiling call only.  (The filter
        # tweak is process-global for this one call; a warning raised
        # concurrently on the pipeline's pack thread during a compile could
        # be affected, an accepted trade-off vs. wrapping every step.)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn(params, batches, step_mask, boundary, weight)

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        """Counters.  ``executables`` counts what jit actually compiled
        behind the live entries: one per entry and device it ran on (the
        mesh path runs one entry on every shard device), plus any hidden
        recompile of an entry for a new argument placement — the count a
        steady round must leave unchanged."""
        return {"compiles": self.compiles, "evictions": self.evictions,
                "hits": self.hits, "entries": len(self._entries),
                "executables": sum(fn._cache_size()
                                   for fn in self._entries.values())}
