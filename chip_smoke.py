#!/usr/bin/env python3
"""Smoke run of the federated round engine on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh path, one worker per chip

One chip: the paper's SR task at its full widths (``sr_init`` defaults:
64 -> 512, 8 residual blocks of 512x512, 35 classes, ~4.2M f32 params),
built by ``repro.launch.train.build_engine`` with the default engine
config (one fused round program, pipeline depth 1, synthetic telemetry)
and driven by ``engine.run``: a cohort of 32 on 4 workers x 2 lanes, at
most 8 local steps per client, gradients clipped to norm 1, 5 rounds,
weights from ``--seed``.  Then
the Pallas combine kernels on the chip against their references, and
round 0 once more in this process on the CPU as the loss reference.

Four chips (``--chips 4``): the same rounds with one worker program per
chip (``mesh_workers=4``), once with the flat combine and once with the
shard -> host -> root tree (``combine_mode="tree"``, ``hosts=2``), each
compared with the fused one-device run of the same seed — and nothing
else.

Each phase prints one JSON object; the last line is
``{"ok": true, "device": {...}}``.  A failed check or an exception exits
non-zero before that line.  Wall times are a smoke reading, not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading

import jax

ROUNDS = 5
# Rounds 0 and 1 compile the round program at the two stream-length
# buckets (S = 24, then 16) that seed 1337's rounds use; every later round
# must reuse them.
WARMUP = 2
# At its full widths the unnormalised residual MLP diverges under the
# CLI's plain SGD (round-0 loss ~5e8 on the CPU, NaN from round 1, seed
# 1337); --grad-clip 1.0 keeps it finite on every device.
SMOKE = dict(task="sr", cohort=32, workers=4, concurrency=2, steps_cap=8,
             grad_clip=1.0)
# TPU f32 matmuls run as bf16 passes by default, the CPU's in full f32:
# round 0's mean loss may differ by that rounding, compounded over up to
# 8 local SGD steps.
CPU_RTOL = 2e-2
# Mesh vs fused on the same chip type: the same arithmetic, re-associated
# (tree combine) or re-tiled (smaller vmap batches) at most.  Round 0
# trains from the same params on the same batches and must agree to
# MESH_RTOL.  From round 1 on, each round trains from a combined model
# whose rounding differs, and the bf16 matmul passes turn a last-bit
# difference in a weight into a larger one in the loss, growing about
# tenfold per round (four v5e chips, round 1: flat 3.3e-7, tree with 2
# hosts 4.9e-5, against 1.5e-6 for the latter on the CPU; round 4: 1.3e-3
# and 5.5e-4).  Later rounds are held to CPU_RTOL; the combine's exact
# arithmetic is checked on the CPU (tests/test_mesh.py).
MESH_RTOL = 1e-5
KERNEL_TOL = 2e-5     # as tests/test_kernels.py


class SmokeFailure(RuntimeError):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """Counts XLA backend compiles (and their seconds) process-wide,
    from JAX's own monitoring events — the round programs, the data
    generator and eager ops alike."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration

    def snapshot(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


def run_engine(label: str, log: CompileLog, seed: int, **overrides):
    """Build through the CLI's ``build_engine``, run ``WARMUP`` rounds,
    then the rest; print losses, compiles and wall times; fail on a
    non-finite loss or a compile after warm-up.  Returns (losses,
    engine)."""
    from repro.launch.train import build_engine

    engine = build_engine(seed=seed, **SMOKE, **overrides)
    c0, s0 = log.snapshot()
    results = engine.run(WARMUP)
    c1, s1 = log.snapshot()
    steps1 = engine.compile_stats
    results += engine.run(ROUNDS - WARMUP)
    c2, s2 = log.snapshot()
    steps2 = engine.compile_stats
    losses = [r.loss for r in results]
    emit("losses", run=label, losses=losses,
         s_steps=[r.s_steps for r in results])
    step_compiles = steps2["compiles"] - steps1["compiles"]
    hidden = steps2["executables"] - steps1["executables"]
    emit("compiles", run=label, warmup_rounds=WARMUP,
         warmup_compiles=c1 - c0, warmup_compile_s=s1 - s0,
         compiles_after_warmup=c2 - c1,
         round_program_compiles_after_warmup=step_compiles,
         round_program_executables=steps2["executables"],
         executables_added_after_warmup=hidden)
    emit("round_wall_s", run=label, note="smoke reading, not a benchmark",
         wall_s=[r.wall_time for r in results],
         exec_s=[r.exec_time for r in results])
    check(all(math.isfinite(x) for x in losses), f"{label}: non-finite loss")
    check(c2 - c1 == 0 and step_compiles == 0 and hidden == 0,
          f"{label}: a round after warm-up compiled")
    return losses, engine


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def one_chip(log: CompileLog, seed: int) -> None:
    from repro.kernels import ops

    check(not ops.INTERPRET, "Pallas kernels would run in interpret mode")
    losses, _ = run_engine("fused", log, seed)
    stats = jax.devices()[0].memory_stats() or {}
    emit("memory", device=0, peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    kernels()

    # Round 0 again, same seed, on the host CPU.  The global default
    # device (not the thread-local context manager) also steers the
    # engine's producer thread.
    from repro.launch.train import build_engine

    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        engine = build_engine(seed=seed, **SMOKE)
        ref = engine.run(1)[0].loss
    finally:
        jax.config.update("jax_default_device", None)
    where = {d.platform for x in jax.tree.leaves(engine.params)
             for d in x.devices()}
    check(where == {"cpu"}, f"the CPU reference ran on {where}")
    diff = rel_diff(losses[0], ref)
    emit("cpu_reference", round=0, tpu_loss=losses[0], cpu_loss=ref,
         rel_diff=diff, rtol=CPU_RTOL)
    check(diff <= CPU_RTOL, f"round-0 loss differs from the CPU by {diff}")


def kernels() -> None:
    """The combine kernels compiled for and run on the chip, on the sr
    model's residual-block leaf and the mlm task's 32000x256 embedding."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    key = jax.random.key(0)
    out = {}
    for shape in ((512, 512), (32000, 256)):
        ks = jax.random.split(key, 4)
        a = jax.random.normal(ks[0], shape)
        t = jax.random.normal(ks[1], shape)
        q = jax.random.randint(ks[2], shape, -128, 128, jnp.int8)
        name = "x".join(map(str, shape))
        got = ops.fedavg_accum(a, t, 10.0, 3.0)
        want = ref.fedavg_accum_ref(a, t, 10.0, 3.0)
        out[f"fedavg_accum_{name}"] = float(jnp.abs(got - want).max())
        got = ops.dequant_merge(a, q, t, 0.013, 10.0, 3.0)
        want = ref.dequant_merge_ref(a, q, t, 0.013, 10.0, 3.0)
        out[f"dequant_merge_{name}"] = float(jnp.abs(got - want).max())
        check(bool(np.all(np.isfinite(np.asarray(got)))),
              f"dequant_merge {name}: non-finite output")
    emit("kernels", interpret=False, max_abs_err=out, tol=KERNEL_TOL)
    check(all(e <= KERNEL_TOL for e in out.values()),
          "a kernel disagrees with its reference")


def four_chips(log: CompileLog, seed: int) -> None:
    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    fused, _ = run_engine("fused", log, seed)
    for label, kw in (("flat", dict(mesh_workers=4)),
                      ("tree_hosts2", dict(mesh_workers=4,
                                           combine_mode="tree", hosts=2))):
        losses, engine = run_engine(label, log, seed, **kw)
        workers = engine.compile_stats["worker_step"]["executables"]
        diffs = [rel_diff(a, b) for a, b in zip(losses, fused)]
        emit("mesh_vs_fused", run=label, bit_identical=losses == fused,
             rel_diff=diffs, rtol_round0=MESH_RTOL, rtol=CPU_RTOL,
             worker_program_executables=workers)
        check(diffs[0] <= MESH_RTOL, f"{label}: round-0 loss off the fused "
              "run")
        check(max(diffs) <= CPU_RTOL, f"{label}: losses off the fused run")
        check(workers >= 4, f"{label}: worker programs ran on {workers} "
              "devices, not 4")
    for i, d in enumerate(jax.devices()[:4]):
        stats = d.memory_stats() or {}
        emit("memory", device=i,
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             bytes_limit=stats.get("bytes_limit"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1337)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit("device", **device)
    emit("compile_cache", dir=enable_compile_cache())
    log = CompileLog()
    if args.chips == 4:
        four_chips(log, args.seed)
    else:
        one_chip(log, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
