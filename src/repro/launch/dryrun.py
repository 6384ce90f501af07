import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # XLA's while-loop invariant-code-motion hoists per-step bf16->f32
    # converts of remat-saved stacks OUT of backward loops, materializing a
    # full f32 copy of every saved activation/weight stack (observed 2-3x
    # temp blowup; see EXPERIMENTS.md §Perf iteration 0).  On a 16 GiB/chip
    # budget that hoist is fatal, so the production config disables it.
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")

"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture × input-shape) cell on the production
meshes — 16×16 (single pod, 256 chips) and 2×16×16 (two pods, 512 chips) —
and records ``memory_analysis()`` (proves it fits), ``cost_analysis()``
(FLOPs/bytes for §Roofline), and the parsed collective schedule.

The XLA_FLAGS line above MUST run before any jax import: jax locks the
device count at first backend init.  This module is the only place the 512
placeholder devices exist; tests and benches see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multipod \
        --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out results/dryrun
"""

import argparse
import json
import time
import traceback


from repro.configs import ARCH_NAMES, SHAPES, get_arch
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import axis_sizes, make_production_mesh
from repro.launch.plan import (make_plan, param_bytes, runnable,
                               sharding_specs, skip_reason)
from repro.launch.roofline import model_flops, roofline_terms
from repro.launch.steps import build_jitted

__all__ = ["run_cell", "main"]


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, *, hlo_dir=None,
             overrides: dict | None = None) -> dict:
    """Lower + compile one cell; returns the record dict (raises on failure)."""
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    ax = axis_sizes(mesh)
    n_dev = 1
    for v in ax.values():
        n_dev *= v
    plan = make_plan(arch, shape, mesh, overrides=overrides)
    shard = sharding_specs(plan, mesh)
    t0 = time.time()
    with mesh:
        jf, args = build_jitted(plan, shard)
        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = _mem_dict(compiled.memory_analysis())
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    pod_size = ax["data"] * ax["model"] if "pod" in ax else 0
    # Trip-count-aware walker (XLA's cost_analysis counts while bodies once —
    # a federated round is scans-inside-scans, so that undercounts ~30-100x).
    hc = analyze_hlo(hlo, pod_size=pod_size)
    flops, byt = hc.flops, hc.bytes
    wire_ici, wire_dcn = hc.wire_bytes(pod_size=pod_size)
    by_kind: dict = {}
    n_coll = 0.0
    for cop in hc.collectives:
        k = by_kind.setdefault(cop.kind, {"count": 0.0, "bytes": 0.0})
        k["count"] += cop.multiplicity
        k["bytes"] += cop.bytes * cop.multiplicity
        n_coll += cop.multiplicity
    csum = {"count": n_coll, "wire_bytes_ici": wire_ici,
            "wire_bytes_dcn": wire_dcn, "by_kind": by_kind}
    tokens = plan.global_batch * (plan.seq_len if plan.kind != "decode" else 1)
    mf = model_flops(plan.cfg, tokens, "train" if plan.kind == "train"
                     else "serve")
    terms = roofline_terms(
        flops_per_device=flops, bytes_per_device=byt,
        wire_ici=wire_ici, wire_dcn=wire_dcn)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "devices": n_dev, "kind": plan.kind, "policy": plan.policy,
        "W": plan.W, "P": plan.P, "S": plan.S, "b": plan.b,
        "param_bytes": param_bytes(plan.cfg),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "flops_per_device": flops, "bytes_per_device": byt,
        "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed", 0.0))},
        "collectives": csum,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_ratio": (mf / n_dev) / flops if flops else 0.0,
        "roofline": terms,
        "status": "ok",
    }
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(
                hlo_dir, f"{arch}__{shape}__{mesh_kind}.hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--hlo-dir", default=None,
                    help="also dump optimized HLO text per cell")
    ap.add_argument("--set", action="append", default=[],
                    help="hillclimb override key=value (int/str/tuple), "
                         "e.g. --set S=1 --set worker_axes=data,model")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json (variant runs)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if "," in v:
            overrides[k] = tuple(x for x in v.split(",") if x)
        elif v == "":
            overrides[k] = ()
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = [args.arch] if args.arch else ARCH_NAMES
    shapes = [args.shape] if args.shape else list(SHAPES)
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            cfg = get_arch(arch)
            for shape in shapes:
                tag = f"{arch:24s} {shape:12s} {mesh_kind:8s}"
                suffix = f"__{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
                if not runnable(cfg, shape):
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "skip",
                           "reason": skip_reason(cfg, shape)}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"SKIP {tag} ({rec['reason'][:60]}...)")
                    continue
                try:
                    rec = run_cell(arch, shape, mesh_kind,
                                   hlo_dir=args.hlo_dir,
                                   overrides=overrides or None)
                    rec["overrides"] = {k: list(v) if isinstance(v, tuple)
                                        else v for k, v in overrides.items()}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    r = rec["roofline"]
                    print(f"OK   {tag} compile={rec['compile_s']:7.1f}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"dom={r['dominant']:12s} "
                          f"frac={r['roofline_fraction']:.3f} "
                          f"useful={rec['useful_ratio']:.3f}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "fail", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"FAIL {tag} {e!r}", flush=True)
    print(f"done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
