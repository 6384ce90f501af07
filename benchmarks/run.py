"""Benchmark driver: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fit placement

Output: CSV-ish lines (benchmark,key...,value...) + a summary."""

import sys
import time


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_aggregation, bench_concurrency,
                            bench_control, bench_fit, bench_frameworks,
                            bench_kernels, bench_pipeline, bench_placement,
                            bench_scalability, bench_utilization)

    table = {
        "pipeline": (bench_pipeline, "pack / deep pipeline / device cache"),
        "control": (bench_control, "closed loop — refit barrier / drift / "
                                   "slots"),
        "fit": (bench_fit, "Fig. 7 — linear vs log-linear fit SSE"),
        "placement": (bench_placement, "Table 2 — idle time LB vs RR vs BB"),
        "frameworks": (bench_frameworks, "Figs. 8/9 — medium-scale compare"),
        "scalability": (bench_scalability, "Figs. 1/11-13 — cohort scaling"),
        "aggregation": (bench_aggregation, "Tables 6/7 — aggregation cost"),
        "utilization": (bench_utilization, "Tables 4/5 — GPU util / VRAM"),
        "concurrency": (bench_concurrency, "Table 3 — concurrency estimate"),
        "kernels": (bench_kernels, "Pallas kernels — err + HBM traffic"),
    }
    picks = [a for a in sys.argv[1:] if a in table] or list(table)
    failures = []
    for name in picks:
        mod, desc = table[name]
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            for row in mod.run():
                print(row)
            print(f"--- {name} done in {time.time() - t0:.1f}s", flush=True)
        except AssertionError as e:
            failures.append((name, repr(e)))
            print(f"!!! {name} ASSERTION FAILED: {e!r}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"!!! {name} ERROR: {e!r}", flush=True)
    print(f"\n{len(picks) - len(failures)}/{len(picks)} benchmarks passed")
    for n, e in failures:
        print(f"  FAILED {n}: {e[:200]}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
