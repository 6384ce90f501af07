"""Docs consistency checks (run by the CI lint job and tier-1 tests).

Three checks, all zero-dependency beyond the repo itself:

1. **Markdown link check** — every relative link in the repo's markdown
   files (top level plus everything under ``docs/``, recursively) must
   resolve to an existing file (anchors are stripped; http(s) and mailto
   links are not fetched).  Catches renamed/moved docs.
2. **Flag-reference freshness** — the README section between
   ``<!-- flags:begin -->`` / ``<!-- flags:end -->`` must equal the output
   of ``python -m repro.launch.train --print-flags-md`` exactly.  The
   table is generated, never hand-edited, so CLI and docs cannot drift.
3. **Doc coverage** — each doc in ``DOC_NEEDLES`` must keep naming the
   subsystems and invariants it exists to explain; a rename or removed
   section must update the doc, not orphan it.  ``tests/test_docs.py``
   asserts the same lists in tier-1.

Usage::

    PYTHONPATH=src python tools/check_docs.py
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
MD_FILES = sorted(
    list(REPO.glob("*.md")) + list((REPO / "docs").rglob("*.md")))
LINK_RX = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
BEGIN, END = "<!-- flags:begin -->", "<!-- flags:end -->"

# What docs/ARCHITECTURE.md must keep covering (case-insensitive): the
# machine's moving parts and the invariants the test suite enforces.
ARCHITECTURE_NEEDLES = (
    "PRODUCER", "CONSUMER", "PackBuffers", "refit barrier",
    "DriftDetector", "DeviceBatchCache", "WorkerShardMap", "mesh_workers",
    "which module owns which invariant", "bit-identical",
    # the hierarchical-mesh layer (per-worker S buckets, shard-local
    # combine trees, orphan-shard reclamation)
    "Hierarchical combine", "bucket_mode", "combine_mode",
    "make_shard_merge_step", "Orphan-shard reclamation", "rebalance",
    "live_shards", "discard_workers", "combine_bytes",
    # the compressed cross-shard combine (delta wire format, error
    # feedback, fused dequant-merge kernel, checkpointed residuals)
    "Compressed combine", "combine_compress", "error feedback",
    "CombineCompressor", "dequant-merge", "residual_norm",
    # the open-world population layer (streaming registry + SLO metrics)
    "Open-world population", "OnlinePoolSampler", "ArrivalIndex",
    "stale_fraction", "never materializes",
    # the observability plane (tracer bit-identity, idle-gap accounting,
    # flight dumps) and controller checkpoint persistence
    "Tracer", "idle_fraction", "flight recorder", "state_dict",
    # the host hierarchy (shard→host partition, canonical pairwise tree,
    # process-per-host harness, sidecar telemetry replay)
    "Host hierarchy", "HostShardMap", "pairwise_reduce",
    "launch.multihost", "SidecarChannel", "host_layout",
    "exec.host_merge", "O(hosts)",
    # the placement feature: the steps a client trains this round
    "The placement feature", "capped_clients",
    # the dropless expert layer and one chip's expert share
    "The dropless expert layer", "the expert share", "moe_dropless",
)

# What docs/OBSERVABILITY.md must keep covering: the tracer's ring
# mechanics and the no-perturbation invariant, the span taxonomy, the
# spans' mirror on the profiler's clock, the idle-gap formula, the
# profiler-trace workflow, the flight-recorder dump triggers, and the
# overhead/trend gates.
OBSERVABILITY_NEEDLES = (
    "Tracer", "MetricsRegistry", "FlightRecorder", "make_observability",
    "NULL_TRACER", "bit-identical", "overwrite-oldest",
    "prep.pack", "prep.barrier", "exec.wait", "exec.sync", "pollen-pack",
    "wait.generator", "exec.wait_prep", "TraceAnnotation", "add_span",
    "critique_round", "idle_time / (makespan * n_workers)",
    "critical_path", "ProfileData", "ui.perfetto.dev", "--trace-out",
    "--flight-rounds", "SIGTERM", "never to raise",
    "tracer_overhead_fraction", "trend_summary.json",
    "state_dict", ".aux.npz", "exec.host_merge", "capped_clients",
    "moe_routed_rows", "moe_buffer_rows", "moe_row_waste_share",
)

# What docs/POPULATION.md must keep covering: the registry's hash streams,
# the arrival model, the streaming sampler's lifecycle and checkpoint
# story, the SLO metric definitions, and the full scenario-storm catalog.
POPULATION_NEEDLES = (
    "ClientMetadataStore", "ArrivalIndex", "OnlinePoolSampler",
    "PopulationDataset", "splitmix64", "diurnal", "rejection",
    "stale_fraction", "slo_p50", "slo_p99", "online_pool",
    "expected_online", "sampler_state", "never materializes",
    "storm catalog", "surge", "outage", "straggler", "fail", "skew",
    "adapt",
)

# doc path (relative to the repo root) -> needles it must keep naming
DOC_NEEDLES = {
    "docs/ARCHITECTURE.md": ARCHITECTURE_NEEDLES,
    "docs/POPULATION.md": POPULATION_NEEDLES,
    "docs/OBSERVABILITY.md": OBSERVABILITY_NEEDLES,
}


def check_doc_coverage() -> list[str]:
    errors = []
    for rel, needles in DOC_NEEDLES.items():
        path = REPO / rel
        if not path.exists():
            errors.append(f"{rel}: missing (coverage-enforced doc)")
            continue
        low = path.read_text(encoding="utf-8").lower()
        errors.extend(f"{rel}: no longer mentions {needle!r}"
                      for needle in needles if needle.lower() not in low)
    return errors


def check_architecture_coverage() -> list[str]:
    return [e for e in check_doc_coverage()
            if e.startswith("docs/ARCHITECTURE.md")]


def check_links() -> list[str]:
    errors = []
    for md in MD_FILES:
        text = md.read_text(encoding="utf-8")
        for target in LINK_RX.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            path = target.split("#", 1)[0]
            if not path:
                continue
            resolved = (md.parent / path).resolve()
            if not resolved.exists():
                errors.append(f"{md.relative_to(REPO)}: broken link "
                              f"-> {target}")
    return errors


def check_flags_section() -> list[str]:
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    if BEGIN not in readme or END not in readme:
        return [f"README.md: missing {BEGIN} / {END} markers"]
    current = readme.split(BEGIN, 1)[1].split(END, 1)[0].strip()
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.train import flags_markdown
    expected = flags_markdown().strip()
    if current != expected:
        return ["README.md flag reference is stale — regenerate with:\n"
                "  PYTHONPATH=src python -m repro.launch.train "
                "--print-flags-md\nand paste between the flags markers"]
    return []


def main() -> int:
    errors = (check_links() + check_flags_section()
              + check_doc_coverage())
    for e in errors:
        print(f"FAIL {e}")
    if not errors:
        print(f"docs OK ({len(MD_FILES)} markdown files, links + flag "
              "reference fresh)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
