"""Docs stay true: architecture doc present and linked, markdown links
resolve, and the README flag reference matches the live argparse parser
(the ``--print-flags-md`` emitter is the single source of truth)."""

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import check_docs  # noqa: E402  (path insert above)


def test_markdown_links_resolve():
    assert check_docs.check_links() == []


def test_readme_flags_table_matches_emitter():
    assert check_docs.check_flags_section() == []


def test_architecture_doc_covers_the_machine():
    """The round-lifecycle walkthrough must keep naming the subsystems it
    exists to explain (renames must update the doc, not orphan it).  The
    needle list lives in tools/check_docs.py so the CI lint job enforces
    the same coverage; this test pins the hierarchy-layer needles so a
    check_docs edit cannot silently drop them either."""
    assert check_docs.check_architecture_coverage() == []
    for needle in ("Hierarchical combine", "bucket_mode", "combine_mode",
                   "Orphan-shard reclamation", "make_shard_merge_step",
                   "discard_workers", "Open-world population",
                   "OnlinePoolSampler"):
        assert needle in check_docs.ARCHITECTURE_NEEDLES, needle
    # linked from README and ROADMAP
    assert "ARCHITECTURE.md" in (REPO / "README.md").read_text()
    assert "ARCHITECTURE.md" in (REPO / "ROADMAP.md").read_text()


def test_population_doc_covers_the_subsystem():
    """docs/POPULATION.md must keep naming the registry, the arrival
    model, the streaming sampler, the SLO metrics, and every scenario
    storm — and it must stay reachable from README and ROADMAP."""
    assert check_docs.check_doc_coverage() == []
    assert "docs/POPULATION.md" in check_docs.DOC_NEEDLES
    for needle in ("ClientMetadataStore", "ArrivalIndex",
                   "OnlinePoolSampler", "stale_fraction", "storm catalog",
                   "never materializes", "surge", "outage"):
        assert needle in check_docs.POPULATION_NEEDLES, needle
    assert "POPULATION.md" in (REPO / "README.md").read_text()
    assert "POPULATION.md" in (REPO / "ROADMAP.md").read_text()


def test_observability_doc_covers_the_plane():
    """docs/OBSERVABILITY.md must keep naming the tracer mechanics, the
    span taxonomy, the spans' mirror on the profiler's clock, the idle-gap
    formula, the profiler-trace workflow, the flight-recorder triggers,
    and the overhead/trend gates — and stay reachable from README and
    ARCHITECTURE."""
    assert check_docs.check_doc_coverage() == []
    assert "docs/OBSERVABILITY.md" in check_docs.DOC_NEEDLES
    for needle in ("Tracer", "FlightRecorder", "make_observability",
                   "bit-identical", "critique_round", "ui.perfetto.dev",
                   "TraceAnnotation", "wait.generator", "exec.wait_prep",
                   "tracer_overhead_fraction", "SIGTERM",
                   "idle_time / (makespan * n_workers)"):
        assert needle in check_docs.OBSERVABILITY_NEEDLES, needle
    assert "OBSERVABILITY.md" in (REPO / "README.md").read_text()
    assert "OBSERVABILITY.md" in \
        (REPO / "docs" / "ARCHITECTURE.md").read_text()


def test_observability_doc_names_every_traced_span():
    """Every span name the engine emits must be documented — adding an
    instrumentation site without documenting it fails here."""
    import re

    src = ""
    for rel in ("src/repro/core/engine.py", "src/repro/fl/round.py",
                "src/repro/data/device_cache.py"):
        src += (REPO / rel).read_text()
    names = set(re.findall(r'\.span\(\s*"([^"]+)"', src))
    names |= set(re.findall(r'add_span\(\s*\n?\s*"([^"]+)"', src))
    assert names, "span-name scrape found nothing — pattern drifted?"
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    for name in names:
        assert name in doc, f"span {name!r} not in OBSERVABILITY.md"


def test_population_doc_catalogs_every_scenario_storm():
    """The storm catalog documents EVERY storm control/scenarios.py can
    run — adding a scenario without documenting it fails here."""
    from repro.control.scenarios import SCENARIOS

    doc = (REPO / "docs" / "POPULATION.md").read_text().lower()
    for name in SCENARIOS:
        assert name.lower() in doc, f"storm {name!r} not in POPULATION.md"


def test_flags_markdown_lists_every_cli_flag():
    from repro.launch.train import _build_parser, flags_markdown

    table = flags_markdown()
    for action in _build_parser()._actions:
        if action.option_strings and action.dest != "help":
            assert action.option_strings[0] in table, action.dest
