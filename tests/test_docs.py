"""Docs suite gates: docstring coverage, catalog completeness, freshness.

Three guarantees:

* every exported symbol on the public surface (``repro.scenarios``,
  ``repro.tiering``, ``repro.memsim``, ``repro.memsim.batched``,
  ``repro.fabric``, the control-plane classes) carries a docstring —
  public methods included;
* the generated scenario catalog contains every registered scenario, and
  the committed ``docs/scenarios.md`` is byte-identical to a fresh
  generation (the same check CI runs — the registry cannot drift from its
  docs);
* the ``--trace`` schema documented in ``docs/telemetry.md`` matches what
  a live run actually emits.
"""

from __future__ import annotations

import importlib
import inspect
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_PUBLIC_MODULES = (
    "repro.scenarios",
    "repro.tiering",
    "repro.memsim",
    "repro.memsim.batched",
    "repro.fabric",
    "repro.workload",
)


def _public_symbols():
    for modname in _PUBLIC_MODULES:
        mod = importlib.import_module(modname)
        assert inspect.getdoc(mod), f"{modname} has no module docstring"
        for name in mod.__all__:
            yield f"{modname}.{name}", getattr(mod, name)
    from repro.core.controller import (
        Decision,
        MikuController,
        SlowTierMiku,
        TierDecisions,
        VectorMikuLadder,
    )
    from repro.core.littles_law import (
        LittlesLawEstimator,
        TierCounters,
        TierWindow,
    )

    for cls in (MikuController, SlowTierMiku, VectorMikuLadder,
                TierDecisions, Decision, LittlesLawEstimator, TierCounters,
                TierWindow):
        yield cls.__name__, cls


def test_public_surface_is_documented():
    undocumented = []
    for label, obj in _public_symbols():
        if not inspect.getdoc(obj):
            undocumented.append(label)
        if inspect.isclass(obj):
            for mname, m in inspect.getmembers(obj, inspect.isfunction):
                if mname.startswith("_"):
                    continue
                if not inspect.getdoc(m):
                    undocumented.append(f"{label}.{mname}")
    assert not undocumented, f"missing docstrings: {undocumented}"


def test_catalog_contains_every_registered_scenario():
    from repro.scenarios import all_scenarios
    from repro.scenarios.catalog import catalog_md

    md = catalog_md()
    for sc in all_scenarios():
        assert f"## `{sc.name}`" in md, f"{sc.name} missing from catalog"
        for axis in sc.axes:
            assert f"`{axis.name}`" in md
        for metric in sc.metrics:
            assert f"`{metric.name}`" in md


def test_docs_scenarios_md_is_fresh():
    from repro.scenarios.catalog import catalog_md

    path = REPO / "docs" / "scenarios.md"
    assert path.exists(), "docs/scenarios.md missing — regenerate with " \
        "benchmarks/run.py --list --format md"
    on_disk = path.read_text()
    assert on_disk == catalog_md(), (
        "docs/scenarios.md is stale; regenerate with:\n"
        "  PYTHONPATH=src python benchmarks/run.py --list --format md "
        "> docs/scenarios.md"
    )


def test_readme_references_current_surface():
    readme = (REPO / "README.md").read_text()
    for needle in ("docs/scenarios.md", "docs/telemetry.md",
                   "docs/decision-laws.md", "--lane batched", "--trace",
                   "examples/README.md"):
        assert needle in readme, f"README.md lost its {needle!r} reference"
    # The pre-scenario-API entry points must stay gone from the quickstart
    # docs (fig modules live on only as registry shims).
    assert "python benchmarks/fig" not in readme


def test_examples_index_covers_all_demos():
    idx = (REPO / "examples" / "README.md").read_text()
    for demo in sorted(p.name for p in (REPO / "examples").glob("*.py")):
        assert demo in idx, f"examples/README.md does not index {demo}"


@pytest.mark.parametrize("doc,needles", [
    ("telemetry.md", ("mytrace.trace.json", "max_concurrency",
                      "t_slow_raw", "class_counts", "tiering",
                      "queue_depth", "arrival-conservation")),
    ("decision-laws.md", ("TierDecisions", "VectorMikuLadder",
                          "uses_fused_solver", "fallback")),
    ("workloads.md", ("ArrivalSpec", "poisson", "zipf", "bursty",
                      "flash_crowd", "trace", "queue_limit", "slo_knee",
                      "REPRO_REGEN")),
])
def test_doc_files_exist_with_key_content(doc, needles):
    text = (REPO / "docs" / doc).read_text()
    for needle in needles:
        assert needle in text, f"docs/{doc} lost {needle!r}"


def test_telemetry_doc_matches_live_window_records():
    """The documented window-record schema must match a real trace."""
    from repro.core.device_model import platform_a
    from repro.memsim.sweep import SimJob, run_job
    from repro.memsim.workloads import bw_test
    from repro.core.littles_law import OpClass

    job = SimJob(
        platform=platform_a(),
        workloads=[
            bw_test("ddr", OpClass.LOAD, 8, name="ddr", miku_managed=False),
            bw_test("cxl", OpClass.LOAD, 8, name="cxl"),
        ],
        sim_ns=60_000.0,
        miku=True,
        record_windows=True,
    )
    res = run_job(job)
    assert res.window_records
    doc = (REPO / "docs" / "telemetry.md").read_text()
    rec = res.window_records[0]
    assert set(rec) == {"window", "t_ns", "tiers", "decision"}
    for tier, counters in rec["tiers"].items():
        assert set(counters) == {"inserts", "occupancy_time",
                                 "class_counts"}
    for tier, decision in rec["decision"].items():
        for key in decision:
            assert key in doc, f"undocumented decision field {key!r}"
    for key in ("window", "t_ns", "tiers", "decision"):
        assert f"`{key}`" in doc


def test_telemetry_doc_matches_live_arrival_block():
    """The documented open-loop `arrival` block must match a real run."""
    from repro.core.device_model import platform_a
    from repro.memsim.sweep import SimJob, run_job
    from repro.memsim.workloads import serve_test
    from repro.workload import ArrivalSpec

    wl = serve_test(2, arrival=ArrivalSpec("poisson", rate=0.01, seed=1))
    job = SimJob(platform=platform_a(), workloads=[wl], sim_ns=60_000.0,
                 record_windows=True)
    res = run_job(job)
    recs = [r for r in res.window_records if "arrival" in r]
    assert recs
    doc = (REPO / "docs" / "telemetry.md").read_text()
    for blk in recs[0]["arrival"].values():
        assert set(blk) == {"generated", "issued", "shed", "queue_depth"}
        for key in blk:
            assert f"`{key}`" in doc, f"undocumented arrival field {key!r}"
