"""Generated scenario catalog — the single source for ``docs/scenarios.md``.

``benchmarks/run.py --list --format md`` prints :func:`catalog_md`; CI
regenerates ``docs/scenarios.md`` from it and fails on any diff, so the
registry and its documentation cannot drift (see ``tests/test_docs.py``
and the ``docs-freshness`` CI step).  Everything here must therefore be a
pure, deterministic function of the registry.
"""

from __future__ import annotations

from typing import List

from repro.scenarios.registry import all_scenarios
from repro.scenarios.spec import Scenario, format_default

_HEADER = """\
# Scenario catalog

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with:
         PYTHONPATH=src python benchmarks/run.py --list --format md > docs/scenarios.md
     CI fails if this file is stale. -->

Every experiment in this repo is a registered, declarative
[`Scenario`](../src/repro/scenarios/spec.py): parameter axes (grid axes
expand into cells), result metrics, and the builder that turns one cell
into [`SimJob`](../src/repro/memsim/sweep.py)s.  Run any of them with:

```bash
PYTHONPATH=src python benchmarks/run.py --scenario NAME \\
    [--set axis=value ...] [--format csv|json] [--trace NAME] \\
    [--lane scalar|batched] [--jobs N]
```

Grid axes are marked `*` — comma lists in `--set` sweep them
(`--set threads=1,16`).  `--lane batched` runs the whole grid through the
vectorized sweep lane (`repro.memsim.batched`); `--trace NAME` records
per-window control-plane telemetry (see [telemetry.md](telemetry.md)).
"""


def _scenario_md(sc: Scenario) -> List[str]:
    lines = [f"## `{sc.name}`", ""]
    bits = [sc.title]
    if sc.figure:
        bits.append(f"reproduces **{sc.figure}**")
    lines.append(".  ".join(bits) + ".")
    lines.append("")
    facts = []
    facts.append("multi-stage (`run_cell`)" if sc.run_cell is not None
                 else "grid (`build` + `reduce`)")
    if sc.slow:
        facts.append("slow — CI runs it in the non-gating lane")
    if sc.device:
        facts.append("device — runs jitted model steps on the accelerator")
    if sc.module:
        facts.append(f"legacy figure module `benchmarks/{sc.module}.py`")
    lines.append(f"*Form:* {'; '.join(facts)}.")
    lines.append("")
    if sc.axes:
        lines.append("| axis | default | description |")
        lines.append("|---|---|---|")
        for a in sc.axes:
            mark = "\\*" if a.is_grid else ""
            lines.append(
                f"| `{a.name}`{mark} | `{format_default(a.default)}` "
                f"| {a.help} |"
            )
        lines.append("")
    if sc.metrics:
        lines.append("| metric | unit | description |")
        lines.append("|---|---|---|")
        for m in sc.metrics:
            unit = f"`{m.unit}`" if m.unit else ""
            lines.append(f"| `{m.name}` | {unit} | {m.help} |")
        lines.append("")
    return lines


def catalog_md() -> str:
    """The full markdown catalog, in registry declaration order."""
    lines = [_HEADER]
    scs = all_scenarios()
    lines.append("| scenario | figure | title |")
    lines.append("|---|---|---|")
    for sc in scs:
        lines.append(
            f"| [`{sc.name}`](#{sc.name}) | {sc.figure or '—'} "
            f"| {sc.title} |"
        )
    lines.append("")
    for sc in scs:
        lines.extend(_scenario_md(sc))
    return "\n".join(lines).rstrip() + "\n"
