"""Declarative scenario specs: Axis / Metric / Scenario / ResultTable.

A :class:`Scenario` is a *description* of one experiment family: its
parameter axes (grid axes expand into cells, scalar axes are shared
knobs), the metrics its rows report, and either

  * ``build`` + ``reduce`` — the declarative grid form: ``build(platform,
    cell)`` returns the :class:`~repro.memsim.sweep.SimJob` list for one
    cell and ``reduce(platform, cell, jobs, results)`` turns that cell's
    results into result-table rows; the planner batches every cell's jobs
    through one :func:`~repro.memsim.sweep.run_sweep`; or
  * ``run_cell`` — the escape hatch for multi-stage experiments whose
    later jobs depend on earlier results (Fig. 2's measured interleave
    split) or that do not run on the DES at all (Fig. 11's serving
    engine).

Scenarios carry no execution state; :mod:`repro.scenarios.planner` owns
expansion and execution, :mod:`repro.scenarios.registry` owns naming.
"""

from __future__ import annotations

import csv
import dataclasses
import enum
import io
import json
from typing import Any, Callable, Dict, List, Optional, Tuple


def _parse_bool(text: str) -> bool:
    t = text.strip().lower()
    if t in ("1", "true", "yes", "on"):
        return True
    if t in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"not a boolean: {text!r}")


def _infer_parse(sample: Any) -> Callable[[str], Any]:
    if isinstance(sample, bool):  # before int: bool is an int subclass
        return _parse_bool
    if isinstance(sample, enum.Enum):
        return type(sample)  # e.g. OpClass("load")
    if isinstance(sample, int):
        return int
    if isinstance(sample, float):
        return float
    return str


@dataclasses.dataclass(frozen=True)
class Axis:
    """One scenario parameter.

    ``default`` being a tuple/list makes this a *grid* axis: the planner
    expands the cartesian product of all grid axes into cells.  A scalar
    default is a shared knob every cell sees unchanged.  ``parse`` converts
    one ``--set axis=value`` CLI token (default: inferred from the default
    value's type; comma-separated tokens become grids).
    """

    name: str
    default: Any
    help: str = ""
    parse: Optional[Callable[[str], Any]] = None

    @property
    def is_grid(self) -> bool:
        return isinstance(self.default, (tuple, list))

    def parse_text(self, text: str) -> Any:
        """Parse one ``--set`` token for this axis (comma lists -> grids)."""
        sample = self.default[0] if self.is_grid else self.default
        fn = self.parse or _infer_parse(sample)
        if self.is_grid:
            return tuple(fn(p.strip()) for p in text.split(","))
        if "," in text:
            raise ValueError(
                f"axis {self.name!r} is a scalar knob, got list {text!r}"
            )
        return fn(text.strip())


@dataclasses.dataclass(frozen=True)
class Metric:
    """One column the scenario's result rows report."""

    name: str
    unit: str = ""
    help: str = ""


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named, declarative experiment over an N-tier platform model."""

    name: str
    title: str
    axes: Tuple[Axis, ...] = ()
    metrics: Tuple[Metric, ...] = ()
    figure: str = ""  # paper figure label, e.g. "Fig. 3"
    module: str = ""  # benchmarks module that presents this scenario
    #: (platform, cell) -> List[SimJob] — one grid cell's job batch.
    build: Optional[Callable[..., List[Any]]] = None
    #: (platform, cell, jobs, results) -> List[dict] — that cell's rows.
    reduce: Optional[Callable[..., List[Dict[str, Any]]]] = None
    #: (platform, cell, processes) -> List[dict] — multi-stage escape hatch.
    #: run_cell scenarios always execute scalar (the batched sweep lane
    #: covers grid scenarios only); bodies that call run_sweep internally
    #: must pin lane="scalar" so REPRO_SWEEP_LANE cannot leak in.
    run_cell: Optional[Callable[..., List[Dict[str, Any]]]] = None
    slow: bool = False  # heavy scenario: CI runs it in the non-gating lane
    #: runs jitted model steps on the accelerator, so its module must run
    #: in the process that holds the chip, never in a pooled worker.
    device: bool = False

    def __post_init__(self):
        grid_form = self.build is not None and self.reduce is not None
        if grid_form == (self.run_cell is not None):
            raise ValueError(
                f"scenario {self.name!r} needs either build+reduce or "
                "run_cell (exactly one form)"
            )

    def axis(self, name: str) -> Axis:
        """This scenario's axis named ``name`` (KeyError lists the axes)."""
        for a in self.axes:
            if a.name == name:
                return a
        raise KeyError(
            f"scenario {self.name!r} has no axis {name!r}; axes: "
            f"{', '.join(a.name for a in self.axes) or '(none)'}"
        )

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a.name for a in self.axes)


def _plain(v: Any) -> Any:
    """JSON/CSV-safe cell value (enums flatten to their value)."""
    if isinstance(v, enum.Enum):
        return v.value
    return v


def format_default(v: Any) -> str:
    """One axis default as display text (enums by value, sequences comma-
    joined, whole floats without the trailing ``.0``).

    The single formatter behind both ``benchmarks/run.py --list`` and the
    generated catalog (``docs/scenarios.md``), so the two listings cannot
    render the same default differently."""
    if isinstance(v, enum.Enum):
        return str(v.value)
    if isinstance(v, (tuple, list)):
        return ", ".join(format_default(x) for x in v)
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return str(v)


@dataclasses.dataclass
class ResultTable:
    """A uniform result table: one scenario, ordered rows of plain dicts."""

    scenario: str
    rows: List[Dict[str, Any]]
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: Per-window control-plane telemetry (one entry per cell, each with
    #: its jobs' per-tier window records) — populated only when the
    #: scenario ran with ``trace=True`` (``benchmarks/run.py --trace``).
    traces: Optional[List[Dict[str, Any]]] = None
    #: Execution metadata: which lane ran the sweep and, for the batched
    #: lane, how many jobs it expressed vs routed back to the scalar DES
    #: (``fallback_reasons`` says why) — see ``run_scenario(..., lane=)``.
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: Sampled request-lifecycle span records (one entry per cell, each
    #: with its jobs' ``SimResult.trace`` payloads) — populated only under
    #: ``run_scenario(..., perfetto=True)`` (``benchmarks/run.py
    #: --perfetto``).  Excluded from :meth:`to_json`; the CLI exports it
    #: separately as Chrome trace-event JSON via
    #: :func:`repro.obs.trace.to_chrome`.
    request_traces: Optional[List[Dict[str, Any]]] = None

    def __post_init__(self):
        self.rows = [{k: _plain(v) for k, v in r.items()} for r in self.rows]
        self.params = {k: _plain(v) for k, v in self.params.items()}

    @property
    def columns(self) -> List[str]:
        cols: List[str] = []
        for r in self.rows:
            for k in r:
                if k not in cols:
                    cols.append(k)
        return cols

    def to_csv(self) -> str:
        """The rows as CSV text (union of row keys, declaration order)."""
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=self.columns, restval="",
                           lineterminator="\n")
        w.writeheader()
        w.writerows(self.rows)
        return buf.getvalue()

    def to_json(self, indent: Optional[int] = 2) -> str:
        """Scenario, params, rows (and non-empty meta) as a JSON document."""
        def default(o: Any) -> Any:
            plain = _plain(o)
            return plain if plain is not o else str(o)

        payload = {"scenario": self.scenario, "params": self.params,
                   "rows": self.rows}
        if self.meta:
            payload["meta"] = self.meta
        return json.dumps(payload, indent=indent, default=default)
