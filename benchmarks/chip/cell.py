"""The cell a run measures, and the files it is built from.

Everything that belongs to one configuration, traffic mix, driver kind or
metric sits in a file of its own and is found by its name in
``BENCHMARK.json``:

* ``configs/<config>.json``: a model or a simulated deployment, with
  ``driver`` naming its driver kind;
* ``traffic/<traffic>.json``: the parameters of a traffic mix;
* ``drivers/<kind>.py``: ``setup``, ``window``, ``readings`` and ``check``
  for one kind of system path;
* ``metrics/<metric>.py``: ``read(run)`` for one metric, returning a
  number or ``None`` when the run holds nothing to read.

A new cell, mix, configuration or metric is new files plus an entry in
``BENCHMARK.json``; no file here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
#: The checkout: ``benchmarks/chip/`` sits two levels below it.
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def kind(self) -> str:
        return self.config["driver"]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration and traffic."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; cells: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m
            else m["moves"] in reported)
    ]
    return Cell(
        name=workload,
        config_name=w["config"],
        config=load_json(os.path.join(root, entry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(HERE, "traffic",
                                       w["traffic"] + ".json")),
        chips=int(w["chips"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def driver(kind: str):
    return load_module(os.path.join(HERE, "drivers", kind + ".py"),
                       f"chipbench_driver_{kind}")


def reader(metric: str):
    """``read(run)`` of ``metrics/<metric>.py``."""
    mod = load_module(os.path.join(HERE, "metrics", metric + ".py"),
                      "chipbench_metric_" + metric.replace(".", "_"))
    return mod.read


@dataclasses.dataclass
class Run:
    """What one run measured, as the metric readers see it."""

    cell: Cell
    seed: int
    #: platform, kind and count of the devices, as JAX reports them
    device: Dict[str, Any]
    setup_s: float
    window_s: float
    #: the driver's own readings (counts, host-clock stamps)
    data: Dict[str, Any]
    #: the reduced profiler trace of a ``--trace 1`` run
    trace: Optional[Any] = None


def read_metrics(run: Run, metrics: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``{name: {"value", "unit"}}`` for each metric that found a reading."""
    out = {}
    for m in metrics:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
