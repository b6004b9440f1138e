# The benchmark/experiment harness, two modes:
#
#   1. Scenario mode — run any registry-declared scenario as a result table:
#        PYTHONPATH=src python benchmarks/run.py --list
#        PYTHONPATH=src python benchmarks/run.py --scenario fig3_bandwidth \
#            --set platform=A --set threads=1,16 --format csv
#        PYTHONPATH=src python benchmarks/run.py --scenario corun3_switch \
#            --set op=load --format json
#      --trace NAME additionally records the ControlLoop's per-window,
#      per-tier decision telemetry and writes NAME.csv (or .json, per
#      --format) plus NAME.trace.json next to each other:
#        PYTHONPATH=src python benchmarks/run.py --scenario corun3_pertier \
#            --set law=pertier --trace corun3_pertier
#      --perfetto NAME samples request-lifecycle span chains and writes
#      NAME.perfetto.json (Chrome trace-event JSON; see
#      docs/observability.md):
#        PYTHONPATH=src python benchmarks/run.py \
#            --scenario fabric_spine_congestion --set law=peredge \
#            --perfetto spine
#
#   2. Figure mode (legacy) — run the paper-figure modules, printing
#      ``name,us_per_call,derived`` CSV:
#        PYTHONPATH=src python benchmarks/run.py [filter] [--jobs N]
#
# ``--jobs N`` runs the figure modules concurrently in a spawned process
# pool (each module's sweep is itself a batch of independent sims;
# figure-level parallelism composes with REPRO_SWEEP_PROCS for the in-module
# sweeps).  Modules that need the accelerator run in the parent instead: a
# chip belongs to one process.  Output order is deterministic (module order)
# either way, and any ``ERROR:`` row makes the harness exit non-zero.
#
# The figure-module list is *derived from the scenario registry* (each
# scenario names the benchmarks module that presents it), so the registry
# and the module list cannot drift; roofline_table is the one non-scenario
# module and is appended explicitly.

from __future__ import annotations

import argparse
import contextlib
import os
import sys

# Allow `python benchmarks/run.py` as well as `python -m benchmarks.run`.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)
_SRC = os.path.join(_REPO_ROOT, "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

_EXTRA_MODULES = ["roofline_table"]  # presentation-only, not a scenario


def _module_names() -> list:
    """Figure modules in registry declaration order + non-scenario extras."""
    from repro.scenarios import all_scenarios

    mods = []
    for sc in all_scenarios():
        if sc.module and sc.module not in mods:
            mods.append(sc.module)
    mods.extend(_EXTRA_MODULES)
    return mods


def _uses_device(name: str) -> bool:
    """Whether a figure module touches the accelerator: one presenting a
    ``device`` scenario always does, and every module does when its sweeps
    run the batched lane on the fused window solver."""
    from repro.memsim.batched import kernel
    from repro.memsim.sweep import default_lane
    from repro.scenarios import all_scenarios

    return any(sc.device for sc in all_scenarios() if sc.module == name) or (
        default_lane() == "batched" and kernel.uses_fused_solver()
    )


def _run_module(name: str) -> list:
    """Worker entry: import + run one figure module, exceptions as rows."""
    import importlib

    try:
        mod = importlib.import_module(f"benchmarks.{name}")
        return list(mod.run())
    except Exception as ex:  # keep the harness going; failures visible
        return [(name, 0.0, f"ERROR:{type(ex).__name__}:{ex}")]


def _fmt_default(v) -> str:
    from repro.scenarios.spec import format_default

    return format_default(v)


def _list_scenarios(fmt: str = "csv") -> None:
    from repro.scenarios import all_scenarios

    if fmt == "md":
        # The generated docs/scenarios.md payload (CI regenerates the file
        # from this output and fails on diff — keep it deterministic).
        from repro.scenarios.catalog import catalog_md

        print(catalog_md(), end="")
        return
    for sc in all_scenarios():
        grid = []
        for a in sc.axes:
            mark = "*" if a.is_grid else ""
            grid.append(f"{a.name}{mark}={_fmt_default(a.default)}")
        figure = f" [{sc.figure}]" if sc.figure else ""
        slow = " (slow)" if sc.slow else ""
        print(f"{sc.name}{figure}{slow} — {sc.title}")
        if grid:
            print(f"    axes: {', '.join(grid)}")
        if sc.metrics:
            print(f"    metrics: {', '.join(m.name for m in sc.metrics)}")


def _write_perfetto(table, name: str) -> None:
    """Flatten per-cell span payloads into one Chrome trace-event file."""
    import json

    from repro.obs.trace import to_chrome

    records = []
    for ci, cell in enumerate(table.request_traces or []):
        for job in cell["jobs"]:
            payload = job["trace"]
            if not payload:
                continue
            for rec in payload["requests"]:
                # One trace process per (cell, job, workload) so grid cells
                # stay distinguishable in the Perfetto UI.
                records.append({
                    **rec,
                    "workload":
                        f"cell{ci}/job{job['job']}/{rec['workload']}",
                })
    path = f"{name}.perfetto.json"
    if not records:
        # No request retired while sampled (e.g. a horizon shorter than one
        # service time): an empty trace would just confuse Perfetto — say
        # so instead of writing it.
        print(f"no request-lifecycle spans were recorded, skipping {path}")
        return
    with open(path, "w") as f:
        json.dump(to_chrome(records), f, indent=1)
        f.write("\n")
    print(f"wrote {path} ({len(records)} traced requests)")


def _run_scenario(name: str, set_args: list, fmt: str, jobs: int,
                  trace: str = "", lane: str = "", perfetto: str = "",
                  profile: bool = False) -> None:
    import json

    from repro.scenarios import (
        UnknownScenarioError,
        get,
        parse_set_args,
        run_scenario,
    )

    try:
        sc = get(name)
    except UnknownScenarioError as ex:
        # Typos exit non-zero with near-miss suggestions instead of a
        # traceback (the message lists every registered name too).
        print(f"error: {ex}", file=sys.stderr)
        sys.exit(2)
    overrides = parse_set_args(sc, set_args)
    table = run_scenario(sc, overrides, processes=jobs if jobs > 1 else None,
                         trace=bool(trace), lane=lane or None,
                         perfetto=bool(perfetto), profile=profile)
    if perfetto:
        _write_perfetto(table, perfetto)
    if profile:
        print(f"profile: {json.dumps(table.meta.get('profile', {}))}",
              file=sys.stderr)
    if lane:
        # Lane routing summary on stderr so csv/json stdout stays clean.
        print(f"lane: {json.dumps(table.meta)}", file=sys.stderr)
        for reason, n in table.meta.get(
                "fallback_reason_counts", {}).items():
            print(f"lane fallback [{n} job(s)]: {reason}", file=sys.stderr)
    if fmt == "json":
        out = table.to_json()
    else:
        out = table.to_csv()
    if trace:
        # Result table and per-window decision telemetry side by side.
        table_path = f"{trace}.{'json' if fmt == 'json' else 'csv'}"
        trace_path = f"{trace}.trace.json"
        with open(table_path, "w") as f:
            f.write(out if out.endswith("\n") else out + "\n")
        has_windows = table.traces and any(
            j["windows"] for t in table.traces for j in t["jobs"]
        )
        if has_windows:
            with open(trace_path, "w") as f:
                json.dump({"scenario": table.scenario, "params": table.params,
                           "traces": table.traces}, f, indent=2)
                f.write("\n")
            print(f"wrote {table_path} and {trace_path}")
        else:
            # No job recorded any control-plane windows (e.g. the horizon is
            # shorter than one window): an empty trace file would just break
            # downstream tooling — say so instead.
            print(f"wrote {table_path}; no per-window telemetry was "
                  f"recorded (no job completed a control window), "
                  f"skipping {trace_path}")
    print(out, end="" if fmt != "json" else "\n")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run registry scenarios or paper-figure modules."
    )
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on figure module names")
    ap.add_argument("--jobs", type=int, default=1,
                    help="process-pool width (figure modules, or the "
                         "scenario's sweep)")
    ap.add_argument("--list", action="store_true", dest="list_scenarios",
                    help="list registered scenarios and exit")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="run one registered scenario as a result table")
    ap.add_argument("--set", action="append", default=[], metavar="AXIS=VAL",
                    dest="set_args",
                    help="override a scenario axis (repeatable; comma "
                         "lists make grids)")
    ap.add_argument("--format", choices=("csv", "json", "md"), default="csv",
                    help="scenario result-table format (md: with --list, "
                         "the generated docs/scenarios.md catalog)")
    ap.add_argument("--trace", default="", metavar="NAME",
                    help="with --scenario: record per-window per-tier "
                         "decision telemetry; write NAME.csv/.json and "
                         "NAME.trace.json")
    ap.add_argument("--perfetto", default="", metavar="NAME",
                    help="with --scenario: sample request-lifecycle span "
                         "chains (every 16th admission, scalar DES) and "
                         "write NAME.perfetto.json — Chrome trace-event "
                         "JSON loadable in Perfetto/chrome://tracing")
    ap.add_argument("--profile", action="store_true",
                    help="with --scenario: print a wall-clock phase "
                         "profile (plan/sweep/reduce + per-job event-loop "
                         "split) and the observability counters to stderr")
    ap.add_argument("--lane", choices=("scalar", "batched"), default="",
                    help="with --scenario: sweep execution lane (batched = "
                         "vectorized repro.memsim.batched; inexpressible "
                         "jobs fall back to the scalar DES)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run with the runtime sanitizer "
                         "(repro.analysis): per-window invariant checks "
                         "on every simulation; violations raise.  Forces "
                         "the scalar DES.  Equivalent to REPRO_SANITIZE=1.")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    if args.sanitize:
        # The env switch (not a SimJob field) so every sim in the process —
        # scenario sweeps, figure modules, TransferQueue benchmarks — is
        # sanitized, including ones built in pool workers, which inherit
        # the environment.
        os.environ["REPRO_SANITIZE"] = "1"

    if args.list_scenarios:
        if args.format == "json":
            ap.error("--list supports --format md (markdown catalog) or "
                     "the default text listing")
        _list_scenarios(args.format)
        return
    if args.format == "md":
        ap.error("--format md is only valid with --list")
    if args.scenario:
        _run_scenario(args.scenario, args.set_args, args.format, args.jobs,
                      args.trace, args.lane, args.perfetto, args.profile)
        return
    if args.set_args:
        ap.error("--set requires --scenario")
    if args.trace:
        ap.error("--trace requires --scenario")
    if args.lane:
        ap.error("--lane requires --scenario")
    if args.perfetto:
        ap.error("--perfetto requires --scenario")
    if args.profile:
        ap.error("--profile requires --scenario")

    from benchmarks.common import emit
    from repro.memsim.sweep import host_pool

    names = [n for n in _module_names()
             if not args.only or args.only in n]
    print("name,us_per_call,derived")
    pooled = [n for n in names if not _uses_device(n)] \
        if args.jobs > 1 else []
    failed = []
    with contextlib.ExitStack() as stack:
        futures = {}
        if len(pooled) > 1:
            pool = stack.enter_context(host_pool(min(args.jobs, len(pooled))))
            futures = {n: pool.submit(_run_module, n) for n in pooled}
        for name in names:
            rows = futures[name].result() if name in futures \
                else _run_module(name)
            emit(rows)
            failed += [r[0] for r in rows if str(r[2]).startswith("ERROR:")]
    if failed:
        print(f"error: {len(failed)} figure row(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
