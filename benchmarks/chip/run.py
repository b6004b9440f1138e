"""Run one benchmark cell once, on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
(see ``cell.py``).  A run sets up (builds and warms every program the
window uses: ``setup_s``), measures for ``--seconds``, reads the device's
peak memory, frees the program's state and checks what the window produced
against the plain reference.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` runs the window under the profiler and reports its
per-layer metrics, with ``busy_s``/``window_s`` and a ``breakdown``.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, then ``breakdown`` when traced, and
``checks`` last: each compared number beside its limit).  The same numbers
are the last lines of stderr.  Anywhere but on a TPU with the chips the
cell asks for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import re
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cell as cell_lib  # noqa: E402
import tracing  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path in the checkout
#: unless ``JAX_COMPILATION_CACHE_DIR`` names another.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCount(logging.Handler):
    """Programs compiled or loaded from the cache since ``reset``, by name,
    from the log line JAX writes for each (at DEBUG unless it logs
    compiles)."""

    PATTERN = re.compile(r"Finished XLA compilation of (.+) in ")

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: list = []
        log = logging.getLogger("jax._src.dispatch")
        log.setLevel(logging.DEBUG)
        log.propagate = False  # count them; print none
        log.addHandler(self)

    def emit(self, record):
        m = self.PATTERN.match(record.getMessage())
        if m:
            self.names.append(m.group(1))

    def reset(self) -> None:
        self.names = []


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run(argv=None, *, require_chip: bool = True, shrink=None) -> int:
    """One run; returns the exit code.  ``require_chip=False`` and
    ``shrink`` (a function of the resolved cell) serve the CPU tests."""
    args = parse_args(argv)
    cell = cell_lib.resolve(args.workload)
    if shrink is not None:
        cell = shrink(cell)
    use_cache()
    import jax

    t_import = time.perf_counter()
    dev = device_info(cell.chips)
    t_devices = time.perf_counter()
    n_dev = len(jax.devices())
    if require_chip and (dev["platform"] != "tpu" or n_dev < cell.chips):
        print(f"error: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {n_dev} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    drv = cell_lib.driver(cell.kind)
    compiles = CompileCount()
    state = drv.setup(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    print(f"set-up {setup_s:.3f} s: imports {t_import - T_START:.3f} s, "
          f"devices {t_devices - t_import:.3f} s, driver "
          f"{T_START + setup_s - t_devices:.3f} s with "
          f"{len(compiles.names)} programs compiled or loaded",
          file=sys.stderr)
    capture = tracing.Capture() if args.trace else None
    compiles.reset()
    data = drv.window(state, args.seconds, capture)
    window_compiles = list(compiles.names)
    dev["memory_peak_bytes"] = memory_peak(cell.chips)
    summary = None
    if capture is not None:
        summary = tracing.summarize(capture.events())
        dev["busy_s"] = summary.busy_s if summary else 0.0
        dev["window_s"] = summary.window_s if summary else 0.0
    result = cell_lib.Run(cell=cell, seed=args.seed, device=dev, setup_s=setup_s,
                          window_s=data["window_s"], data=data,
                          trace=summary)
    metrics = cell_lib.read_metrics(
        result, cell.per_layer if args.trace else cell.end_to_end)
    checks = drv.check(state, result)
    if window_compiles:
        print(f"programs compiled in the window: {window_compiles}",
              file=sys.stderr)
    checks.append({"name": "window_compiles", "value": len(window_compiles),
                   "limit": 0, "ok": not window_compiles})
    correct = all(c["ok"] for c in checks)
    line = {"correct": correct, "attempted": data["attempted"],
            "failed": data["failed"], "metrics": metrics, "device": dev}
    if summary is not None:
        line["breakdown"] = summary.breakdown()
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(run())
