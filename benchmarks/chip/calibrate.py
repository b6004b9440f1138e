"""Readings that a cell's correctness limits are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
      --seeds 1,2,3 [--control-seeds 1,2,3]

In one process, for each seed: the cell's set-up and a short window at its
own load, then the numbers its check compares, read for the program and,
on the control seeds, for the control (the reference one precision step
below the configuration's, put in the program's place at the same inputs).
One JSON line per seed on stdout.  The limit goes between the largest
program reading and the smallest control reading (see ``PERF.md``).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as run_lib  # noqa: E402  (puts the program's sources on the path)
import cell as cell_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    run_lib.use_cache()
    import jax

    cell = cell_lib.resolve(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("error: calibration runs on the chip", file=sys.stderr)
        return 2
    drv = cell_lib.driver(cell.kind)
    for seed in seeds + sorted(control - set(seeds)):
        t0 = time.perf_counter()
        state = drv.setup(cell, seed)
        data = drv.window(state, args.seconds, None)
        res = cell_lib.Run(cell=cell, seed=seed, device={}, setup_s=0.0,
                           window_s=data["window_s"], data=data)
        read = drv.readings(state, res, control=seed in control)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "seconds": time.perf_counter() - t0, **read}),
              flush=True)
        del state, data, res
    return 0


if __name__ == "__main__":
    sys.exit(main())
