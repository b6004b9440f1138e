"""The planner's share of the grids' wall time, in %: the ``plan`` phase
that ``run_scenario(..., profile=True)`` records, over the host-clock
seconds of the grids of the traced window."""


def read(run):
    if run.trace is None or run.data.get("plan_s", 0.0) <= 0.0:
        return None
    return 100.0 * run.data["plan_s"] / run.data["grid_wall_s"]
