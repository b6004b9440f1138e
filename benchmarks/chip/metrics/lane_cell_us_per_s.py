"""Simulated cell-microseconds per wall second over the measured window:
cells times simulated microseconds of every grid run in the window (the
window ends with the grid that crosses ``--seconds``), over the window's
host-clock seconds.  Planning, host preparation, the solver's dispatches
and the reduction are all inside it.
"""


def read(run):
    return run.data["cell_us"] / run.data["window_s"]
