"""Device milliseconds of one window solve: the device time of the fused
solver's program (``jit_solve``) in the trace over its executions."""

PROGRAM = "jit_solve"


def read(run):
    t = run.trace
    if t is None or not t.program_n.get(PROGRAM):
        return None
    return 1e3 * t.program_s[PROGRAM] / t.program_n[PROGRAM]
