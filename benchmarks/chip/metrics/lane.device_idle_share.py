"""Share of the traced window, in %, in which the device runs no
operation (1 - union of busy intervals / window)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0.0 or t.chips == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
