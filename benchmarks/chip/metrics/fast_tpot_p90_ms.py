"""90th percentile, over the HBM-placed engines' requests both sent and
finished inside the window, of (t_done - t_first) / (outputs - 1): the
host-clock time per output token after the first, in ms.  The 90th
is the highest percentile with ten or more of a window's requests beyond
it."""

import statistics


def read(run):
    t1 = run.data["t1"]
    per = [(r.t_done - r.t_first) / (len(r.request.output) - 1)
           for r in run.data["fast"]
           if r.t_done is not None and r.t_done <= t1
           and len(r.request.output) > 1]
    if len(per) < 2:
        return None
    return 1e3 * statistics.quantiles(per, n=100, method="inclusive")[89]
