"""90th percentile, over the HBM-placed engines' requests sent in the
window, of the host-clock time from a request's submission to the end of
the tick that produced its first token, in ms.  Requests still waiting at
the window's close are followed past it (their wait counts).  The 90th is
the highest percentile with ten or more of a window's requests beyond
it."""

import statistics


def read(run):
    waits = [r.t_first - r.t_sub for r in run.data["fast"]
             if r.t_first is not None]
    if len(waits) < 2:
        return None
    return 1e3 * statistics.quantiles(waits, n=100, method="inclusive")[89]
