"""Host-clock milliseconds of one decode step of a host-placed engine in
the traced window: the harness's ``decode.<engine>`` spans, each holding
the weights' pinned_host -> HBM fetch, the step and its token reads.  The
fetch itself leaves no event in the TPU trace that can be told apart, so
this span is the tier-transfer layer's reading (tiered cells only)."""


def read(run):
    t = run.trace
    hosts = run.data.get("host_engines", [])
    if t is None or not hosts:
        return None
    secs = sum(t.span_s.get("decode." + n, 0.0) for n in hosts)
    steps = sum(t.span_n.get("decode." + n, 0) for n in hosts)
    if not steps:
        return None
    return 1e3 * secs / steps
