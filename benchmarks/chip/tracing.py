"""Profiler capture and its reduction to device busy time, per-program
device time and idle gaps attributed to the harness's own host spans.

The reduction works on event tuples gathered into columns, so it can be
checked on a small recorded trace (``tests/data/``) without a chip:

* device busy: the union of the intervals of the ``XLA Ops`` events on
  each TPU plane, clipped to the traced window, averaged over the chips;
* per-program device time: ``XLA Modules`` events by program name
  (``jit_solve``, ``jit_decode_step``, ...), the ``(...)`` suffix dropped;
* idle gaps: each stretch of the window where the device runs nothing is
  charged to the innermost ``bench.*`` host span open over it (the
  drivers open them around each grid, solve, tick, admit, fetch and
  decode), or to ``(no span)``.

The traced window is the host span ``bench.window`` that the driver opens
around the traced part of its measured window.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from array import array
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


#: Ops that only hold other ops (a scan's loop): their time is their
#: body's, counted there.
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")


def op_name(event_name: str) -> str:
    """``%fusion.17 = (f32[...]) fusion(...)`` -> ``%fusion.17``."""
    return event_name.split(" = ", 1)[0].strip()


def program_name(event_name: str) -> str:
    """``jit_solve(1234)`` -> ``jit_solve``."""
    return event_name.split("(", 1)[0].strip()


class Capture:
    """A profiler trace of part of a window, written under ``TMPDIR``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._annotation = None

    def start(self) -> None:
        import jax

        # No Python tracer: it would slow the host path it is to observe.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._annotation.__enter__()

    def stop(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def events(self) -> "Collected":
        """Device-plane events and the harness's host spans; the trace
        files are removed once read."""
        from jax.profiler import ProfileData

        try:
            (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                recursive=True)
            return load_events(ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (cheap when none is running)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Collected:
    """A trace's device events, as columns per (plane, line), and the
    harness's host spans: compact enough for millions of events."""

    def __init__(self):
        self.cols: Dict[Tuple[str, str], tuple] = {}
        self.names: Dict[str, int] = {}
        self.spans: List[Tuple[float, float, str]] = []

    def add(self, plane: str, line: str, name: str, start: float,
            dur: float) -> None:
        if not is_device_plane(plane):
            if name.startswith(SPAN_PREFIX):
                self.spans.append((start, start + dur, name))
            return
        if line not in (OPS_LINE, MODULES_LINE):
            return
        col = self.cols.get((plane, line))
        if col is None:
            col = self.cols[(plane, line)] = (array("d"), array("d"),
                                              array("q"))
        col[0].append(start)
        col[1].append(dur)
        col[2].append(self.names.setdefault(name, len(self.names)))

    def column(self, plane: str, line: str):
        """``(start, end, name id)`` arrays of one line, or empty ones."""
        col = self.cols.get((plane, line))
        if col is None:
            return np.zeros(0), np.zeros(0), np.zeros(0, np.int64)
        start = np.frombuffer(col[0], np.float64)
        return start, start + np.frombuffer(col[1], np.float64), \
            np.frombuffer(col[2], np.int64)


def collect(events: Iterable[Event]) -> Collected:
    out = Collected()
    for e in events:
        out.add(*e)
    return out


def load_events(profile) -> Collected:
    out = Collected()
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                out.add(plane.name, line.name, ev.name, float(ev.start_ns),
                        float(ev.duration_ns))
    return out


def union(start: np.ndarray, end: np.ndarray):
    """Merged ``(start, end)`` arrays of possibly overlapping intervals."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def _clip(start, end, lo, hi):
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    return s[keep], e[keep]


def _complement(start, end, lo, hi):
    """Gaps of merged, sorted intervals inside ``[lo, hi]``."""
    gs = np.concatenate([[lo], end])
    ge = np.concatenate([start, [hi]])
    keep = ge > gs
    return gs[keep], ge[keep]


def attribute(gap_start: np.ndarray, gap_end: np.ndarray,
              spans: List[Tuple[float, float, str]], lo: float,
              hi: float) -> Dict[str, float]:
    """Seconds of the gaps under the innermost span open over each part.

    ``spans`` nest (host annotations of one thread); a part of a gap that
    no span covers goes to ``(no span)``."""
    cum = np.concatenate([[0.0], np.cumsum(gap_end - gap_start)])

    def idle_until(t):
        k = np.searchsorted(gap_end, t, side="right")
        part = np.where(k < gap_start.size,
                        np.clip(t - gap_start[np.minimum(k, gap_start.size - 1)],
                                0.0, None), 0.0)
        return cum[k] + part

    cuts = sorted({lo, hi} | {min(max(p, lo), hi)
                              for a, b, _ in spans for p in (a, b)})
    idle = np.diff(idle_until(np.asarray(cuts)))
    opens: Dict[float, list] = {}
    for s in spans:
        opens.setdefault(min(max(s[0], lo), hi), []).append(s)
    active: list = []
    out: Dict[str, float] = {}
    for (a, _), d in zip(zip(cuts, cuts[1:]), idle):
        active = [s for s in active if s[1] > a] + opens.get(a, [])
        if d <= 0.0:
            continue
        # The innermost open span started last (the shorter one on a tie).
        inner = max(active, key=lambda s: (s[0], -s[1]), default=None)
        name = inner[2][len(SPAN_PREFIX):] if inner else NO_SPAN
        out[name] = out.get(name, 0.0) + d * 1e-9
    return out


@dataclasses.dataclass
class Summary:
    """A traced window, reduced."""

    window_s: float
    #: device busy seconds, averaged over the chips that ran anything
    busy_s: float
    chips: int
    #: device seconds and event counts per XLA program (all chips)
    program_s: Dict[str, float]
    program_n: Dict[str, int]
    #: device seconds per XLA op (all chips), loop containers left out
    op_s: Dict[str, float]
    #: idle device seconds per innermost host span (first chip)
    idle_s: Dict[str, float]
    #: host seconds and counts per harness span inside the window
    span_s: Dict[str, float]
    span_n: Dict[str, int]

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        def head(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": head(self.op_s), "idle_gaps": head(self.idle_s)}


def _by_name(ids, secs, names, key) -> Tuple[Dict[str, float],
                                              Dict[str, int]]:
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    sums = np.bincount(ids, weights=secs, minlength=len(names))
    ns = np.bincount(ids, minlength=len(names))
    for name, i in names.items():
        if ns[i]:
            k = key(name)
            if k is not None:
                total[k] = total.get(k, 0.0) + float(sums[i])
                count[k] = count.get(k, 0) + int(ns[i])
    return total, count


def summarize(trace) -> Optional[Summary]:
    """Reduce a traced window (a :class:`Collected` or a list of events);
    ``None`` where the trace holds no window."""
    if not isinstance(trace, Collected):
        trace = collect(trace)
    windows = [s for s in trace.spans if s[2] == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi, _ = max(windows, key=lambda s: s[1] - s[0])
    spans = [s for s in trace.spans
             if s[2] != WINDOW_SPAN and s[0] < hi and s[1] > lo]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for a, b, name in spans:
        name = name[len(SPAN_PREFIX):]
        span_s[name] = span_s.get(name, 0.0) + (min(b, hi) - max(a, lo)) * 1e-9
        span_n[name] = span_n.get(name, 0) + 1
    planes = sorted({p for p, _ in trace.cols})
    busy_total, idle_s = 0.0, {}
    program_s: Dict[str, float] = {}
    program_n: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    used = 0
    for k, plane in enumerate(planes):
        start, end, ids = trace.column(plane, OPS_LINE)
        secs = np.clip(np.minimum(end, hi) - np.maximum(start, lo), 0.0,
                       None) * 1e-9
        busy_s, busy_e = _clip(*union(start, end), lo, hi)
        if busy_s.size:
            used += 1
        busy_total += float(np.sum(busy_e - busy_s)) * 1e-9
        ops, _ = _by_name(ids, secs, trace.names,
                          lambda n: None if CONTAINER.match(op_name(n))
                          else op_name(n))
        for name, v in ops.items():
            op_s[name] = op_s.get(name, 0.0) + v
        start, end, ids = trace.column(plane, MODULES_LINE)
        inside = (end > lo) & (start < hi)
        secs = np.clip(np.minimum(end, hi) - np.maximum(start, lo), 0.0,
                       None) * 1e-9
        progs, counts = _by_name(ids[inside], secs[inside], trace.names,
                                 program_name)
        for name, v in progs.items():
            program_s[name] = program_s.get(name, 0.0) + v
            program_n[name] = program_n.get(name, 0) + counts[name]
        if k == 0:
            idle_s = attribute(*_complement(busy_s, busy_e, lo, hi), spans,
                               lo, hi)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / max(used, 1),
        chips=used,
        program_s=program_s,
        program_n=program_n,
        op_s=op_s,
        idle_s=idle_s,
        span_s=span_s,
        span_n=span_n,
    )
