"""The trace reduction: busy union, per-program time, idle attribution."""

import gzip
import json
import os

import pytest

import tracing
from conftest import HERE
from tracing import Event

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    host = "/host:CPU"
    return [
        ev("python", "bench.window", 0, 1000, host),
        ev("python", "bench.tick", 0, 600, host),
        ev("python", "bench.decode.hbm", 100, 300, host),
        ev("python", "bench.tick", 600, 400, host),
        ev("XLA Modules", "jit_decode_step(1)", 200, 300),
        ev("XLA Ops", "fusion.1", 200, 100),
        ev("XLA Ops", "fusion.2", 250, 150),  # overlaps fusion.1
        ev("XLA Ops", "copy.3", 700, 100),
        ev("XLA Ops", "fusion.1", 1500, 100),  # past the window
    ]


def test_busy_and_programs():
    s = tracing.summarize(synthetic())
    assert s.window_s == pytest.approx(1e-6)
    # Busy [200, 400) and [700, 800): 300 ns.
    assert s.busy_s == pytest.approx(300e-9)
    assert s.program_s == {"jit_decode_step": pytest.approx(300e-9)}
    assert s.program_n == {"jit_decode_step": 1}
    assert s.op_s["fusion.1"] == pytest.approx(100e-9)
    assert s.span_n == {"tick": 2, "decode.hbm": 1}


def test_idle_goes_to_innermost_span():
    s = tracing.summarize(synthetic())
    # Idle [0, 200): tick [0, 100) and decode.hbm [100, 200);
    # [400, 600) tick; [600, 700) and [800, 1000) the second tick.
    assert s.idle_s["tick"] == pytest.approx((100 + 200 + 100 + 200) * 1e-9)
    assert s.idle_s["decode.hbm"] == pytest.approx(100e-9)
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_window_no_summary():
    assert tracing.summarize([ev("XLA Ops", "fusion", 0, 10)]) is None


def recorded(tag):
    """A few milliseconds of a TPU v5 lite trace of the cell's window:
    device ops and programs, and the harness's spans (op names cut to the
    instruction's name)."""
    with gzip.open(os.path.join(HERE, "data", f"{tag}_trace.json.gz")) as f:
        return [Event(*e) for e in json.load(f)]


def oracle_busy(events, lo, hi):
    """Busy nanoseconds by a plain walk over the sorted op intervals."""
    ivs = sorted((max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi))
                 for e in events
                 if e.plane == DEV and e.line == tracing.OPS_LINE)
    busy, cur = 0.0, None
    for a, b in ivs:
        if b <= a:
            continue
        if cur and a <= cur[1]:
            cur[1] = max(cur[1], b)
        else:
            if cur:
                busy += cur[1] - cur[0]
            cur = [a, b]
    return busy + (cur[1] - cur[0] if cur else 0.0)


@pytest.mark.parametrize("tag,program,n", [("lane", "jit_solve", 1),
                                           ("hbm", "jit_decode_step", 1)])
def test_recorded_trace(tag, program, n):
    events = recorded(tag)
    (w,) = [e for e in events if e.name == tracing.WINDOW_SPAN]
    s = tracing.summarize(events)
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    assert s.chips == 1
    assert s.busy_s == pytest.approx(oracle_busy(events, lo, hi) * 1e-9)
    assert s.program_n[program] == n
    # One program runs at a time: its device time is at most the busy time.
    assert s.program_s[program] <= s.busy_s * (1 + 1e-9)
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert all(not tracing.CONTAINER.match(k) for k in s.op_s)
