"""Device time of a program's named scopes, from a profiler trace.

``tracing`` reduces a trace to device busy time, per-program time and
per-op time; its op names (``%fusion.17``) do not say which part of the
model an op computes.  The program marks its parts with
``jax.named_scope`` (``ssm``, ``moe``, ``attn``), which XLA keeps in each
instruction's ``metadata={op_name="jit(decode_step)/.../ssm/..."}``.  So:

* :func:`op_scopes` reads a compiled program's HLO text into a map from
  instruction name to the first scope in its ``op_name`` path;
* :func:`scope_seconds` sums the device time of the ops each scope holds,
  over the events of that program (ops are attributed to the ``XLA
  Modules`` event they run inside) in the traced window.

A fusion carries the metadata of the op it was built around, so an op of
one scope fused into another's counts for the other.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable

import numpy as np

import tracing

SCOPES = ("ssm", "moe", "attn")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*'
                    r'op_name="([^"]*)"')


def op_scopes(hlo_text: str, scopes: Iterable[str] = SCOPES) -> Dict[str, str]:
    """``{instruction name: scope}`` for each instruction of a compiled
    module whose ``op_name`` path passes through one of ``scopes``."""
    want = set(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        for part in m.group(2).split("/"):
            if part in want:
                out[m.group(1)] = part
                break
    return out


def scope_seconds(trace: "tracing.Collected", program: str,
                  scope_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per scope of the ops that ran inside ``program``'s
    modules within the traced window (summed over the chips)."""
    windows = [s for s in trace.spans if s[2] == tracing.WINDOW_SPAN]
    if not windows:
        return {}
    lo, hi, _ = max(windows, key=lambda s: s[1] - s[0])
    names = {i: n for n, i in trace.names.items()}
    out: Dict[str, float] = {}
    for plane in sorted({p for p, _ in trace.cols}):
        ms, me, mid = trace.column(plane, tracing.MODULES_LINE)
        mine = np.array([tracing.program_name(names[int(i)]) == program
                         for i in mid], bool)
        order = np.argsort(ms[mine], kind="stable")
        ms, me = ms[mine][order], me[mine][order]
        if ms.size == 0:
            continue
        os_, oe, oid = trace.column(plane, tracing.OPS_LINE)
        k = np.searchsorted(ms, os_, side="right") - 1
        inside = (k >= 0) & (os_ < me[np.maximum(k, 0)])
        secs = np.clip(np.minimum(oe, hi) - np.maximum(os_, lo), 0.0,
                       None) * 1e-9
        sums = np.bincount(oid[inside], weights=secs[inside],
                           minlength=len(names))
        for i in np.flatnonzero(sums):
            op = tracing.op_name(names[int(i)])
            if tracing.CONTAINER.match(op):
                continue
            scope = scope_of.get(op.lstrip("%"))
            if scope is not None:
                out[scope] = out.get(scope, 0.0) + float(sums[i])
    return out
