"""The decode program's expert layers' share of their HBM roofline, in %:
the bytes the ``moe`` scope needs over the traced window's decode steps
(``counts_granite.moe_bytes``: each step's routers and shared MLPs, and
each held expert a step's tokens were routed to, counted by the engine's
``moe.experts_touched``) at the chip's peak HBM bandwidth, over the device
time of the ops in that scope of ``jit_decode_step`` (``scopes.py``)."""

import counts_granite
import peaks


def read(run):
    traced = run.data.get("traced") or {}
    secs = (run.data.get("scope_s") or {}).get("moe")
    if run.trace is None or not secs or "experts_touched" not in traced:
        return None
    need = counts_granite.moe_bytes(run.data["cfg"], len(traced["decode"]),
                                    traced["experts_touched"])
    return 100.0 * need / peaks.of(run.device)["hbm_bytes_per_s"] / secs
