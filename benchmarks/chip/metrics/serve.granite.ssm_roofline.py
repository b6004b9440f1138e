"""The decode program's Mamba-2 mixers' share of their HBM roofline, in %:
the bytes the ``ssm`` scope needs over the traced window's decode steps
(``counts_granite.ssm_bytes``: each step's mixer weights, and each active
slot's SSM and conv state read and written) at the chip's peak HBM
bandwidth, over the device time of the ops in that scope of
``jit_decode_step`` (``scopes.py``)."""

import counts_granite
import peaks


def read(run):
    traced = run.data.get("traced") or {}
    secs = (run.data.get("scope_s") or {}).get("ssm")
    if run.trace is None or not secs or "decode" not in traced:
        return None
    need = counts_granite.ssm_bytes(run.data["cfg"], traced["decode"])
    return 100.0 * need / peaks.of(run.device)["hbm_bytes_per_s"] / secs
