"""The decode program's latent attention's share of its HBM roofline, in
%: the bytes the ``attn`` scope needs over the traced window's decode
steps (``counts_deepseek.mla_bytes``: each step's MLA weights in every
layer, and each active slot's live latent and rope-key rows read and its
new row written) at the chip's peak HBM bandwidth, over the device time
of the ops in that scope of ``jit_decode_step`` (``scopes.py``)."""

import counts_deepseek
import peaks


def read(run):
    traced = run.data.get("traced") or {}
    secs = (run.data.get("scope_s") or {}).get("attn")
    if run.trace is None or not secs or "decode" not in traced:
        return None
    need = counts_deepseek.mla_bytes(run.data["cfg"], traced["decode"])
    return 100.0 * need / peaks.of(run.device)["hbm_bytes_per_s"] / secs
