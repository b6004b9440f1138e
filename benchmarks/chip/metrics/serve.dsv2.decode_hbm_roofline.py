"""DeepSeek-V2 decode's share of its HBM roofline, in %: the bytes every
decode step of the traced window needs (``counts_deepseek.decode_bytes``:
every weight outside the held experts once, the held experts the steps
touched, each active slot's live latent and rope-key rows in every layer
and its new row) at the chip's peak HBM bandwidth, over the device time
of the decode program (``jit_decode_step``) in the trace."""

import counts_deepseek
import peaks

PROGRAM = "jit_decode_step"


def read(run):
    t = run.trace
    traced = run.data.get("traced") or {}
    if (t is None or not t.program_s.get(PROGRAM)
            or "experts_touched" not in traced):
        return None
    need = counts_deepseek.decode_bytes(run.data["cfg"], traced["decode"],
                                        traced["experts_touched"])
    peak = peaks.of(run.device)["hbm_bytes_per_s"]
    return 100.0 * need / peak / t.program_s[PROGRAM]
