"""Decode's share of its HBM roofline, in %: the bytes every decode step
of the traced window needs (``counts.decode_bytes``: all weights once, and
each active slot's live K/V rows and new row) at the chip's peak HBM
bandwidth, over the device time of the decode program (``jit_decode_step``)
in the trace.  Decode is bound by bytes here: its operations at peak take
a small fraction of that time."""

import counts
import peaks

PROGRAM = "jit_decode_step"


def read(run):
    t = run.trace
    if t is None or not t.program_s.get(PROGRAM):
        return None
    cfg = run.data["cfg"]
    need = sum(counts.decode_bytes(cfg, lengths)
               for lengths in run.data["traced"]["decode"])
    peak = peaks.of(run.device)["hbm_bytes_per_s"]
    return 100.0 * need / peak / t.program_s[PROGRAM]
