"""The whole serving step's share of the chip's peak operations, in %:
the forward operations of every prompt and output token processed in the
traced window (``counts.prefill_flops`` per admission, ``counts.
decode_flops`` per decode step, from the configuration's widths), over the
traced window's seconds times the chip's peak FLOP/s."""

import counts
import peaks


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0.0:
        return None
    cfg, traced = run.data["cfg"], run.data["traced"]
    flops = sum(counts.prefill_flops(cfg, p) for p in traced["prefill"])
    flops += sum(counts.decode_flops(cfg, lengths)
                 for lengths in traced["decode"])
    if not flops:
        return None
    return 100.0 * flops / (t.window_s * peaks.of(run.device)["flops_per_s"])
