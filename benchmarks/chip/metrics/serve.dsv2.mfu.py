"""The whole DeepSeek-V2 serving step's share of the chip's peak
operations, in %: the forward operations of every prompt and output token
processed in the traced window (``counts_deepseek.prefill_flops`` per
admission, in the expanded form, and ``counts_deepseek.decode_flops`` per
decode step, in the absorbed form), over the traced window's seconds
times the chip's peak FLOP/s."""

import counts_deepseek
import peaks


def read(run):
    t = run.trace
    traced = run.data.get("traced")
    if t is None or t.window_s <= 0.0 or not traced:
        return None
    cfg = run.data["cfg"]
    flops = sum(counts_deepseek.prefill_flops(cfg, p)
                for p in traced["prefill"])
    flops += sum(counts_deepseek.decode_flops(cfg, lengths)
                 for lengths in traced["decode"])
    if not flops:
        return None
    return 100.0 * flops / (t.window_s * peaks.of(run.device)["flops_per_s"])
