"""Model step, prefill: the FLOPs the real (unpadded) prompt tokens need,
logits at the last position only (the family's ``prefill_flops``), over
the chip's peak times the device time inside the harness's ``prefill``
spans of the traced window."""

import trace_reduce as tr


def read(run):
    if run.peaks is None:
        return None
    busy = run.trace["busy"]
    flops = dev = 0.0
    for call, (lo, hi) in run.traced_calls("prefill"):
        flops += run.family.prefill_flops(run.shape, call.work)
        dev += tr.overlap(busy, lo, hi) * 1e-9
    if dev <= 0:
        return None
    return 100.0 * flops / (run.peaks["bf16_flops"] * dev)
