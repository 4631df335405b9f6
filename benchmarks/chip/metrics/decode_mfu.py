"""Model step, decode: the FLOPs of the live requests' decode steps at
their real KV lengths (the family's ``decode_flops``), over the chip's
peak times the device time inside the harness's ``decode_chunk`` spans
of the traced window.  The compaction gather, dispatched without a wait
just before a chunk, runs inside that chunk's span; its device time is
left out."""

import trace_reduce as tr

# the compaction kernels' events: ``fused_compact.<n>`` and the ops of
# ``jit(fused_compact)``
COMPACTION = r"fused_compact"


def read(run):
    if run.peaks is None:
        return None
    busy = tr.busy(tr.excluding(run.trace["ops"], COMPACTION))
    flops = dev = 0.0
    for call, (lo, hi) in run.traced_calls("decode_chunk"):
        flops += run.family.decode_flops(run.shape, call.work)
        dev += tr.overlap(busy, lo, hi) * 1e-9
    if dev <= 0:
        return None
    return 100.0 * flops / (run.peaks["bf16_flops"] * dev)
