"""Kernels: the ragged decode-attention kernel's share of its roofline.

Per kernel call (one layer of one decode step over the bucket) the least
time is the larger of its FLOPs over the peak and its bytes over the HBM
bandwidth, counted for the live requests at their valid KV lengths only
(q, the valid K and V rows, the output) by the family's
``kernels["ragged_decode_attention"]``, times ``shape.layers``; a family
without that entry reads nothing.  The share is that least time
summed over the traced decode chunks, over the device time of the
kernel's events inside those chunks' spans."""

import trace_reduce as tr

# The kernel's events in the device trace: its HLO instruction is named
# after the jitted wrapper, ``ragged_decode_attention.<n>``, with the op
# path ``.../jit(ragged_decode_attention)/pallas_call``.  The compaction
# gather, also a Pallas call, runs inside the next decode chunk's span
# (it is dispatched without a wait) and is named ``fused_compact.<n>``.
KERNEL = r"ragged_decode_attention"


def read(run):
    count = run.family.kernels.get(KERNEL)
    if run.peaks is None or count is None:
        return None
    m = run.shape
    kernel = tr.matching(run.trace["ops"], KERNEL)
    least = spent = 0.0
    for call, (lo, hi) in run.traced_calls("decode_chunk"):
        for j in range(1, call.size + 1):
            f = b = 0
            for base, steps in call.work:
                if j <= steps:
                    df, db = count(m, base + j)
                    f, b = f + df, b + db
            if f:
                least += m.layers * max(f / run.peaks["bf16_flops"],
                                        b / run.peaks["hbm_bytes_per_s"])
        spent += tr.overlap(kernel, lo, hi) * 1e-9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
