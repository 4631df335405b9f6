"""KV cache: device time per decode step of the operations the program
puts under its ``kv_cache`` scope (the cache write, its layout constraints
and the attention read, the ragged kernel and its ``[B, S, Hkv*D]`` view
among them), from the traced window: the union of their intervals inside
the harness's ``decode_chunk`` spans, compaction left out, over the steps
those chunks ran.  An operation's scope is its op path, which the chip's
trace gives only through the HLO stored with it (``op_paths``).
Operations XLA inserted outside the scope (the layer scan's slices and
whole-cache copies) are not counted."""

import re

import op_paths
import trace_reduce as tr
from harness import TRACE_DIR, metric_reader

# ``jit(decode_chunk)/.../kv_cache/...``; an unscoped ``while`` around
# scoped operations is left out, and nested scoped operations count once
SCOPE = re.compile(r"/kv_cache(/|$)")
COMPACTION = metric_reader("decode_mfu").COMPACTION


def read(run):
    chunks = run.traced_calls("decode_chunk")
    if not chunks:
        return None
    paths = op_paths.load(str(TRACE_DIR))
    kv = tr.union((s, e) for s, e, *_ in (
        op for op in tr.excluding(run.trace["ops"], COMPACTION)
        if SCOPE.search(paths.of(op))))
    spent = sum(tr.overlap(kv, lo, hi) for _, (lo, hi) in chunks)
    steps = sum(call.size for call, _ in chunks)
    if spent <= 0:
        return None
    return 1e-6 * spent / steps
