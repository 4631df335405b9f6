"""Device: the share of the time inside the harness's ``generate`` spans
of the traced window in which no operation ran on the chip."""

import trace_reduce as tr


def read(run):
    lo, hi = run.traced
    spans = tr.union(s for s in tr.spans_of(run.trace, "generate").values()
                     if s[0] >= lo and s[1] <= hi)
    total = tr.length(spans)
    if total <= 0:
        return None
    busy = run.trace["busy"]
    used = sum(tr.overlap(busy, s, e) for s, e in spans)
    return 100.0 * (1.0 - used / total)
