"""End-to-end metrics from the harness's wall-clock stamps.

Every request due in the window [t0, t1) counts.  Time to first token
runs from its due time to the return of the prefill that made the token;
a request the loop never reached enters at its time so far, when the
loop stopped.  The gap between tokens of a request that made two or more
is (last token - first token) / (tokens - 1), the last token stamped at
the return of the decode chunk that made it.  Output tokens per second
counts every token delivered to the host inside the window, whichever
request it belongs to, over the window's length.
"""

from __future__ import annotations

import numpy as np


def p95(values) -> float:
    """95th percentile, numpy's linear rule; NaN for no values."""
    return float(np.percentile(values, 95)) if len(values) else float("nan")


def window_requests(run):
    return [r for r in run.reqs if run.t0 <= run.loop0 + r.due < run.t1]


def ttft_s(run, reqs):
    stop = run.loop["stop"]
    return [run.rec.first.get(r.rid, stop) - (run.loop0 + r.due)
            for r in reqs]


def tpot_s(run, reqs):
    out = []
    for r in reqs:
        n = run.rec.produced.get(r.rid, 0)
        if n >= 2 and r.rid in run.rec.last:
            out.append((run.rec.last[r.rid] - run.rec.first[r.rid]) / (n - 1))
    return out


def tokens_per_s(run) -> float:
    made = sum(c.tokens for c in run.rec.calls if run.in_window(c))
    return made / (run.t1 - run.t0)


def end_to_end(run) -> dict:
    reqs = window_requests(run)
    return {"ttft_p95_ms": 1e3 * p95(ttft_s(run, reqs)),
            "tpot_p95_ms": 1e3 * p95(tpot_s(run, reqs)),
            "output_tok_per_s": tokens_per_s(run)}
