"""Batch formation: 95th percentile of the wait from a request's due time
to the ``generate`` call that serves it, over the requests due in the
window (one the loop never reached waits until the loop stopped).  Host
clock."""

import e2e


def read(run):
    reqs = e2e.window_requests(run)
    if not reqs:
        return None
    stop = run.loop["stop"]
    waits = [run.loop["dispatched"].get(r.rid, stop) - (run.loop0 + r.due)
             for r in reqs]
    return 1e3 * e2e.p95(waits)
