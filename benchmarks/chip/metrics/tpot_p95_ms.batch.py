"""Engine: the gap between tokens (``e2e.tpot_s``), p95 over the window's
requests with two or more tokens, in the cells judged on output tokens
per second.  There a batch holds many requests of one pace, so one host
stall inside one batch sets the p95: it is read here, with no bound."""

import e2e


def read(run):
    gaps = e2e.tpot_s(run, e2e.window_requests(run))
    return 1e3 * e2e.p95(gaps) if gaps else None
