"""The timed path: an open loop on the wall clock.

Every request keeps the due time its seed gave it.  Batches are formed by
the cell's policy from ``repro.core.policies`` exactly as the program's
scheduler forms them (``formation(due, n).next_batch(now)`` reads only
requests due by ``now``), and each batch is served by one
``Engine.generate`` call.  The loop sleeps while nothing is due, never
dispatches a batch after the window has closed, and lets the batch in
flight at the close finish.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from traffic import bucket, compaction_shapes, decode_shapes, \
    prefill_shapes, batch_sizes


def _policy(traffic: dict):
    from repro.core.policies import ElasticPolicy, policy_from_spec
    policy = policy_from_spec(traffic["policy"])
    return policy, isinstance(policy, ElasticPolicy)


def warm(eng, traffic: dict) -> int:
    """Run every program this cell's traffic reaches, through the same
    entry points the window drives.  Returns the number of calls."""
    policy, elastic = _policy(traffic)
    ecfg = eng.ecfg
    pre = prefill_shapes(traffic, ecfg)
    shortest = {}
    for b, s in pre:
        shortest[b] = min(shortest.get(b, s), s)
        eng.prefill_batch([np.zeros(s, np.int32)] * b)

    def gen(n, targets):
        # a bucket that only compaction reaches is warmed by a batch that
        # starts there, with the shortest prompt bucket
        b = bucket(n, ecfg.min_bucket, ecfg.max_batch)
        s = shortest.get(b, min(shortest.values()))
        eng.generate([np.zeros(s, np.int32)] * n, targets,
                     elastic=elastic, n_max=policy.n_max, return_tokens=True)

    calls = len(pre)
    for n in batch_sizes(traffic, ecfg):          # the host-side bookkeeping
        gen(n, [2] * n)
        calls += 1
    for b, steps in decode_shapes(traffic, ecfg):
        gen(b, [steps + 1] * b)                  # one chunk of ``steps``
        calls += 1
    for b, nb in compaction_shapes(traffic, ecfg):
        # after one chunk of two steps the short members are done and the
        # rest compact b -> nb, with the sampling keys the chunk returned
        gen(b, [4] * nb + [2] * (b - nb))
        calls += 1
    jax.effects_barrier()
    return calls


def serve(eng, rec, traffic: dict, reqs, loop0: float, t0: float, t1: float,
          clock=time.perf_counter, between=None) -> dict:
    """Serve ``reqs`` (due times in seconds after ``loop0``) until the
    window [t0, t1) closes.  ``between(now)`` is called before each
    batch is dispatched (the traced run starts and stops its profiler
    there).  Returns per-request dispatch times and served tokens, and
    when the loop stopped."""
    policy, elastic = _policy(traffic)
    due = np.array([r.due for r in reqs])
    clipped = np.array([policy.clip(r.target) for r in reqs], np.float64)
    fs = policy.formation(due, clipped)
    dispatched, served = {}, {}
    late = []
    while True:
        now = clock()
        if now >= t1:
            break
        nb = fs.next_batch(now - loop0)
        if nb is None:
            break
        start, idx = nb
        if loop0 + start >= t1:
            break
        if loop0 + start > now:
            time.sleep(loop0 + start - now)
        if between is not None:
            between(clock())
        t = clock()
        late.append(t - (loop0 + start))
        batch = [reqs[i] for i in idx]
        targets = [int(clipped[i]) for i in idx]
        for i in idx:
            dispatched[int(i)] = t
        rec.begin([int(i) for i in idx], [len(r.prompt) for r in batch],
                  targets)
        with jax.profiler.TraceAnnotation(f"bench.generate#{len(rec.calls)}"):
            res = eng.generate([r.prompt for r in batch],
                               [r.target for r in batch], elastic=elastic,
                               n_max=policy.n_max, return_tokens=True)
        for i, toks in zip(idx, res["tokens"]):
            served[int(i)] = toks
    return {"dispatched": dispatched, "served": served, "stop": clock(),
            "late_s": late}
