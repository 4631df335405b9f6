"""The requests of one run, made from the seed by one general generator.

A traffic file (``traffic/<mix>.json``) holds only parameters::

    {"arrivals": {"kind": "poisson"} | {"kind": "backlog", "count": N},
     "prompt": LENGTHS, "output": LENGTHS,
     "policy": {"kind": "elastic", "b_max": 16, "n_max": 256},
     "lead_in_s": 5, "order": "shuffle" | "blocks", "block": 16}

    LENGTHS = {"kind": "lognormal", "median": m, "sigma": s,
               "lo": a, "hi": b} | {"kind": "fixed", "n": n}

``policy`` is a ``repro.core.policies`` registry spec.  A Poisson mix
takes its rate in requests per second from the cell file
(``cells/<cell>.json``, ``rate_rps``); a backlog is due all at once at
the start.  The lognormal lengths are the program's
``LogNormalTokens`` law (a lognormal rounded to whole tokens), clipped
to ``[lo, hi]``; the policy's ``n_max`` clips outputs further.

Every seed gets the same multiset of prompt lengths, output lengths and
gaps between arrivals: stratified quantiles of each law.  The seed only
orders them and draws the token ids.  So two seeds offer the same work
in another order, and the spread between runs is the system's, not the
sampler's.  ``"order": "shuffle"`` (the default) permutes each multiset
at random.  ``"blocks"`` cuts each sorted multiset into ``block`` strata
of equal size and deals every consecutive ``block`` requests one length
from each stratum, in an order drawn from the seed: so every prefix that
a backlog serves in its window holds the whole mix, whatever the seed.
Prompt and output lengths are dealt apart, so their pairing is drawn
too.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np
from scipy import stats

_SALT = 0x7A1F


@dataclasses.dataclass
class Request:
    rid: int
    due: float                 # seconds after the loop starts
    prompt: np.ndarray         # int32 token ids
    target: int                # output tokens asked for, before n_max


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int) -> np.ndarray:
    """``n`` lengths at stratified quantiles of ``law``, in order."""
    if law["kind"] == "fixed":
        return np.full(n, int(law["n"]), np.int64)
    if law["kind"] == "lognormal":
        d = stats.lognorm(s=float(law["sigma"]), scale=float(law["median"]))
        x = np.rint(d.ppf(_quantiles(n))).astype(np.int64)
        return np.clip(x, max(int(law["lo"]), 1), int(law["hi"]))
    raise ValueError(f"unknown length law {law['kind']!r}")


def length_cdf(law: dict, x: float) -> float:
    """P(length <= x)."""
    if law["kind"] == "fixed":
        return float(x >= int(law["n"]))
    if x < law["lo"]:
        return 0.0
    if x >= law["hi"]:
        return 1.0
    d = stats.lognorm(s=float(law["sigma"]), scale=float(law["median"]))
    return float(d.cdf(math.floor(x) + 0.5))


def blocked_order(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` dealt so that each run of ``block`` consecutive entries,
    from the start, holds one value of each of ``block`` equal strata of
    the sorted values; which value of a stratum and the order within a
    run are drawn from ``rng``."""
    n = len(values)
    if block < 1 or n % block:
        raise ValueError(f"{n} requests do not fill blocks of {block}")
    strata = np.sort(values).reshape(block, n // block)
    runs = np.stack([rng.permutation(s) for s in strata]).T
    return np.stack([rng.permutation(r) for r in runs]).reshape(-1)


def count(traffic: dict, rate: float, seconds: float) -> int:
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        return int(arr["count"])
    return int(math.ceil(rate * (float(traffic["lead_in_s"]) + seconds)))


def make_requests(traffic: dict, rate: float, seconds: float, seed: int,
                  vocab: int) -> List[Request]:
    """The run's requests, sorted by due time."""
    n = count(traffic, rate, seconds)
    rng = np.random.default_rng(np.random.SeedSequence([_SALT, int(seed)]))
    order = traffic.get("order", "shuffle")
    if order == "shuffle":
        prompts = rng.permutation(lengths(traffic["prompt"], n))
        outputs = rng.permutation(lengths(traffic["output"], n))
    elif order == "blocks":
        k = int(traffic["block"])
        prompts = blocked_order(lengths(traffic["prompt"], n), k, rng)
        outputs = blocked_order(lengths(traffic["output"], n), k, rng)
    else:
        raise ValueError(f"unknown order {order!r}")
    if traffic["arrivals"]["kind"] == "poisson":
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
        due = np.cumsum(gaps)
    elif traffic["arrivals"]["kind"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']['kind']!r}")
    return [Request(i, float(due[i]),
                    rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                    int(outputs[i]))
            for i in range(n)]


# --------------------------------------------------------------------------
# Which programs a mix can reach, for the warm-up
# --------------------------------------------------------------------------

def bucket(n: int, lo: int, hi: int) -> int:
    """The engine's bucket rule: the smallest power-of-two multiple of
    ``lo`` that holds ``n``, capped at ``hi``."""
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)


# A shape is warmed when one batch reaches it with at least this chance.
REACH = 1e-5


def batch_sizes(traffic: dict, ecfg) -> List[int]:
    b_max = min(int(traffic["policy"]["b_max"]), ecfg.max_batch)
    if traffic["arrivals"]["kind"] == "backlog":
        return [b_max]
    return list(range(1, b_max + 1))


def _bucket_of_sizes(sizes, ecfg):
    out = {}
    for n in sizes:
        out.setdefault(bucket(n, ecfg.min_bucket, ecfg.max_batch), []).append(n)
    return out


def prefill_shapes(traffic: dict, ecfg) -> List[tuple]:
    """(batch bucket, prompt bucket) pairs that one batch reaches with a
    chance of at least ``REACH``: the longest of its prompts falls in the
    prompt bucket."""
    law = traffic["prompt"]
    hi = int(law.get("hi", law.get("n", 0)))
    s_all = sorted({bucket(p, ecfg.prompt_bucket, ecfg.max_seq)
                    for p in range(int(law.get("lo", law.get("n", 1))),
                                   hi + 1)})
    shapes = []
    for b, sizes in _bucket_of_sizes(batch_sizes(traffic, ecfg),
                                     ecfg).items():
        prev = 0
        for s in s_all:
            p = max(length_cdf(law, s) ** n - length_cdf(law, prev) ** n
                    for n in sizes)
            if p >= REACH:
                shapes.append((b, s))
            prev = s
    return shapes


def prompt_buckets(traffic: dict, ecfg) -> List[int]:
    return sorted({s for _, s in prefill_shapes(traffic, ecfg)})


def _compacts(traffic: dict) -> bool:
    """Whether members of a batch can finish at different steps and the
    policy then moves the rest to a smaller bucket."""
    return traffic["policy"]["kind"] == "elastic" and \
        traffic["output"]["kind"] != "fixed"


def decode_buckets(traffic: dict, ecfg) -> List[int]:
    """Batch buckets that decode chunks run at: those batches start at
    and, where the batch compacts, every smaller one."""
    buckets = set(_bucket_of_sizes(batch_sizes(traffic, ecfg), ecfg))
    if _compacts(traffic):
        b = max(buckets)
        while b > ecfg.min_bucket:
            b = max(b // 2, ecfg.min_bucket)
            buckets.add(b)
    return sorted(buckets)


def decode_shapes(traffic: dict, ecfg) -> List[tuple]:
    """(batch bucket, steps) pairs of the fused decode chunks."""
    n_max = traffic["policy"].get("n_max")
    law = traffic["output"]
    buckets = decode_buckets(traffic, ecfg)
    chunk = ecfg.decode_chunk
    if law["kind"] == "fixed":
        # every member of a batch owes the same tokens: one chain of steps
        rem = min(int(law["n"]), n_max or int(law["n"])) - 1
        steps = set()
        while rem > 0:
            st = chunk if rem >= chunk else 1 << (rem.bit_length() - 1)
            steps.add(st)
            rem -= st
    else:
        most = min(int(law["hi"]), n_max or int(law["hi"])) - 1
        steps = {1 << k for k in range(chunk.bit_length())
                 if (1 << k) <= min(chunk, most)}
    return [(b, st) for b in buckets for st in sorted(steps)]


def compaction_shapes(traffic: dict, ecfg) -> List[tuple]:
    """(bucket, smaller bucket) pairs of elastic compaction: only where
    members of a batch can finish at different steps."""
    if not _compacts(traffic):
        return []
    buckets = decode_buckets(traffic, ecfg)
    return [(b, nb) for b in buckets for nb in buckets if nb <= b // 2]
