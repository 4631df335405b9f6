"""Reduction of a profiler trace to intervals the metric readers use.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps three
things, all in nanoseconds on the profiler's one clock:

* ``ops``: every operation that ran on a chip (the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane), as ``(start, end, label, chip)``;
  the label is the event's name and its ``tf_op`` stat (the JAX op
  path, ``.../pallas_call`` for a kernel), joined by ``|``;
* ``spans``: the harness's own host spans, ``bench.<kind>#<n>``, as
  ``{name: (start, end)}``;
* ``busy``: the union of the operations' intervals, over all chips.

The functions below it work on plain lists, so the tests can check them
on a small hand-written trace.
"""

from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

SPAN = re.compile(r"^bench\.[a-z_]+#\d+$|^bench\.window$")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[tuple] = []
    spans: Dict[str, Interval] = {}
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            found = False
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    found = True
                    tf_op = next((str(v) for k, v in ev.stats
                                  if k == "tf_op"), "")
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                f"{ev.name}|{tf_op}", devices))
            devices += found
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if SPAN.match(ev.name):
                        spans[ev.name] = (ev.start_ns,
                                          ev.start_ns + ev.duration_ns)
    ops.sort()
    return {"ops": ops, "spans": spans, "busy": busy(ops)}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(disjoint: List[Interval], lo: float, hi: float) -> float:
    """Length of ``disjoint`` (sorted, merged) inside [lo, hi]."""
    i = max(bisect.bisect_right(disjoint, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(disjoint) and disjoint[i][0] < hi:
        s, e = disjoint[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def busy(ops) -> List[Interval]:
    """Union of the intervals in which some operation ran."""
    return union((s, e) for s, e, *_ in ops)


def matching(ops, pattern: str) -> List[Interval]:
    """Union of the intervals of operations whose name matches
    ``pattern``: a kernel's device time, ready for ``overlap``."""
    rx = re.compile(pattern)
    return union((s, e) for s, e, name, *_ in ops if rx.search(name))


def excluding(ops, pattern: str) -> list:
    """The operations whose name does not match ``pattern``."""
    rx = re.compile(pattern)
    return [o for o in ops if not rx.search(o[2])]


def busy_per_chip(ops, lo: float, hi: float) -> float:
    """Busy nanoseconds inside [lo, hi], averaged over the chips."""
    chips = sorted({c for *_, c in ops})
    if not chips:
        return 0.0
    return sum(length(clip(busy([o for o in ops if o[3] == c]), lo, hi))
               for c in chips) / len(chips)


def spans_of(trace: dict, kind: str) -> Dict[int, Interval]:
    """The harness's spans of one kind, by sequence number."""
    pre = f"bench.{kind}#"
    return {int(k[len(pre):]): v for k, v in trace["spans"].items()
            if k.startswith(pre)}


def top_ops(ops, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` operations that took the most device seconds, grouped by
    label with instruction numbers dropped."""
    tot: Dict[str, float] = {}
    for s, e, name, *_ in ops:
        if e > lo and s < hi:
            key = re.sub(r"\.\d+(?=\||$)", "", name)
            tot[key] = tot.get(key, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans: Dict[str, Interval], lo: float, hi: float,
              n: int = 10) -> List[list]:
    """The longest gaps in device work inside [lo, hi], each named by the
    harness span the host was in when the gap began."""
    b = clip(busy(ops), lo, hi)
    gaps = [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)]
    if b:
        gaps = [(lo, b[0][0])] + gaps + [(b[-1][1], hi)]
    named = []
    inner = sorted(((s, e, k) for k, (s, e) in spans.items()
                    if k != "bench.window"), key=lambda t: t[1] - t[0])
    for s, e in gaps:
        if e <= s:
            continue
        where = next((re.sub(r"#\d+$", "", k) for a, z, k in inner
                      if a <= s < z), "outside any span")
        named.append([where, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    return named[:n]
