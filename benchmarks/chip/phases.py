"""Where one traced run's time goes, by the program's own names.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

Runs the cell as ``run.py --trace 1`` does (the same set-up, window,
check and per-layer metrics), keeps the trace, and reduces it further
with the engine's host spans ``engine.<phase>`` and the op paths'
scopes:

* ``clock``: each ``engine.*`` event of the trace matched, in order, to
  its ``(phase, start_ns, end_ns)`` record in ``Engine.step_log``; one
  offset per trace, fitted on the first, and the largest distance left;
* ``idle``: device idle inside the harness's traced ``generate`` spans,
  split by the engine phase the host was in; idle between two engine
  phases where a harness span ends (``bench.prefill`` / ``decode_chunk``
  / ``compact``: ``engine_io.Recorder`` reads back and keeps its records
  there, after the engine's call returned) is the harness's wrapper; the
  rest (``unattributed``) is in no phase;
* ``scopes``: device time per decode step inside the traced
  ``decode_chunk`` spans (compaction left out) by scope (``kv_cache``,
  ``ffn``, ``logits``, ``sample``, read from each operation's op path,
  ``op_paths``; ``unscoped`` is busy time in none of them), with the
  heaviest operations of ``kv_cache`` and of the unscoped leaves
  (``while`` loops left out);
* ``patterns``: the operations ``ragged_attn_roofline`` and
  ``decode_mfu`` match (``ragged_decode_attention``, ``fused_compact``),
  by instruction, with their device seconds;
* ``host_gap_ms``: ``decode_host_gap_ms`` over the untraced chunks (the
  metric) beside the same mean over the traced ones.

The last line of standard output is the JSON; ``--out`` writes it too.
On a program without engine spans the engine's sections come out empty.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import op_paths  # noqa: E402
import trace_reduce as tr  # noqa: E402

SCOPES = ("kv_cache", "ffn", "logits", "sample")
PATTERNS = ("ragged_decode_attention", "fused_compact")
WRAPPED = ("prefill", "decode_chunk", "compact")     # the harness's spans


def engine_events(trace_dir: str) -> list:
    """``(phase, start, end)`` of every ``engine.*`` host event, sorted."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name[len("engine."):], ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events
                        if ev.name.startswith("engine.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def subtract(a, b):
    """Sorted disjoint intervals ``a`` minus the union of ``b``."""
    b = tr.union(b)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def clock_fit(step_log, events) -> dict:
    """Match the trace's engine events to the last records of
    ``step_log`` (the trace holds the run's tail), one offset fitted on
    the first pair."""
    records = sorted((r for e in step_log for r in e.get("phases", ())),
                     key=lambda r: (r[1], -r[2]))[-len(events):] \
        if events else []
    if not events or [e[0] for e in events] != [r[0] for r in records]:
        return {"events": len(events), "matched": False}
    off = events[0][1] - records[0][1]
    dev = [max(abs(ev[1] - off - r[1]), abs(ev[2] - off - r[2]))
           for ev, r in zip(events, records)]
    return {"events": len(events), "matched": True, "offset_ns": off,
            "max_abs_ns": max(dev), "mean_abs_ns": sum(dev) / len(dev)}


def idle_by_phase(trace, events, lo, hi) -> dict:
    gens = tr.union(s for s in tr.spans_of(trace, "generate").values()
                    if s[0] >= lo and s[1] <= hi)
    idle = subtract(gens, trace["busy"])
    total = tr.length(idle)
    phases = {}
    for name, s, e in events:
        phases.setdefault(name, []).append((s, e))
    out = {"generate_ms": 1e-6 * tr.length(gens), "idle_ms": 1e-6 * total}
    for name, spans in sorted(phases.items()):
        if name != "generate":
            u = tr.union(spans)
            out[name] = 1e-6 * sum(tr.overlap(u, s, e) for s, e in idle)
    inner = [e for e in events if e[0] != "generate"]
    ends = sorted(s[1] for k in WRAPPED
                  for s in tr.spans_of(trace, k).values())
    wrapper = tr.union(
        (a[2], b[1]) for a, b in zip(inner, inner[1:])
        if bisect.bisect_left(ends, a[2]) < bisect.bisect_right(ends, b[1]))
    out["harness_wrapper"] = 1e-6 * sum(tr.overlap(wrapper, s, e)
                                        for s, e in idle)
    named = sum(v for k, v in out.items()
                if k not in ("generate_ms", "idle_ms"))
    out["unattributed"] = 1e-6 * total - named
    return out


def _key(label: str) -> str:
    """An operation's instruction, its result type and its op path (the
    trace names an operation by its HLO text, operands and all)."""
    text, _, path = label.partition("|")
    name, _, rest = text.partition(" = ")
    return f"{name} {rest.split(' ')[0][:48]}|{path}"


def scopes_per_step(run, compaction: str, paths) -> dict:
    ops = tr.excluding(run.trace["ops"], compaction)
    chunks = run.traced_calls("decode_chunk")
    steps = sum(c.size for c, _ in chunks)
    if not steps:
        return {}

    spans = tr.union(span for _, span in chunks)

    def inside(intervals):
        u = tr.union(intervals)
        return sum(tr.overlap(u, lo, hi) for lo, hi in spans)

    def scope_of(op):
        path = paths.of(op).split("/")
        return next((s for s in SCOPES if s in path), None)

    out = {"steps": steps}
    scoped = []
    for s in SCOPES:
        mine = [op[:2] for op in ops if scope_of(op) == s]
        scoped += mine
        out[f"{s}_ms"] = 1e-6 * inside(mine) / steps
    busy = tr.busy(ops)
    out["busy_ms"] = 1e-6 * inside(busy) / steps
    out["unscoped_ms"] = 1e-6 * inside(subtract(busy, scoped)) / steps

    def top(pred, n=12):
        tot = {}
        for op in ops:
            if pred(op):
                t = tr.overlap(spans, op[0], op[1])
                if t:
                    k = f"{_key(op[2])}{paths.of(op)}"
                    tot[k] = tot.get(k, 0) + t
        return [[k, 1e-6 * v / steps]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    out["kv_cache_top"] = top(lambda op: scope_of(op) == "kv_cache")
    out["unscoped_top"] = top(lambda op: scope_of(op) is None
                              and not re.match(r"%?while\.", op[2]))
    return out


def pattern_ops(ops, lo, hi) -> dict:
    out = {}
    for pat in PATTERNS:
        rx = re.compile(pat)
        tot = {}
        for s, e, label, *_ in ops:
            if rx.search(label) and e > lo and s < hi:
                k = _key(label)
                tot[k] = tot.get(k, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        out[pat] = sorted(([k, v] for k, v in tot.items()),
                          key=lambda kv: -kv[1])
    return out


def host_gaps(run, reader) -> dict:
    out = {}
    for side in ("untraced", "traced"):
        gaps = reader.boundaries(run, traced=side == "traced")
        out[side] = {"n": len(gaps),
                     "mean_ms": 1e-6 * sum(gaps) / len(gaps) if gaps else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from spec import load_cell
    cell = load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("phases.py: needs a TPU", file=sys.stderr)
        return 1
    from harness import TRACE_DIR, metric_reader, run_cell, use_compile_cache
    use_compile_cache()
    try:
        out = run_cell(cell, args.seed, args.seconds, True, T_START,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
        run = out["run"]
        events = engine_events(str(TRACE_DIR))
        paths = op_paths.load(str(TRACE_DIR))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    lo, hi = run.traced
    in_trace = [e for e in events if e[1] >= lo and e[2] <= hi]
    result = {
        "workload": cell.name, "seed": args.seed, "correct": out["correct"],
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "device": out["device"], "breakdown": out["breakdown"],
        "clock": clock_fit(run.step_log, events),
        "idle": idle_by_phase(run.trace, in_trace, lo, hi),
        "scopes": scopes_per_step(run, metric_reader("decode_mfu").COMPACTION,
                                  paths),
        "patterns": pattern_ops(run.trace["ops"], lo, hi),
        "host_gap_ms": host_gaps(run, metric_reader("decode_host_gap_ms")),
    }
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
