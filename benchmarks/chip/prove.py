"""Prove one cell on the chip, in one call: a cold first run, the knee
sweep, the correctness limits, two sets of 6 runs and 3 traced runs.

    python3 benchmarks/chip/prove.py --workload <cell> --out <dir> \
        --seconds 50 --seed <n> [--sweep 3,4,5,6] [--sweep-seconds 20] \
        [--cal-seconds 15] [--phases first,sweep,cal,sets,trace]

Every step is a process of its own (``run.py`` or ``calibrate.py``), one
after the other, so one process holds the chip at a time; this one never
imports JAX.  Each step's output goes to ``<dir>/<step>.out|.err`` and a
summary to standard output.  The sweep and the calibration write what
they find into ``cells/<cell>.json`` (the rate, the limits), which the
later steps read; copy the file back from where the call ran.

* sweep: the knee is the highest rate, in order, that delivers at least
  90% of the output tokens it offers and whose backlog grows by under
  15% of the rate over the window; the cell runs at 0.8 of it.
* cal: 12 seeds of program and control gaps (widest and mean); a limit
  for each number whose smallest control reading is at least three times
  the largest program reading, at lo^(1/3) * hi^(2/3) (nearer the
  control, so fresh seeds have room above the program's readings).
* sets: two sets of 6 runs on the same seeds; prints each metric's
  medians and spreads (interquartile range over the median).
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
T0 = time.time()


def say(*a):
    print(f"[{time.time() - T0:7.1f}s]", *a, flush=True)


class Steps:
    def __init__(self, cell: str, out: Path):
        self.cell, self.out = cell, out
        out.mkdir(parents=True, exist_ok=True)

    def proc(self, name, argv, timeout):
        s = time.time()
        with open(self.out / f"{name}.out", "w") as fo, \
                open(self.out / f"{name}.err", "w") as fe:
            try:
                rc = subprocess.run([sys.executable, *argv], stdout=fo,
                                    stderr=fe, timeout=timeout).returncode
            except subprocess.TimeoutExpired:
                rc = 124
        out = (self.out / f"{name}.out").read_text().strip().splitlines()
        err = (self.out / f"{name}.err").read_text().splitlines()
        return rc, time.time() - s, out, err

    def run(self, name, seed, secs, trace, timeout=600):
        rc, wall, out, err = self.proc(name, [
            str(HERE / "run.py"), "--workload", self.cell, "--seed",
            str(seed), "--seconds", str(secs), "--trace", str(trace)],
            timeout)
        if rc != 0 or not out:
            say(f"{name} seed={seed} rc={rc} wall={wall:.0f}s FAILED")
            print("\n".join(err[-40:]), flush=True)
            return None
        res = json.loads(out[-1])
        values = {k: v["value"] for k, v in res["metrics"].items()}
        say(f"{name} seed={seed} wall={wall:.0f}s correct={res['correct']} "
            f"attempted={res['attempted']} failed={res['failed']} "
            f"metrics={values} device={res['device']} "
            f"compiles_in_window={res.get('compiles_in_window')}")
        for line in err:
            if line.startswith(("[bench]", "check")):
                print("   ", line[:400], flush=True)
        if trace:
            print("    breakdown", json.dumps(res.get("breakdown")),
                  flush=True)
        return res

    def calibrate(self, name, argv, timeout=1500):
        rc, wall, out, err = self.proc(name, [
            str(HERE / "calibrate.py"), "--workload", self.cell, *argv],
            timeout)
        say(f"{name} rc={rc} wall={wall:.0f}s")
        rows = [json.loads(line) for line in out if line.startswith("{")]
        for line in out:
            print("   ", line, flush=True)
        if rc != 0:
            print("\n".join(err[-40:]), flush=True)
        return rows


def spread(v):
    q = statistics.quantiles(v, n=4)
    return (q[2] - q[0]) / statistics.median(v)


def mean_output(cell: str) -> float:
    """Mean output tokens a request of the cell's mix asks for, after the
    policy's clip."""
    sys.path.insert(0, str(HERE))
    import traffic as tf
    from spec import load_cell
    mix = load_cell(cell).traffic
    n_max = mix["policy"].get("n_max") or 1 << 30
    return float(tf.lengths(mix["output"], 100_000).clip(max=n_max).mean())


def knee(rows, mean_out):
    """The highest rate, in order, that delivers at least 90% of the
    tokens it offers and whose backlog grows by under 15% of the rate
    (a batch of ``b_max`` swings the backlog within a short window)."""
    best = None
    for x in rows:
        offered = x["rate_rps"] * mean_out
        if x["output_tok_per_s"] < 0.9 * offered or \
                x["slope_rps"] > 0.15 * x["rate_rps"]:
            break
        best = x["rate_rps"]
    return best


def limits(rows):
    out = {}
    for name, key in (("max_logit_gap", ""), ("mean_logit_gap", "_mean")):
        lo = max(x["program" + key] for x in rows)
        hi = min(x["control" + key] for x in rows)
        say(f"{name}: program largest {lo}, control smallest {hi}, "
            f"ratio {hi / lo if lo else math.inf}")
        if hi >= 3 * lo:
            out[name] = float(f"{lo ** (1 / 3) * hi ** (2 / 3):.2g}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-seconds", default="20")
    ap.add_argument("--cal-seconds", default="15")
    ap.add_argument("--phases", default="first,sweep,cal,sets,trace")
    args = ap.parse_args(argv)
    phases, seed0 = args.phases.split(","), args.seed
    st = Steps(args.workload, Path(args.out))
    cell_file = HERE / "cells" / f"{args.workload}.json"
    cf = json.loads(cell_file.read_text())
    say("cell file", cf)
    if "first" in phases and st.run("first", seed0, 15, 0, 1500) is None:
        return 1
    if "sweep" in phases and args.sweep:
        rows = st.calibrate("sweep", ["--seeds", str(seed0 + 2), "--seconds",
                                      args.sweep_seconds, "--sweep",
                                      args.sweep])
        mean_out = mean_output(args.workload)
        say(f"mean clipped output {mean_out} tokens")
        k = knee(rows, mean_out)
        if k is None:
            say("no rate of the sweep is sustained")
            return 1
        cf["rate_rps"] = round(0.8 * k, 2)
        cell_file.write_text(json.dumps(cf) + "\n")
        say(f"knee {k} req/s, rate {cf['rate_rps']}")
    if "cal" in phases:
        seeds = [seed0 + 100 + 7919 * i for i in range(12)]
        rows = st.calibrate("cal", ["--seeds", ",".join(map(str, seeds)),
                                    "--seconds", args.cal_seconds])
        lim = limits(rows) if len(rows) == 12 else {}
        if not lim:
            say("no number separates the control from the program")
            return 1
        cf["limits"] = lim
        cell_file.write_text(json.dumps(cf) + "\n")
        say(f"cell file {cf}")
    if "sets" in phases:
        seeds = [seed0 + 1000 + 104729 * i for i in range(6)]
        sets = []
        for k in (1, 2):
            res = [st.run(f"set{k}_{i}", s, args.seconds, 0)
                   for i, s in enumerate(seeds)]
            if any(r is None or not r["correct"] for r in res):
                say("a run of the set failed or was not correct")
                return 1
            sets.append(res)
        for name in sets[0][0]["metrics"]:
            vals = [[r["metrics"][name]["value"] for r in res]
                    for res in sets]
            say(f"{name}: medians {[statistics.median(v) for v in vals]} "
                f"spreads {[spread(v) for v in vals]}")
    if "trace" in phases:
        for i in range(3):
            r = st.run(f"trace{i}", seed0 + 5000 + 31 * i, args.seconds, 1)
            if r is None or not r["correct"]:
                return 1
    say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
