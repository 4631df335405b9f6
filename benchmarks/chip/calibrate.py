"""Readings that set a cell's correctness limit and its rate, in one
process, through the same ``run_cell`` that ``run.py`` drives.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds 15 [--sweep 2,3,4]

For each seed: weights and traffic from the seed, a window at the cell's
own load through the timed path, then the check of the run against the
reference with the control (the reference computed with int8 weights and
bfloat16 activations) judged in the program's place.  Prints one JSON
line per seed with the program's widest and mean gap and the
control's, and whether the control came out not correct.

With ``--sweep`` it instead serves the first seed's traffic at each rate
in req/s, one window each, and prints the backlog's trend, to find the
highest rate the system sustains.

The engine is built and warmed once; each seed's weights are drawn anew
into it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import run_cell, set_up, use_compile_cache  # noqa: E402
from spec import load_cell  # noqa: E402


def backlog_trend(run) -> dict:
    """Requests due but not yet dispatched, at each dispatch in the
    window: its slope over the window (req/s), its last and largest
    value."""
    due = sorted(run.loop0 + r.due for r in run.reqs)
    disp = sorted(run.loop["dispatched"].values())
    ts = [t for t in disp if run.t0 <= t < run.t1]
    if len(ts) < 2:
        return {"slope_rps": float("nan"), "last": float("nan")}
    q = [np.searchsorted(due, t, "right") - np.searchsorted(disp, t, "left")
         for t in ts]
    slope = float(np.polyfit(np.array(ts) - ts[0], q, 1)[0])
    return {"slope_rps": slope, "last": int(q[-1]), "max": int(max(q))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    sweep = [float(r) for r in args.sweep.split(",")] if args.sweep else []
    runs = [(seeds[0], r) for r in sweep] or [(s, None) for s in seeds]
    t = time.perf_counter()
    eng, _, _ = set_up(cell, seeds[0])
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    log(f"[calibrate] set-up {time.perf_counter() - t:.1f} s")
    for seed, rate in runs:
        out = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       log=log, control=not sweep, eng=eng, rate=rate)
        line = {"seed": seed, "rate_rps": rate or cell.cell.get("rate_rps"),
                **{k: v["value"] for k, v in out["metrics"].items()},
                "attempted": out["attempted"],
                **out["gaps"],
                "control_correct": out["correct"] if not sweep else None,
                "wrong_token_counts":
                    out["checks"]["wrong_token_counts"]["value"],
                "compiles_in_window": out["compiles_in_window"],
                **out["checked"]}
        if sweep:
            line.update(backlog_trend(out["run"]))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
