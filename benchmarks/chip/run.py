"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's model with weights drawn from the seed, warms every
program the cell's traffic reaches, serves the seeded open-loop traffic
for ``--seconds`` on the wall clock, then checks a sample of the served
tokens against the plain float32 reference.  With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` it traces the
window with the JAX profiler and reports the per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
limit).  The last lines of standard error repeat the checks.  Where JAX
finds no TPU, or fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.seed < 0:
        print("run.py: --seed must be a non-negative whole number",
              file=sys.stderr)
        return 2
    from spec import load_cell
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    try:
        import repro.serving.engine  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    import shutil
    from harness import TRACE_DIR, run_cell, use_compile_cache
    use_compile_cache()
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, log=lambda s: print(s, file=sys.stderr,
                                                    flush=True))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if args.trace:
        keys.append("breakdown")
    line = {k: out[k] for k in keys}
    line["compiles_in_window"] = out["compiles_in_window"]
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
