# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (plus a roofline summary read from the dry-run artifacts).

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _retry(step, quick: bool, attempts: int = 3, backoff: float = 2.0):
    """Run one bench step; in quick (CI) mode, retry transient failures
    with exponential backoff — shared-runner flakiness (timer jitter
    tripping a perf assertion, OOM from a neighbour) should not fail the
    whole suite.  Full local runs keep fail-fast semantics so a real
    regression is never masked by a retry."""
    if not quick:
        return step()
    for attempt in range(attempts):
        try:
            return step()
        except Exception as e:          # pragma: no cover - flake path
            if attempt + 1 == attempts:
                raise
            wait = backoff * (2.0 ** attempt)
            print(f"bench step {getattr(step, '__name__', step)!r} failed "
                  f"({type(e).__name__}: {e}); retry {attempt + 1}/"
                  f"{attempts - 1} in {wait:.0f}s", file=sys.stderr)
            time.sleep(wait)


def main() -> None:
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        bench_latency_model, bench_batch_scaling, bench_order_stats,
        bench_clipping, bench_batching_policies, bench_fixed_batching,
        bench_predictors, bench_fleet, bench_faults, bench_engine_e2e,
        bench_scale, bench_autoscale, bench_sessions, bench_memory)

    print("name,us_per_call,derived")
    steps = [
        bench_latency_model.main,       # Table I + Fig 2a
        bench_batch_scaling.main,       # Fig 2b
        bench_order_stats.main,         # Fig 3
        bench_clipping.main,            # Fig 4
        bench_batching_policies.main,   # Fig 5
        bench_fixed_batching.main,      # Fig 6
        bench_predictors.main,          # prediction-noise robustness
        bench_fleet.main,               # fleet routing across replicas
        bench_faults.main,              # fault tolerance / degradation
        bench_engine_e2e.main,          # beyond-paper engine E2E
        bench_scale.main,               # sharded sweeps + fused serving
        bench_autoscale.main,           # non-stationary traffic + control
        bench_sessions.main,            # re-entrant sessions / affinity
        bench_memory.main,              # KV budget / prefill-decode tandem
    ]
    for step in steps:
        _retry(lambda s=step: s(quick), quick)

    # roofline table (deliverable g) from the dry-run artifacts, if present
    try:
        from benchmarks.roofline import load_all, render_table
        rows = load_all("results/dryrun", "single")
        if rows:
            print("\n=== Roofline (single pod, baseline cells) ===")
            print(render_table(rows))
    except Exception as e:  # pragma: no cover
        print("roofline table unavailable:", e)


if __name__ == '__main__':
    main()
