"""PR 7: sharded sweeps + fused serving, the scale benchmark.

Two tracked records, both under the ``pr7_scale`` key:

1. **BENCH_simulators.json**: the sharded fleet sweep
   (``shardsweep.fleet_sweep``, every replica sub-stream of every (R, λ)
   cell a lane of one ``shard_map`` dispatch) against the per-cell
   ``fleet.sweep`` path of PR 5/6, on a forced 4-CPU-device mesh
   (``JAX_PLATFORMS=cpu`` and
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, run in a
   subprocess so the parent's single-device JAX config is untouched; the
   child never loads the TPU runtime, so it cannot contend for a chip the
   parent holds).  It is a CPU-mesh rehearsal, and its record says so.
   The grid simulates ~1M total requests in quick mode (~10M full); the
   sharded result must be BIT-equal and the round_robin grid must clear a
   2x sweep-throughput gain.
2. **BENCH_engine.json**: dense vs ragged decode attention µs/step in
   interpret mode (honest CPU-interpret numbers — the ragged kernel only
   wins compiled on TPU, which is exactly why ``decode_attention_impl=
   "auto"`` resolves to dense off-TPU), plus elastic-generate compaction
   accounting: fused (Pallas gather, device-resident keep) vs host
   recompaction, identical tokens, host_syncs(fused) == host_syncs(host)
   minus one per compaction event.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

if __package__ in (None, ""):          # direct `python bench_....py` run
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import emit, emit_bench, timer

_WORKER = textwrap.dedent("""
    import json, sys, time
    import numpy as np
    import jax
    from repro.core import fleet, shardsweep
    from repro.core.distributions import LogNormalTokens
    from repro.core.latency_model import BatchLatencyModel
    from repro.core.policies import ElasticPolicy

    n_req = int(sys.argv[1])
    LN = LogNormalTokens()
    LAT = BatchLatencyModel(k1=0.05, k2=0.5, k3=0.0005, k4=0.02)
    R_grid = [2, 4, 8]
    lams = [0.5, 0.55, 0.6, 0.65, 0.7, 0.75, 0.8, 0.85]
    pol = ElasticPolicy(b_max=8)
    total = len(R_grid) * len(lams) * n_req

    def best_of(fn, reps=3):
        fn()                                   # warm the compile caches
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            ts.append(time.perf_counter() - t0)
        return min(ts), out

    res = {"platform": jax.default_backend(),
           "devices": jax.device_count(), "n_req_per_cell": n_req,
           "cells": len(R_grid) * len(lams), "total_requests": total,
           "R_grid": R_grid, "lams": lams}
    for router in ("round_robin", "least_work"):
        ts, a = best_of(lambda: fleet.sweep(
            R_grid, lams, router, pol, LN, LAT, num_requests=n_req, seed=3))
        th, b = best_of(lambda: shardsweep.fleet_sweep(
            R_grid, lams, router, pol, LN, LAT, num_requests=n_req, seed=3))
        assert np.array_equal(a["mean_wait"], b["mean_wait"]), router
        res[router] = {
            "single_device_s": ts, "sharded_s": th, "speedup": ts / th,
            "single_req_per_s": total / ts, "sharded_req_per_s": total / th,
            "bit_equal": True}
    print(json.dumps(res))
""")


def _sharded_record(quick: bool) -> dict:
    """Run the forced-4-device sweep comparison in a fresh CPU process."""
    n_req = 42_000 if quick else 420_000
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _WORKER, str(n_req)],
                       env=env, capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"sharded sweep worker failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _decode_attention_record(quick: bool) -> dict:
    """Dense vs ragged decode attention, interpret mode (CPU-honest)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ragged_decode_attention import ragged_decode_attention
    from repro.models.layers import decode_attention

    b, s, hq, hkv, d = 8, 512, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    lens = jnp.asarray(np.linspace(1, s, b).astype(np.int32))

    dense = jax.jit(lambda: decode_attention(
        q[:, None], kc, vc, lens, window=None)[:, 0])
    ragged = lambda: ragged_decode_attention(q, kc, vc, lens, block_kv=128)
    np.testing.assert_allclose(np.asarray(ragged()), np.asarray(dense()),
                               atol=2e-5, rtol=2e-5)
    reps = 5 if quick else 20
    out = {"batch": b, "max_seq": s, "heads": f"{hq}q/{hkv}kv",
           "interpret_mode": jax.default_backend() != "tpu",
           "resolved_default": "ragged" if jax.default_backend() == "tpu"
           else "dense"}
    for name, fn in (("dense", dense), ("ragged", ragged)):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        dt = (time.perf_counter() - t0) / reps
        out[f"{name}_us_per_step"] = dt * 1e6
        out[f"{name}_tok_per_s"] = b / dt
    return out


def _compaction_record(quick: bool) -> dict:
    """Elastic generate under both compaction impls: fused must match the
    host path token-for-token while paying zero syncs per compaction."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.serving.engine import Engine, EngineConfig

    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"), num_layers=2)
    ecfg = EngineConfig(max_batch=4, max_seq=256, prompt_bucket=16)
    prompts = [np.arange(6, dtype=np.int32) + i for i in range(3)]
    targets = [25, 4, 13] if quick else [90, 10, 45]
    runs = {}
    for impl in ("fused", "host"):
        eng = Engine(cfg, dataclasses.replace(ecfg, compact_impl=impl))
        t0 = time.perf_counter()
        r = eng.generate(prompts, targets, elastic=True, chunk=4,
                         return_tokens=True, temperature=0.8, seed=11)
        dt = time.perf_counter() - t0
        ev = [e for e in eng.step_log if e["kind"] == "compact"]
        runs[impl] = {"wall_s": dt, "host_syncs": r["host_syncs"],
                      "compaction_events": len(ev),
                      "syncs_per_compaction": (
                          sum(e["syncs"] for e in ev) / max(len(ev), 1)),
                      "tokens": r["tokens"]}
    assert runs["fused"]["tokens"] == runs["host"]["tokens"]
    assert runs["fused"]["syncs_per_compaction"] == 0.0
    assert runs["fused"]["host_syncs"] == (
        runs["host"]["host_syncs"] - runs["host"]["compaction_events"])
    for v in runs.values():
        del v["tokens"]
    return {"impls": runs, "tokens_identical": True,
            "target_tokens": sum(targets)}


def main(quick: bool = False):
    derived = {}
    with timer() as t_all:
        sharded = _sharded_record(quick)
        rr = sharded["round_robin"]
        assert rr["speedup"] >= 2.0, \
            f"sharded sweep below the 2x bar: {rr['speedup']:.2f}x"
        derived["sweep_speedup_rr"] = rr["speedup"]
        derived["sweep_speedup_lw"] = sharded["least_work"]["speedup"]
        derived["sweep_total_requests"] = sharded["total_requests"]
        derived["sharded_req_per_s"] = rr["sharded_req_per_s"]

        attn = _decode_attention_record(quick)
        derived["dense_decode_us"] = attn["dense_us_per_step"]
        derived["ragged_decode_us"] = attn["ragged_us_per_step"]

        comp = _compaction_record(quick)
        derived["fused_syncs_per_compaction"] = \
            comp["impls"]["fused"]["syncs_per_compaction"]
        derived["host_syncs_saved"] = \
            comp["impls"]["host"]["compaction_events"]

    emit_bench("simulators", {
        "workload": f"fleet grid R={sharded['R_grid']} x "
                    f"{len(sharded['lams'])} lams x "
                    f"{sharded['n_req_per_cell']} reqs/cell "
                    f"({sharded['total_requests']} total), elastic b8, "
                    f"forced {sharded['devices']}-device CPU mesh",
        "platform": sharded["platform"],
        "devices": sharded["devices"],
        "total_requests": sharded["total_requests"],
        "round_robin": sharded["round_robin"],
        "least_work": sharded["least_work"],
    }, key="pr7_scale")
    emit_bench("engine", {
        "decode_attention": attn,
        "compaction": comp,
    }, key="pr7_scale")
    emit("scale", t_all.seconds, derived)
    return derived


if __name__ == "__main__":
    main(quick=os.environ.get("REPRO_BENCH_QUICK", "0") == "1")
