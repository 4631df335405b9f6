"""Production serving launcher: the paper's technique as the control plane.

Runs the batched engine on a Poisson request stream; the AdaptiveController
watches arrivals/completions and sets (n_max, b_max, policy) from the
paper's queueing models (Eqs 10-13, 25, §IV-D). Straggler mitigation at the
request level = elastic batching + max-token clipping (DESIGN.md §6).

CPU-scale usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 32 --lam 0.5

Without ``--smoke`` the published configuration is served at full width
(weights drawn from a seed), which needs a TPU.  ``main(argv)`` is also
the Python entry point: it returns a summary of the run.
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "dynamic", "elastic"])
    ap.add_argument("--log-mean", type=float, default=3.0)
    ap.add_argument("--log-std", type=float, default=0.7)
    return ap.parse_args(argv)


def model_config(args):
    from repro.configs import get_config, get_smoke_config
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return dataclasses.replace(cfg, decode_cache_update="scatter")


def engine_config(args, cfg):
    """The KV cache is held in the model's compute dtype (bfloat16 at
    published widths, float32 for the smoke variants)."""
    from repro.serving.engine import EngineConfig
    return EngineConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                        prompt_bucket=16, cache_dtype=cfg.dtype)


def request_stream(args, cfg):
    from repro.core.distributions import LogNormalTokens
    from repro.data.pipeline import make_request_stream
    dist = LogNormalTokens(args.log_mean, args.log_std,
                           support=args.max_seq // 2)
    return make_request_stream(args.requests, args.lam, dist,
                               vocab=cfg.vocab_size, seed=0)


def main(argv=None) -> dict:
    """Serve ``--requests`` requests and return a summary: requests
    served, tokens produced against the tokens asked for (each request's
    target, clipped at the controller's ``n_max``), mean queue wait,
    batches and compactions run, and greedy fallbacks on non-finite
    logits."""
    args = parse_args(argv)

    from repro.core.control import AdaptiveController
    from repro.core.latency_model import BatchLatencyModel, LatencyModel
    from repro.serving.engine import Engine

    cfg = model_config(args)
    eng = Engine(cfg, engine_config(args, cfg))
    reqs = request_stream(args, cfg)
    ctrl = AdaptiveController(
        LatencyModel(a=5e-3, c=0.05),
        BatchLatencyModel(k1=5e-3, k2=5e-2, k3=1e-4, k4=5e-3),
        theta=119 / 120, elastic_available=(args.policy != "dynamic"),
        min_samples=8)

    clock = 0.0
    served = batches = produced_total = asked_total = 0
    waits = []
    i = 0
    while i < len(reqs):
        # collect everything that has arrived by `clock` (dynamic batching)
        rec = ctrl.recommendation()
        b_cap = rec.b_max or args.max_batch
        batch = [reqs[i]]
        ctrl.observe_arrival(reqs[i].arrival)
        clock = max(clock, reqs[i].arrival)
        i += 1
        while i < len(reqs) and reqs[i].arrival <= clock and len(batch) < b_cap:
            ctrl.observe_arrival(reqs[i].arrival)
            batch.append(reqs[i])
            i += 1
        for r in batch:
            waits.append(clock - r.arrival)
        elastic = (rec.policy == "elastic") if args.policy == "auto" \
            else (args.policy == "elastic")
        res = eng.generate([r.prompt_tokens for r in batch],
                           [r.target_output_tokens for r in batch],
                           elastic=elastic, n_max=rec.n_max)
        clock += res["batch_seconds"]
        for r, produced in zip(batch, res["produced"]):
            ctrl.observe_completion(int(produced))
        asked = [r.target_output_tokens for r in batch]
        if rec.n_max is not None:
            asked = np.minimum(asked, rec.n_max)
        served += len(batch)
        batches += 1
        produced_total += int(np.sum(res["produced"]))
        asked_total += int(np.sum(asked))
        print(f"[serve] t={clock:8.2f}s batch={len(batch)} "
              f"policy={'elastic' if elastic else 'dynamic'} "
              f"n_max={rec.n_max} served={served}/{args.requests}",
              flush=True)

    print(f"[serve] mean queue wait {np.mean(waits):.3f}s | "
          f"p95 {np.percentile(waits, 95):.3f}s | "
          f"final rec: policy={ctrl.recommendation().policy} "
          f"n_max={ctrl.recommendation().n_max} "
          f"b_max={ctrl.recommendation().b_max}", flush=True)
    return {
        "requests": served,
        "tokens": produced_total,
        "target_tokens": asked_total,
        "mean_wait_s": float(np.mean(waits)),
        "batches": batches,
        "compactions": sum(e["kind"] == "compact" for e in eng.step_log),
        "sample_fallbacks": eng.sample_fallbacks,
    }


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
