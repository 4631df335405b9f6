"""Bring-up check of the served path on one TPU chip.

    python chip_smoke.py

Serves qwen2.5-3b at its published widths (36 layers, d_model 2048, 16
query and 2 KV heads, d_ff 11008, vocab 151936; bfloat16 weights drawn
from a seed, nothing downloaded) through ``repro.launch.serve.main`` with
elastic batching, so the ragged decode-attention kernel and the fused
compaction kernel both run compiled.  Then, on the same chip, it checks:

* every request produced the tokens it asked for, and no sample fell back
  to greedy decoding on non-finite logits;
* continuous batching (``serve_continuous``, 4 slots) does the same;
* decode attention resolves to the ragged kernel, and the compiled
  decode-chunk and compaction programs each hold a ``tpu_custom_call``;
* one decode step on the ragged path matches the dense path;
* fused compaction is bit-equal to the host gather on a real cache.

Every phase runs twice.  The first call includes compilation; the second
reuses the compiled programs, from memory or from the persistent compile
cache.  After each phase the device's peak memory in use so far is
printed.  The times and memory printed are from one smoke run and are not
benchmark metrics.

Where JAX finds no TPU the script exits non-zero and prints no result.  A
failed check raises.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.kernels.compaction import fused_compact  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import decode_step  # noqa: E402
from repro.serving.continuous import serve_continuous  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402

# 16 requests arriving within about a third of a second: batches fill to
# the 8-slot bucket, so elastic compaction fires as short replies finish.
SERVE_ARGV = ["--arch", "qwen2.5-3b", "--requests", "16", "--lam", "50",
              "--policy", "elastic"]
CONTINUOUS_REQUESTS = 6
SLOTS = 4
# Ragged vs dense logits after one decode step through every layer.  The
# dense path rounds its attention scores and probabilities to bfloat16
# where the kernel keeps float32, so the two differ by bfloat16 rounding
# carried through the depth of the model: allow a relative L2 error of
# 4 bfloat16 epsilons (2**-8 each).
LOGITS_REL_TOL = 4 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def serve_phase(argv) -> dict:
    """The user's entry point: every request must produce the tokens it
    asked for, with no greedy fallback, and compaction must fire."""
    summary = serve.main(argv)
    n = serve.parse_args(argv).requests
    check(summary["requests"] == n, f"served {summary['requests']} of {n}")
    check(summary["tokens"] == summary["target_tokens"],
          f"produced {summary['tokens']} of {summary['target_tokens']} "
          "tokens")
    check(summary["sample_fallbacks"] == 0,
          f"{summary['sample_fallbacks']} samples fell back to greedy")
    check(summary["compactions"] > 0, "elastic compaction never fired")
    return summary


def continuous_phase(eng: Engine, reqs) -> dict:
    prompts = [r.prompt_tokens for r in reqs]
    targets = [r.target_output_tokens for r in reqs]
    fallbacks = eng.sample_fallbacks
    res = serve_continuous(eng, prompts, targets, slots=SLOTS)
    check(list(res.produced) == targets,
          f"continuous produced {list(res.produced)}, asked {targets}")
    check(eng.sample_fallbacks == fallbacks,
          "continuous batching fell back to greedy")
    return {"requests": len(reqs), "tokens": int(res.produced.sum()),
            "decode_steps": res.decode_steps}


def _prefilled(eng: Engine, reqs):
    """A full bucket prefilled with the requests' prompts: (cache, kv_lens,
    next tokens)."""
    prompts = [r.prompt_tokens for r in reqs[:eng.ecfg.max_batch]]
    cache, kv_lens, last, _, _ = eng.prefill_batch(prompts)
    return cache, kv_lens, jnp.argmax(last, axis=-1).astype(jnp.int32)


def compiled_programs(eng: Engine, reqs) -> dict:
    """HLO text of the compiled decode-chunk and compaction programs the
    engine runs at its largest bucket."""
    cache, kv_lens, tok = _prefilled(eng, reqs)
    b = tok.shape[0]
    counters = jnp.zeros((b,), jnp.int32)
    keys = jnp.zeros((b, 2), jnp.uint32)
    chunk = eng._get_decode_chunk(b, eng.ecfg.decode_chunk)
    decode = chunk.lower(eng.params, cache, tok, kv_lens, counters,
                         counters, keys).compile()
    compact = fused_compact.lower(cache, kv_lens, tok, keys, counters,
                                  counters, nb=b // 2).compile()
    return {"decode_chunk": decode.as_text(), "compaction": compact.as_text()}


def check_kernels_compiled(cfg, programs: dict) -> None:
    """The Pallas kernels must be on the path, compiled for the chip and
    not replaced by their interpret-mode emulation."""
    check(cfg.resolved_decode_attention_impl == "ragged",
          f"decode attention resolved to {cfg.resolved_decode_attention_impl}")
    for name, text in programs.items():
        check("tpu_custom_call" in text,
              f"the compiled {name} program holds no Pallas kernel")


def ragged_vs_dense(eng: Engine, reqs) -> dict:
    """One decode step on the ragged kernel against the dense path, with
    the same weights and the same prefilled cache."""
    cache, kv_lens, tok = _prefilled(eng, reqs)
    logits = {}
    for impl in ("ragged", "dense"):
        cfg = dataclasses.replace(eng.cfg, decode_attention_impl=impl)
        step = jax.jit(functools.partial(decode_step, cfg))
        out, _ = step(eng.params, cache, tok, kv_lens)
        logits[impl] = np.asarray(out, np.float32)
    diff = logits["ragged"] - logits["dense"]
    rel = float(np.linalg.norm(diff) / np.linalg.norm(logits["dense"]))
    check(np.isfinite(logits["ragged"]).all(), "ragged logits not finite")
    check(rel <= LOGITS_REL_TOL,
          f"ragged vs dense logits: relative L2 error {rel} > "
          f"{LOGITS_REL_TOL}")
    return {"rel_l2": rel, "max_abs": float(np.abs(diff).max()),
            "argmax_agree": float(np.mean(logits["ragged"].argmax(-1) ==
                                          logits["dense"].argmax(-1)))}


def compaction_phase(eng: Engine, reqs) -> dict:
    """``compact_fused`` against the host gather ``compact`` on a real
    cache: half the bucket still owes tokens."""
    cache, kv_lens, tok = _prefilled(eng, reqs)
    b = tok.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(0), b)
    produced = np.ones(b, np.int32)
    targets = np.where(np.arange(b) % 2 == 0, 5, 1).astype(np.int32)
    keep = np.nonzero(targets > produced)[0].astype(np.int32)
    hc, hl, ht, hb, _, hk = eng.compact(cache, kv_lens, tok, keep, keys)
    fc, fl, ft, fb, fk = eng.compact_fused(
        cache, kv_lens, tok, jnp.asarray(produced), jnp.asarray(targets),
        len(keep), keys)
    check(fb == hb, f"fused bucket {fb} != host bucket {hb}")
    host = jax.tree.leaves((hc, hl, ht, hk))
    fused = jax.tree.leaves((fc, fl, ft, fk))
    check(len(host) == len(fused), "compaction results differ in structure")
    for h, f in zip(host, fused):
        check(np.array_equal(np.asarray(h), np.asarray(f)),
              "fused compaction is not bit-equal to the host gather")
    return {"bucket": fb, "leaves": len(fused)}


def _twice(name: str, fn, *args):
    """Run a phase twice and log each call's wall time, and the device's
    peak memory in use so far."""
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = fn(*args)
        walls.append(time.perf_counter() - t0)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"phase={name} first_call_s={walls[0]} second_call_s={walls[1]} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    return out


def main() -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    log(f"device kind={device.device_kind} count={len(jax.devices())} "
        "(one smoke run, not a benchmark)")

    log(f"serve: {_twice('serve', serve_phase, SERVE_ARGV)}")

    args = serve.parse_args(SERVE_ARGV)
    cfg = serve.model_config(args)
    t0 = time.perf_counter()
    eng = Engine(cfg, serve.engine_config(args, cfg))
    jax.block_until_ready(eng.params)
    log(f"weights_init_s={time.perf_counter() - t0}")
    reqs = serve.request_stream(args, cfg)

    out = _twice("continuous", continuous_phase, eng,
                 reqs[:CONTINUOUS_REQUESTS])
    log(f"continuous: {out}")
    programs = _twice("compile_kernels", compiled_programs, eng, reqs)
    check_kernels_compiled(cfg, programs)
    log("tpu_custom_call count: " + ", ".join(
        f"{k}={v.count('tpu_custom_call')}" for k, v in programs.items()))
    out = _twice("ragged_vs_dense", ragged_vs_dense, eng, reqs)
    log(f"ragged_vs_dense: {out}")
    log(f"compaction: {_twice('compaction', compaction_phase, eng, reqs)}")

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
