"""Batched serving engine (TPU-style static-bucket execution).

XLA wants static shapes, so the engine compiles one executable per
(batch-bucket, seq-bucket) pair and routes work to the smallest bucket that
fits — the TPU adaptation of GPU dynamic batching (DESIGN.md §3). Elastic
batching gets its *real* speedup from bucket compaction: when enough replies
finish early, the live requests are gathered into the next-smaller batch
bucket and decoding continues there (the kernel-level analogue is the ragged
decode kernel in repro.kernels).

Host-sync accounting (the chunked-decode design)
------------------------------------------------
Decoding is driven by ``decode_chunk``: a ``jax.lax.scan`` of up to
``EngineConfig.decode_chunk`` decode steps compiled once per
(batch-bucket, step-count) pair. The carry — ``(cache, tok, kv_lens,
produced, per-slot sampling keys)`` — lives on device for the whole chunk, so the host blocks once
per chunk instead of once per token: O(tokens / chunk) syncs instead of
O(tokens). Each sync is counted in ``Engine.host_syncs`` and each chunk is
logged in ``step_log``; ``generate`` reports the syncs it spent so the
benchmark suite can assert the accounting. Elastic bucket compaction and
completion bookkeeping happen at chunk boundaries (per-request completion
times are interpolated inside a chunk from the per-step active mask the scan
emits). Compaction itself is device-resident by default
(``EngineConfig.compact_impl="fused"``): one jitted call around the Pallas
gather kernel in ``repro.kernels.compaction``, keep indices derived in-jit
from the chunk's produced/targets carry — zero host syncs per compaction
event. ``compact_impl="host"`` keeps the reference path (host keep indices,
per-leaf eager gathers) and counts one host-visible event per compaction.
``decode_batch`` (one step, one sync) is kept as the reference path
— ``generate(..., chunk=1)`` reproduces it step for step.

The engine serves two roles:
  * run actual tiny models on CPU (examples, wall-clock calibration of the
    paper's a, c, k1..k4 constants),
  * expose per-step timing hooks the schedulers use to drive policy
    experiments on a virtual clock at paper scale.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.config import ModelConfig
from repro.models.model import (
    param_specs, init_cache, prefill, decode_step, stack_group_cache)
from repro.models.params import init_params


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 16            # largest batch bucket (power of 2)
    max_seq: int = 512             # KV capacity per slot
    prompt_bucket: int = 64        # prompts padded to a multiple of this
    cache_dtype: str = "float32"
    greedy: bool = True
    min_bucket: int = 1
    decode_chunk: int = 32         # decode steps fused per host sync
    temperature: float = 0.0       # 0 -> greedy argmax decoding
    top_k: Optional[int] = None    # sample from the k best logits only
    # elastic bucket compaction implementation:
    #   fused - one jitted call around the Pallas gather kernel
    #           (repro.kernels.compaction); keep indices derived on device
    #           from the chunk's produced/targets counters, zero host syncs
    #   host  - reference path: host-resident keep indices + per-leaf eager
    #           gathers (one host-visible event per compaction)
    compact_impl: str = "fused"
    # KV-token budget for one engine (repro.core.memory two-resource
    # model): generate() refuses a batch whose worst-case footprint
    # (prompt + target tokens per member) exceeds it, and tracks the
    # realized occupancy from the live kv_lens at chunk boundaries
    # (Engine.kv_report).  None = unconstrained.
    kv_budget: Optional[int] = None


def _bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)


@contextlib.contextmanager
def _phase(name: str, record: list):
    """One host phase of the engine: a profiler span ``engine.<name>``
    (written only while a trace is recorded) and, on leaving it, the record
    ``(name, start_ns, end_ns)`` appended to ``record``.  The stamps are
    ``time.perf_counter_ns()``, the clock of ``step_log``'s ``seconds``,
    taken inside the span, so one offset per trace maps every record onto
    its event in the device trace."""
    with jax.profiler.TraceAnnotation(f"engine.{name}"):
        start = time.perf_counter_ns()
        yield
        record.append((name, start, time.perf_counter_ns()))


def _jit(name: str, fn, **kw):
    """``jax.jit`` under a stable name: the device trace's op paths read
    ``jit(<name>)/...``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


def _guard_logits(logits):
    """Per-slot non-finite guard: ``bad[b]`` is True when the slot's
    logits contain NaN/inf (one poisoned request), ``safe`` replaces
    non-finite entries with -inf so argmax/categorical stay defined.
    Finite logits pass through bit-identical."""
    finite = jnp.isfinite(logits)
    bad = ~jnp.all(finite, axis=-1)
    return jnp.where(finite, logits, -jnp.inf), bad


def _guarded_argmax(logits):
    """Greedy decode over guarded logits; returns (tokens, bad mask)."""
    safe, bad = _guard_logits(logits)
    return jnp.argmax(safe, axis=-1).astype(jnp.int32), bad


def _sample_tokens(keys, logits, temperature: float, top_k: Optional[int]):
    """Temperature / top-k sampling over [b, vocab] logits with one PRNG
    key PER SLOT (``keys``: [b, 2]); temperature is a trace-time constant
    and temperature=0 callers use argmax instead.  Sampling per slot from
    its own key — rather than one batch-wide key the categorical splits
    internally by row — is what makes sampled streams independent of the
    batch bucket a request happens to occupy.

    Slots with non-finite logits fall back to greedy over the guarded
    logits (the categorical is undefined there) and are reported in the
    returned ``bad`` mask; finite slots sample bit-identically to the
    unguarded path.  Returns (tokens, bad)."""
    safe, bad = _guard_logits(logits)
    greedy = jnp.argmax(safe, axis=-1).astype(jnp.int32)
    if top_k is not None:
        kth = jax.lax.top_k(safe, top_k)[0][..., -1:]
        safe = jnp.where(safe < kth, -jnp.inf, safe)
    sampled = jax.vmap(
        lambda k, l: jax.random.categorical(k, l / temperature, axis=-1)
    )(keys, safe).astype(jnp.int32)
    return jnp.where(bad, greedy, sampled), bad


def _split_slot_keys(keys):
    """Advance every slot's key one step: returns (carried, subkeys)."""
    split = jax.vmap(jax.random.split)(keys)
    return split[:, 0], split[:, 1]


class Engine:
    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig,
                 params=None, seed: int = 0, ctx: ShardCtx = NULL_CTX):
        self.cfg = cfg
        self.ecfg = ecfg
        self.ctx = ctx
        if params is None:
            params = init_params(param_specs(cfg), jax.random.PRNGKey(seed),
                                 jnp.dtype(cfg.dtype))
        self.params = params
        self._prefill_fns: Dict[Tuple[int, int], callable] = {}
        self._decode_fns: Dict[int, callable] = {}
        self._chunk_fns: Dict[tuple, callable] = {}
        # one entry per engine call (kind, batch, seconds; seq of a prefill,
        # steps of a decode chunk), each with the id ``gen`` of the
        # ``generate`` call it belongs to (None outside one) and its host
        # phases: records (phase, start_ns, end_ns) on
        # ``time.perf_counter_ns()``, mirrored by profiler spans
        # ``engine.<phase>``.  ``generate`` appends its own entry on return.
        self.step_log: List[dict] = []
        self._gen: Optional[int] = None   # id of the generate call running
        self._gens = 0                    # generate calls so far
        self.host_syncs = 0               # device->host blocking round-trips
        self.sample_fallbacks = 0         # non-finite-logit greedy fallbacks
        self.kv_peak = 0                  # max live KV tokens observed
        self._sample_key = jax.random.PRNGKey(seed)   # decode sampling stream

    # ------------------------------------------------------------------
    def _get_prefill(self, b: int, s: int):
        key = (b, s)
        if key not in self._prefill_fns:
            cfg, ctx = self.cfg, self.ctx

            def fn(params, cache, tokens, prompt_lens):
                return prefill(cfg, params, tokens, cache=cache,
                               prompt_lens=prompt_lens, ctx=ctx)

            self._prefill_fns[key] = _jit("prefill", fn, donate_argnums=(1,))
        return self._prefill_fns[key]

    def _get_decode(self, b: int):
        if b not in self._decode_fns:
            cfg, ctx = self.cfg, self.ctx

            def fn(params, cache, tokens, kv_lens):
                return decode_step(cfg, params, cache, tokens, kv_lens, ctx=ctx)

            self._decode_fns[b] = _jit("decode_step", fn, donate_argnums=(1,))
        return self._decode_fns[b]

    def _get_decode_chunk(self, b: int, steps: int, temperature: float = 0.0,
                          top_k: Optional[int] = None):
        """Fused multi-step decode: ``steps`` decode iterations as one
        ``lax.scan``, carrying (cache, tok, kv_lens, produced, per-slot
        keys) device-side.

        PER-SLOT PRNG keys (``[b, 2]``) ride the scan carry and each slot
        splits its OWN key once per step, so temperature/top-k sampling
        inside the fused chunk consumes per-request key streams that are
        invariant to both chunk size AND batch composition: chunk=1 and
        chunk=N produce identical samples, and a request gathered into a
        smaller bucket by elastic compaction keeps its key and therefore
        its stream (the keys are gathered alongside the cache in
        ``compact``).  ``temperature=0`` (the default) is greedy argmax
        and never touches the keys.

        Emits the per-step sampled token and active mask so the caller can
        reconstruct exact token streams / completion steps after the single
        end-of-chunk sync. ``kv_lens`` advances only for slots still below
        their target (except in 'uniform' cache-update mode, which requires
        lock-step positions), so early-exited slots stop moving their ring
        pointer; with the ragged decode-attention kernel they also stop
        paying padded KV compute.
        """
        key = (b, steps, float(temperature), top_k)
        if key not in self._chunk_fns:
            cfg, ctx = self.cfg, self.ctx
            max_seq = self.ecfg.max_seq
            advance_all = cfg.decode_cache_update == "uniform"

            def fn(params, cache, tok, kv_lens, produced, targets, keys):
                def body(carry, _):
                    cache, tok, kv_lens, produced, keys = carry
                    logits, cache = decode_step(cfg, params, cache, tok,
                                                kv_lens, ctx=ctx)
                    if cfg.decode_unroll_layers:
                        # unrolled decode returns a per-group split dict;
                        # restack so the scan carry keeps one structure
                        with jax.named_scope("kv_cache"):
                            cache = stack_group_cache(cache, cfg.num_groups)
                    with jax.named_scope("sample"):
                        if temperature > 0.0:
                            keys, subs = _split_slot_keys(keys)
                            nxt, bad = _sample_tokens(subs, logits,
                                                      temperature, top_k)
                        else:
                            nxt, bad = _guarded_argmax(logits)
                    active = produced < targets
                    produced = produced + active.astype(produced.dtype)
                    step = (jnp.ones_like(kv_lens) if advance_all
                            else active.astype(kv_lens.dtype))
                    kv_lens = jnp.minimum(kv_lens + step, max_seq - 1)
                    nbad = jnp.sum((bad & active).astype(jnp.int32))
                    return (cache, nxt, kv_lens, produced, keys), \
                        (nxt, active, nbad)

                carry, (toks, actives, nbads) = lax.scan(
                    body, (cache, tok, kv_lens, produced, keys), None,
                    length=steps)
                cache, tok, kv_lens, produced, keys = carry
                return (cache, tok, kv_lens, produced, keys, toks, actives,
                        jnp.sum(nbads))

            self._chunk_fns[key] = _jit("decode_chunk", fn,
                                         donate_argnums=(1,))
        return self._chunk_fns[key]

    def new_cache(self, batch_bucket: int):
        return init_cache(self.cfg, batch_bucket, self.ecfg.max_seq,
                          jnp.dtype(self.ecfg.cache_dtype))

    # ------------------------------------------------------------------
    def prefill_batch(self, prompts: List[np.ndarray]):
        """Pad to buckets, run prefill. Returns (cache, kv_lens, last_logits,
        batch_bucket, wall_seconds).  Its phase ``prefill`` covers the
        padding, the cache allocation, the dispatch and the wait for the
        logits; ``wall_seconds`` the dispatch and the wait."""
        phases = []
        with _phase("prefill", phases):
            b = _bucket(len(prompts), self.ecfg.min_bucket,
                        self.ecfg.max_batch)
            max_p = max(len(p) for p in prompts)
            s = min(_bucket(max_p, self.ecfg.prompt_bucket,
                            self.ecfg.max_seq), self.ecfg.max_seq)
            tokens = np.zeros((b, s), np.int32)
            lens = np.zeros((b,), np.int32)
            for i, p in enumerate(prompts):
                tokens[i, :len(p)] = p[:s]
                lens[i] = min(len(p), s)
            lens = np.maximum(lens, 1)
            cache = self.new_cache(b)
            fn = self._get_prefill(b, s)
            t0 = time.perf_counter()
            last, cache = fn(self.params, cache, jnp.asarray(tokens),
                             jnp.asarray(lens))
            last = jax.block_until_ready(last)
            dt = time.perf_counter() - t0
        self.host_syncs += 1
        self.step_log.append(
            {"kind": "prefill", "batch": b, "seq": s, "seconds": dt,
             "gen": self._gen, "phases": phases})
        return cache, jnp.asarray(lens), last, b, dt

    def decode_batch(self, cache, kv_lens, tokens):
        """One decode step for the whole bucket (one host sync). Returns
        (next_tokens, cache, wall_seconds). Reference path for the fused
        ``decode_chunk``."""
        b = int(tokens.shape[0])
        fn = self._get_decode(b)
        t0 = time.perf_counter()
        logits, cache = fn(self.params, cache, tokens, kv_lens)
        logits = jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        self.host_syncs += 1
        self.step_log.append(
            {"kind": "decode", "batch": b, "seq": int(jnp.max(kv_lens)),
             "seconds": dt})
        nxt, bad = _guarded_argmax(logits)
        self.sample_fallbacks += int(jnp.sum(bad))
        return nxt, cache, dt

    def decode_chunk(self, cache, kv_lens, tokens, produced, targets,
                     steps: int, temperature: float = 0.0,
                     top_k: Optional[int] = None, slot_keys=None):
        """Run ``steps`` fused decode iterations (one host sync). All array
        args/results are device-side; returns (cache, tok, kv_lens, produced,
        slot_keys, step_tokens [steps,B], step_active [steps,B],
        wall_seconds).  ``slot_keys`` ([B, 2], one PRNG key per slot) ride
        the scan carry and each slot splits its own key once per decode
        step — sampled streams are invariant to chunking AND to which
        bucket/slot a request occupies (pass the gathered keys after
        elastic compaction, and thread the returned keys into the next
        chunk, as ``generate`` does).  ``slot_keys=None`` with
        ``temperature>0`` falls back to fresh per-slot keys forked off
        the advancing engine stream (``Engine._sample_key``) — still
        well-distributed randomness per call, but only threading the keys
        gives cross-chunk stream invariance; greedy callers get dummy
        zeros (never consumed).  Its phase ``decode_chunk`` runs from the
        dispatch to where ``block_until_ready`` returns (``wall_seconds``),
        then ``readback`` brings the fallback count to the host."""
        b = int(tokens.shape[0])
        if slot_keys is None:
            if temperature > 0.0:
                self._sample_key, base = jax.random.split(self._sample_key)
                slot_keys = jax.vmap(
                    lambda i: jax.random.fold_in(base, i))(jnp.arange(b))
            else:
                slot_keys = jnp.zeros((b, 2), jnp.uint32)
        fn = self._get_decode_chunk(b, steps, temperature, top_k)
        phases = []
        with _phase("decode_chunk", phases):
            cache, tok, kv_lens, produced, slot_keys, toks, actives, nbad = \
                fn(self.params, cache, tokens, kv_lens, produced, targets,
                   slot_keys)
            tok = jax.block_until_ready(tok)
        _, dispatched, ready = phases[0]
        dt = (ready - dispatched) * 1e-9
        self.host_syncs += 1
        with _phase("readback", phases):
            self.sample_fallbacks += int(nbad)
        self.step_log.append(
            {"kind": "decode_chunk", "batch": b, "steps": steps,
             "seconds": dt, "gen": self._gen, "phases": phases})
        return cache, tok, kv_lens, produced, slot_keys, toks, actives, dt

    def compact(self, cache, kv_lens, tokens, keep_idx: np.ndarray,
                slot_keys=None):
        """Gather live slots into a smaller bucket (elastic batching's real
        speedup on TPU) — HOST reference path: the keep indices live on
        host and each cache leaf's gather is dispatched eagerly, so every
        compaction is one host-visible event (counted in ``host_syncs``
        and ``step_log``).  ``compact_fused`` is the device-resident twin
        the engine runs by default.  ``slot_keys`` are gathered alongside
        so each surviving request keeps its own sampling stream."""
        nb = _bucket(len(keep_idx), self.ecfg.min_bucket, self.ecfg.max_batch)
        idx = np.zeros((nb,), np.int32)
        idx[:len(keep_idx)] = keep_idx
        gidx = jnp.asarray(idx)
        cache = jax.tree.map(
            lambda leaf: leaf[:, gidx] if leaf.ndim >= 2 else leaf, cache)
        keys = None if slot_keys is None else slot_keys[gidx]
        self.host_syncs += 1
        self.step_log.append(
            {"kind": "compact", "impl": "host", "batch": nb, "syncs": 1,
             "gen": self._gen})
        return (cache, kv_lens[gidx], tokens[gidx], nb,
                int(len(keep_idx)), keys)

    def compact_fused(self, cache, kv_lens, tokens, produced, targets,
                      n_live: int, slot_keys=None):
        """Device-resident compaction (``EngineConfig.compact_impl=
        "fused"``): ONE jitted call around the scalar-prefetch Pallas
        gather kernel (:mod:`repro.kernels.compaction`).  The keep indices
        are derived ON DEVICE from the chunk's ``produced``/``targets``
        carry (live iff ``produced < targets`` — bit-identical to the host
        path's ``still`` selection), so nothing crosses the host boundary
        and ``host_syncs`` per compaction event is zero.  Only the bucket
        size ``nb`` is a host decision (static shapes), made from counts
        the chunk-boundary sync already paid for.  Bit-equal to
        :meth:`compact` — including the gathered per-slot PRNG keys, so
        sampled streams stay invariant to compaction (PR 4 guarantee)."""
        from repro.kernels.compaction import fused_compact
        nb = _bucket(n_live, self.ecfg.min_bucket, self.ecfg.max_batch)
        cache, kv_lens, tokens, keys, _ = fused_compact(
            cache, kv_lens, tokens, slot_keys, produced, targets, nb=nb)
        self.step_log.append(
            {"kind": "compact", "impl": "fused", "batch": nb, "syncs": 0,
             "gen": self._gen})
        return cache, kv_lens, tokens, nb, keys

    # ------------------------------------------------------------------
    def _track_kv(self, kv_lens, nlive: int) -> int:
        """Record live KV occupancy (sum of kv_lens over occupied slots —
        the REAL tokens pinned in the cache, not the worst case)."""
        live_kv = int(np.asarray(kv_lens)[:nlive].sum())
        if live_kv > self.kv_peak:
            self.kv_peak = live_kv
        return live_kv

    def kv_report(self) -> dict:
        """Realized KV occupancy vs the configured budget (the engine-layer
        twin of the simulator's ``memory`` block)."""
        cap = self.ecfg.kv_budget
        return {
            "kv_budget": cap,
            "kv_peak": int(self.kv_peak),
            "utilization": (self.kv_peak / cap) if cap else 0.0,
        }

    # ------------------------------------------------------------------
    def generate(self, prompts: List[np.ndarray], target_tokens: List[int],
                 elastic: bool = False, n_max: Optional[int] = None,
                 chunk: Optional[int] = None, return_tokens: bool = False,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None, seed: Optional[int] = None):
        """Run one batch to completion on the fused chunked-decode loop.

        Padded ('dynamic') mode decodes everyone for max(target) steps (the
        paper's padding semantics). Elastic mode lets finished replies exit
        and compacts buckets at chunk boundaries. ``chunk`` overrides
        ``EngineConfig.decode_chunk`` (chunk=1 == the per-step reference
        loop; larger chunks produce identical tokens with O(tokens/chunk)
        host syncs). ``temperature``/``top_k`` override the EngineConfig
        sampling settings (temperature 0 == greedy, the default).  Each
        request gets its OWN sampling key (``fold_in`` of the batch base
        key by request index) carried per-slot through the fused scan and
        gathered on compaction, so for a given ``seed`` sampled tokens are
        invariant to chunk size AND to elastic bucket compaction — padded
        and elastic runs emit identical streams per request. Returns dict
        with per-request completion times (seconds of engine wall time
        after batch start) and token counts.
        """
        entry = {"kind": "generate", "gen": self._gens,
                 "requests": len(prompts), "phases": []}
        self._gens += 1
        self._gen = entry["gen"]
        try:
            with _phase("generate", entry["phases"]):
                res = self._generate(entry, prompts, target_tokens, elastic,
                                     n_max, chunk, return_tokens,
                                     temperature, top_k, seed)
        finally:
            self._gen = None
        self.step_log.append(entry)
        return res

    def _generate(self, entry, prompts, target_tokens, elastic, n_max, chunk,
                  return_tokens, temperature, top_k, seed):
        """The body of ``generate``.  Besides ``prefill`` and
        ``decode_chunk`` (their own entries), it records its host phases in
        ``entry``: ``first_token`` (guard and argmax of the prefill logits,
        the fallback count, the first tokens to the host), then at every
        chunk boundary ``compact`` (the decision, and the compaction
        dispatch), ``upload`` (the slot counters), ``readback`` (actives,
        produced, tokens and ``kv_lens`` to the host) and ``bookkeeping``
        (token lists, completion times, ``_track_kv``)."""
        phases = entry["phases"]
        chunk = int(chunk if chunk is not None else self.ecfg.decode_chunk)
        assert chunk >= 1
        temperature = float(self.ecfg.temperature if temperature is None
                            else temperature)
        top_k = self.ecfg.top_k if top_k is None else top_k
        if seed is not None:
            self._sample_key = jax.random.PRNGKey(seed)
        targets = np.asarray(target_tokens)
        if n_max is not None:
            targets = np.minimum(targets, n_max)
        nreq = len(prompts)
        if self.ecfg.kv_budget is not None:
            worst = int(sum(min(len(p), self.ecfg.max_seq) + int(t)
                            for p, t in zip(prompts, targets)))
            if worst > self.ecfg.kv_budget:
                raise ValueError(
                    f"batch worst-case KV footprint {worst} exceeds "
                    f"kv_budget {self.ecfg.kv_budget}; cap the batch "
                    "upstream (memory-gated admission) or raise the budget")
        syncs0 = self.host_syncs
        cache, kv_lens, last, b, t_prefill = self.prefill_batch(prompts)
        entry["batch"] = b
        with _phase("first_token", phases):
            self._track_kv(kv_lens, nreq)
            slot_keys = None
            if temperature > 0.0:
                # one key per REQUEST (slot i holds request i right after
                # prefill); padding slots get keys too, but never emit tokens
                self._sample_key, base = jax.random.split(self._sample_key)
                slot_keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                    jnp.arange(b))
                slot_keys, subs = _split_slot_keys(slot_keys)
                tok, bad0 = _sample_tokens(subs, last, temperature, top_k)
            else:
                tok, bad0 = _guarded_argmax(last)
            self.sample_fallbacks += int(jnp.sum(bad0[:nreq]))
            live = np.arange(nreq)
            produced = np.ones(nreq, np.int64)    # first token from prefill
            done_at = np.full(nreq, np.nan)
            clock = t_prefill
            done_at[targets <= 1] = clock
            out_tokens = ([list(t) for t in
                           np.asarray(tok)[:nreq, None]] if return_tokens
                          else None)

        def slot_state(bucket, ids):
            prod = np.zeros(bucket, np.int64)
            targ = np.zeros(bucket, np.int64)
            prod[:len(ids)] = produced[ids]
            targ[:len(ids)] = targets[ids]
            return jnp.asarray(prod), jnp.asarray(targ)

        prod_d = targ_d = None      # device twins of the slot counters
        while True:
            with _phase("compact", phases):
                rem = targets[live] - produced[live]
                if elastic:
                    still = live[rem > 0]
                    if len(still) == 0:
                        break
                    if len(still) <= b // 2 and b > self.ecfg.min_bucket:
                        if self.ecfg.compact_impl == "fused":
                            # device-resident keep: the produced/targets
                            # carry from the last chunk (or a fresh upload
                            # right after prefill) selects the live slots
                            # in-jit — zero additional host syncs
                            if prod_d is None:
                                prod_d, targ_d = slot_state(b, live)
                            cache, kv_lens, tok, b, slot_keys = \
                                self.compact_fused(cache, kv_lens, tok,
                                                   prod_d, targ_d, len(still),
                                                   slot_keys)
                        else:
                            # host reference path: map global ids to slot ids
                            slot_of = {g: i for i, g in enumerate(live)}
                            keep = np.array([slot_of[g] for g in still],
                                            np.int32)
                            cache, kv_lens, tok, b, _, slot_keys = \
                                self.compact(cache, kv_lens, tok, keep,
                                             slot_keys)
                        live = still
                        rem = targets[live] - produced[live]
                        prod_d = targ_d = None   # stale after re-bucketing
                else:
                    if np.all(produced >= targets):
                        break
                # quantize tail chunks to powers of two: produced counts gate
                # every step, so shorter chunks never change tokens, and this
                # bounds the executable count at log2(chunk) per bucket
                rem_max = int(rem.max())
                steps = (chunk if rem_max >= chunk
                         else 1 << (rem_max.bit_length() - 1))
            with _phase("upload", phases):
                prod_d, targ_d = slot_state(b, live)   # also feeds compaction
            cache, tok, kv_lens, prod_d, slot_keys, toks, actives, dt = \
                self.decode_chunk(cache, kv_lens, tok, prod_d, targ_d, steps,
                                  temperature=temperature, top_k=top_k,
                                  slot_keys=slot_keys)
            with _phase("readback", phases):
                kv_np = np.asarray(kv_lens)
                actives_np = np.asarray(actives)            # [steps, b]
                prod_np = np.asarray(prod_d)
                toks_np = np.asarray(toks) if return_tokens else None
            with _phase("bookkeeping", phases):
                self._track_kv(kv_np, len(live))
                clock += dt
                produced[live] = prod_np[:len(live)]
                if return_tokens:
                    for s, g in enumerate(live):
                        out_tokens[g].extend(
                            toks_np[actives_np[:, s], s].tolist())
                newly = live[(produced[live] >= targets[live])
                             & np.isnan(done_at[live])]
                slot_of = {g: i for i, g in enumerate(live)}
                for g in newly:
                    hit = np.nonzero(actives_np[:, slot_of[g]])[0]
                    fin = int(hit[-1]) if hit.size else 0
                    # completion interpolated at that step's chunk fraction
                    done_at[g] = clock - dt + dt * (fin + 1) / steps
        done_at[np.isnan(done_at)] = clock
        if not elastic:
            # padded semantics (paper Eq 18): the whole batch is returned
            # when its longest member completes
            done_at[:] = clock
        res = {
            "completion_seconds": done_at,
            "batch_seconds": clock,
            "produced": produced,
            "prefill_seconds": t_prefill,
            "host_syncs": self.host_syncs - syncs0,
        }
        if return_tokens:
            res["tokens"] = out_tokens
        return res

    # ------------------------------------------------------------------
    def calibration_log(self) -> dict:
        """Measurements for fitting the paper's latency constants. Chunked
        decode entries are normalized to per-step seconds so the k3/k4 fit
        is chunk-size independent."""
        pre = [(e["batch"], e["seq"], e["seconds"])
               for e in self.step_log if e["kind"] == "prefill"]
        dec = [(e["batch"], e["seconds"])
               for e in self.step_log if e["kind"] == "decode"]
        dec += [(e["batch"], e["seconds"] / e["steps"])
                for e in self.step_log if e["kind"] == "decode_chunk"]
        return {"prefill": pre, "decode": dec}
