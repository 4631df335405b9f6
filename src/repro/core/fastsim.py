"""Compiled simulation kernels behind the batching-policy core (fast §V).

The NumPy event loops in :mod:`repro.core.simulate` stay the *reference
oracle*; this module re-derives them as compiled recursions so λ-grid
sweeps and policy search run 10-100x faster.  Dispatch is structural: every
:class:`repro.core.policies.BatchPolicy` names its kernel via
``policy.fast_kernel`` and the ``KERNELS`` table maps that name to an
implementation — policies without a compiled twin fall back to the oracle:

  * ``"mg1"``          — Lindley / workload recursion.  tau=None is the
    same closed-form cumulative-minimum as the reference; the impatience
    path becomes a ``lax.scan`` over the workload process (admit iff
    V < tau).
  * ``"batch_scan"``   — dynamic/elastic batch formation as a *per-request*
    scan with O(1) carry (start, count, token sum, token max); one scan
    step per request, ``vmap``-able across (λ, policy) lanes.
  * ``"fixed_cummax"`` — fully closed form: the free-time recursion
    F_k = max(F_{k-1}, A_k) + H_k telescopes to a running maximum.
  * ``"multibin"``     — per-bin FIFO queues + one shared server as a
    jitted ``lax.while_loop`` over batch events: per-bin head pointers,
    vmapped ``searchsorted`` for the waiting count, and a sparse-table
    (power-of-two window) range-max for the batch's padded token length.
    One iteration per BATCH, so high-load sweeps cost far fewer steps than
    requests.
  * ``"wait"``         — WAIT threshold admission (Dai et al. 2025) as a
    jitted ``lax.while_loop`` over batch events: the trigger is the k-th
    buffered arrival (or the head's timeout), membership via
    ``searchsorted``, padding via the shared sparse-table range max.
  * ``"srpt"``         — shortest-predicted-first batching as a
    ``lax.while_loop`` over a min-segment-tree keyed by (PREDICTED token,
    arrival) rank: 'leftmost rank with arrival <= start' is an O(log n)
    tree descent, so each batch pops its b_max shortest waiting requests
    in O(b_max log n).

Every kernel honors the predicted-vs-true column convention
(:mod:`repro.core.predictors`): membership/ordering inputs (SRPT's rank
order, multi-bin's bin assignment) come from ``Workload.predicted`` while
the service-law inputs (range-max tables, scan token carries) stay on the
true tokens.  ``sweep_noise(policy_factory, lam_grid, sigma_grid, ...)``
sweeps the (arrival rate, prediction noise) plane; SRPT cells are stacked
as lanes of ONE vmapped batch-event loop.

``sweep(policies, lam_grid, ...)`` is the uniform entry point: every
(λ, policy) combination whose policy rides the shared ``batch_scan``
kernel becomes a lane of ONE vmapped scan; the remaining policies dispatch
through ``KERNELS`` per cell.  ``simulate_policy_fast`` is the single-cell
twin.  Legacy entry points (``simulate_mg1_fast``, ...) wrap the same
kernels and keep their pre-refactor signatures.

The fleet layer (:mod:`repro.core.fleet`) rides the same kernels: every
kernel accepts a precomputed ``workload`` (a routed replica sub-stream,
padded to power-of-two shapes so nearby sizes share compiles), the
state-dependent routers' backlog recursion compiles to one ``lax.scan``
carrying the per-replica backlog vector (``backlog_route``), and
``simulate_fleet_fast`` is the fleet twin of the oracle's
``fleet.route_oracle``.

All absolute-time arithmetic runs under :func:`x64` —
simulated clocks reach ~1e6 seconds where float32 ULP (~0.25 s) would swamp
the waits being measured.  Scans run with ``unroll=8``, which amortizes
XLA's per-iteration loop overhead on CPU while keeping compile time
sub-second.

Every kernel samples its workload through the policy's ``sample_workload``
— the *same* rng call order as the reference oracle — so equal seeds give
trajectory-level (not just moment-level) agreement; ``tests/test_fastsim.py``
and ``tests/test_policies.py`` pin this down.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.distributions import TokenDistribution
from repro.core.latency_model import BatchLatencyModel, LatencyModel
from repro.core.policies import (
    BatchPolicy, DynamicPolicy, ElasticPolicy, FCFSPolicy, FixedPolicy,
    policy_from_spec, single_from_batch)
from repro.core.simulate import (
    _warm, simulate_fixed_batching, simulate_policy)

_UNROLL = 8          # scan body replication (amortizes loop overhead on CPU)
_NEG = -1e30
_NO_CAP = 1e18       # "b_max=None" as a finite cap (inf would poison carries)

KERNELS: Dict[str, Callable] = {}


def x64():
    """Scope in which the simulators' float64 clocks stay float64."""
    return jax.enable_x64(True)


def kernel(name: str):
    """Register a compiled kernel; ``BatchPolicy.fast_kernel`` names it."""
    def deco(fn):
        KERNELS[name] = fn
        return fn
    return deco


def simulate_policy_fast(policy: BatchPolicy, lam: float,
                         dist: Optional[TokenDistribution], lat,
                         num_requests: int = 200_000, seed: int = 0,
                         workload=None, fault_trace=None,
                         traffic=None, sessions=None,
                         prefix_discount: float = 0.0, memory=None) -> dict:
    """Fast twin of :func:`repro.core.simulate.simulate_policy`: dispatch to
    the policy's compiled kernel, or fall back to the oracle when the
    policy has none (``fast_kernel=None``).

    ``workload`` overrides the policy's own sampling, exactly like the
    oracle twin's parameter — the fleet layer routes one stream and runs
    each replica's sub-workload through the unchanged kernels.  Kernels
    pad provided workloads to power-of-two lengths (sliced off the
    outputs) so replica sub-streams of nearby sizes share one compile.

    ``fault_trace`` injects failure epochs exactly like the oracle twin:
    the transform arithmetic is the SAME host-side code
    (``simulate._with_fault_trace``), only the inner fault-free run is
    the compiled kernel — so oracle and fastsim see bit-identical
    epochs and trajectory-equal faulty waits.

    ``traffic`` modulates the arrival rate exactly like the oracle
    twin's parameter: the HOST-side time-rescaling warp runs before the
    kernel sees the workload, so both layers simulate the identical
    modulated arrival instants; a null model never warps (the kernel
    keeps its internal sampling path, bit-equal to PR 5/6/7).

    ``sessions`` re-enters completed turns exactly like the oracle
    twin's parameter: the SAME feedback fixed point
    (:func:`repro.core.sessions.simulate_policy_sessions`) runs with the
    compiled kernels as the inner pass, so oracle ≡ fastsim under
    feedback is structural; a null model takes this exact code path.

    ``memory`` switches batch service to the prefill/decode tandem with
    KV-budget admission, exactly like the oracle twin's parameter: the
    dynamic (``batch_scan``, non-elastic) lane gets a compiled
    batch-event while_loop (``_tandem_loop``, bit-equal trajectories);
    elastic and the batch-event policies fall back to the tandem oracle
    the way ``fast_kernel=None`` policies always have.  A null budget
    takes this exact code path."""
    mem = None
    if memory is not None:
        from repro.core.memory import check_policy_supports_memory, \
            memory_from_spec
        mem = memory_from_spec(memory)
        if mem.is_null:
            mem = None
        else:
            check_policy_supports_memory(policy)
    if sessions is not None:
        from repro.core.sessions import (session_from_spec,
                                         simulate_policy_sessions)
        model = session_from_spec(sessions)
        if not model.is_null:
            if mem is not None:
                raise ValueError(
                    "sessions= x memory= is not supported: turn re-entry "
                    "holds KV across think times (a different occupancy "
                    "law); run the tandem on the expanded per-turn stream "
                    "instead")
            if workload is not None:
                raise ValueError("sessions= expands its own workload; "
                                 "pass lam/num_requests/seed instead of "
                                 "workload=")
            return simulate_policy_sessions(
                policy, lam, dist, lat, num_requests, seed, model,
                fault_trace=fault_trace, traffic=traffic,
                prefix_discount=prefix_discount, fast=True)
    if policy.uses_single_latency and isinstance(lat, BatchLatencyModel):
        lat = single_from_batch(lat)
    if traffic is not None:
        from repro.core.traffic import traffic_from_spec, warp_workload
        tm = traffic_from_spec(traffic)
        if not tm.is_null:
            wl = workload if workload is not None else \
                policy.sample_workload(lam, dist, num_requests, seed)
            workload = warp_workload(wl, tm, seed)
    if mem is not None:
        lane = policy.scan_lane()
        if lane is None or lane[0]:
            # elastic (per-request release times) and the batch-event
            # policies (non-contiguous membership) have no compiled
            # tandem twin yet: oracle fallback, traffic already applied
            return simulate_policy(policy, lam, dist, lat,
                                   num_requests=num_requests, seed=seed,
                                   workload=workload,
                                   fault_trace=fault_trace, memory=mem)
        if fault_trace is not None and not fault_trace.empty:
            from repro.core.simulate import _with_fault_trace
            wl = workload if workload is not None else \
                policy.sample_workload(lam, dist, num_requests, seed)
            return _with_fault_trace(
                lambda op_wl: _tandem_dynamic_kernel(
                    policy, lam, dist, lat, num_requests, seed, mem,
                    workload=op_wl),
                wl, fault_trace)
        return _tandem_dynamic_kernel(policy, lam, dist, lat, num_requests,
                                      seed, mem, workload=workload)
    if policy.fast_kernel is None:
        return simulate_policy(policy, lam, dist, lat,
                               num_requests=num_requests, seed=seed,
                               workload=workload, fault_trace=fault_trace)
    if fault_trace is not None and not fault_trace.empty:
        from repro.core.simulate import _with_fault_trace
        wl = workload if workload is not None else \
            policy.sample_workload(lam, dist, num_requests, seed)
        return _with_fault_trace(
            lambda op_wl: KERNELS[policy.fast_kernel](
                policy, lam, dist, lat, num_requests, seed, workload=op_wl),
            wl, fault_trace)
    return KERNELS[policy.fast_kernel](policy, lam, dist, lat,
                                       num_requests, seed, workload=workload)


# ----------------------------------------------------------------------------
# M/G/1 with deterministic impatience tau (workload recursion as a scan)
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _impatience_scan():
    def run(inter, service, tau):
        def step(v, xs):
            a, s = xs
            v = jnp.maximum(0.0, v - a)
            lost = v >= tau
            wait = jnp.where(lost, tau, v)
            v = jnp.where(lost, v, v + s)
            return v, (wait, lost)

        _, (waits, lost) = lax.scan(step, jnp.float64(0.0),
                                    (inter, service), unroll=_UNROLL)
        return waits, lost

    return jax.jit(run)


def _pad_pow2_1d(arr: np.ndarray, fill: float) -> np.ndarray:
    """Pad one row to the next power-of-two length (>= 2) so provided
    workloads of nearby sizes (fleet replica sub-streams) share one
    compiled shape; the padded tail is inert (arrivals at +inf never
    join/form batches) and is sliced off every output.  Thin single-row
    wrapper over the batch-event kernels' shared ``_pow2_rows`` layout
    helper."""
    return _pow2_rows([np.asarray(arr, np.float64)], fill)[0][0]


@kernel("mg1")
def _mg1_kernel(policy, lam, dist, lat, num_requests, seed,
                workload=None) -> dict:
    if policy.tau is None:
        # the reference tau=None path is already a closed-form vectorized
        # Lindley recursion — it IS the fast path.
        return simulate_policy(policy, lam, dist, lat,
                               num_requests=num_requests, seed=seed,
                               workload=workload)
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    n = len(wl.tokens)
    service = np.asarray(lat.service_time(wl.tokens), np.float64)
    # fleet sub-streams pad to power-of-two so replica sizes share one
    # compile; padded tail gaps are infinite, so wait=0, lost=False
    inter = _pad_pow2_1d(wl.inter, np.inf) if workload is not None \
        else np.asarray(wl.inter, np.float64)
    service = _pad_pow2_1d(service, 0.0) if workload is not None \
        else service
    with x64():
        waits, lost = _impatience_scan()(
            jnp.asarray(inter, jnp.float64),
            jnp.asarray(service, jnp.float64),
            jnp.float64(policy.tau))
        waits = np.asarray(waits)[:n]
        lost = np.asarray(lost)[:n]
    waits_w, lost_w = _warm(waits), _warm(lost)
    served = waits_w[~lost_w]
    return {
        "mean_wait": float(waits_w.mean()),
        "mean_wait_served": float(served.mean()) if served.size else 0.0,
        "loss_frac": float(lost_w.mean()),
        "p95_wait": float(np.percentile(waits_w, 95)),
        "waits": waits_w,
    }


def simulate_mg1_fast(lam: float, dist: TokenDistribution, lat: LatencyModel,
                      n_max: Optional[int] = None, tau: Optional[float] = None,
                      num_requests: int = 200_000, seed: int = 0) -> dict:
    """Drop-in fast twin of :func:`repro.core.simulate.simulate_mg1`."""
    return simulate_policy_fast(FCFSPolicy(n_max=n_max, tau=tau), lam, dist,
                                lat, num_requests=num_requests, seed=seed)


# ----------------------------------------------------------------------------
# Dynamic / elastic batching (per-request scan with O(1) forming-batch carry)
# ----------------------------------------------------------------------------

def _batching_core(arr, tok, k1, k2, k3, k4, elastic, b_max):
    """Per-request recursion. Carry = (start, count, sum, max) of the batch
    currently being formed; closing a batch advances the server-free time by
    its Eq-18 (padded) or Eq-26 (elastic) duration. Returns (per-request
    batch start times, per-request batch-close flags)."""

    def step(c, xs):
        a, t = xs
        t_cur, cnt, ssum, smax = c
        t_free = t_cur + jnp.where(
            elastic, k1 * cnt + k2 + k3 * ssum + k4 * smax,
            k1 * cnt + k2 + (k3 * cnt + k4) * smax)
        joins = (a <= t_cur) & (cnt < b_max)
        start_new = jnp.where(a >= t_free, a, t_free)
        t_cur = jnp.where(joins, t_cur, start_new)
        cnt = jnp.where(joins, cnt + 1.0, 1.0)
        ssum = jnp.where(joins, ssum + t, t)
        smax = jnp.where(joins, jnp.maximum(smax, t), t)
        return (t_cur, cnt, ssum, smax), (t_cur, ~joins)

    # cnt0 > b_max forces request 0 to "close" the empty batch; that bogus
    # close exactly offsets the last real batch, which never closes — so
    # sum(closed) equals the reference batch count.
    c0 = (jnp.float64(_NEG), b_max + 1.0, jnp.float64(0.0), jnp.float64(0.0))
    _, (starts, closed) = lax.scan(step, c0, (arr, tok), unroll=_UNROLL)
    return starts, closed


@functools.lru_cache(maxsize=None)
def _batching_scan(vmapped: bool):
    if vmapped:
        return jax.jit(jax.vmap(_batching_core,
                                in_axes=(0, 0, None, None, None, None, 0, 0)))
    return jax.jit(_batching_core)


def _batch_lane_stats(starts, closed, arrivals):
    starts = np.asarray(starts)
    nb = int(np.asarray(closed).sum())
    waits = starts - arrivals
    w = _warm(waits)
    return {
        "mean_wait": float(w.mean()),
        "p95_wait": float(np.percentile(w, 95)),
        "mean_batch": float(len(starts) / max(nb, 1)),
        "waits": w,
    }


@kernel("batch_scan")
def _batch_scan_kernel(policy, lam, dist, lat, num_requests, seed,
                       workload=None) -> dict:
    elastic, b_max = policy.scan_lane()
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    n = len(wl.arrivals)
    # padded arrivals at +inf never join the forming batch; their bogus
    # singleton "batches" live past index n and are sliced off
    arr_p = _pad_pow2_1d(wl.arrivals, np.inf) if workload is not None \
        else wl.arrivals
    tok_p = _pad_pow2_1d(wl.tokens, 0.0) if workload is not None \
        else wl.tokens
    with x64():
        starts, closed = _batching_scan(False)(
            jnp.asarray(arr_p, jnp.float64),
            jnp.asarray(tok_p, jnp.float64),
            jnp.float64(lat.k1), jnp.float64(lat.k2),
            jnp.float64(lat.k3), jnp.float64(lat.k4),
            jnp.asarray(bool(elastic)),
            jnp.float64(b_max if b_max is not None else _NO_CAP))
        return _batch_lane_stats(np.asarray(starts)[:n],
                                 np.asarray(closed)[:n], wl.arrivals)


def simulate_dynamic_batching_fast(lam: float, dist: TokenDistribution,
                                   lat: BatchLatencyModel,
                                   b_max: Optional[int] = None,
                                   elastic: bool = False,
                                   n_max: Optional[int] = None,
                                   num_requests: int = 200_000,
                                   seed: int = 0) -> dict:
    """Drop-in fast twin of simulate_dynamic_batching (same seeds =>
    trajectory-identical batch boundaries up to float rounding)."""
    cls = ElasticPolicy if elastic else DynamicPolicy
    return simulate_policy_fast(cls(n_max=n_max, b_max=b_max), lam, dist,
                                lat, num_requests=num_requests, seed=seed)


# ----------------------------------------------------------------------------
# Fixed batching (closed form — the recursion telescopes to a cummax)
# ----------------------------------------------------------------------------

@kernel("fixed_cummax")
def _fixed_kernel(policy, lam, dist, lat, num_requests, seed,
                  workload=None) -> dict:
    if "batch_time" in vars(policy):
        # an instance-level batch_time override cannot be vectorized:
        # delegate to the reference loop (same trajectory by construction)
        return simulate_policy(policy, lam, dist, lat,
                               num_requests=num_requests, seed=seed,
                               workload=workload)
    b = policy.b
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    n_served = (len(wl.arrivals) // b) * b    # provided workloads may be
    arrivals = wl.arrivals[:n_served]         # ragged (fleet sub-streams)
    tokens = wl.tokens[:n_served]
    arr_kb = arrivals.reshape(-1, b)
    h = np.asarray(lat.batch_time(b, tokens.reshape(-1, b).max(axis=1)),
                   np.float64)
    c = np.cumsum(h)
    # F_k = max(F_{k-1}, A_k) + H_k  =>  F_k - C_k = cummax_j(A_j - C_{j-1})
    free = np.maximum.accumulate(arr_kb[:, -1] - (c - h)) + c
    starts = free - h
    waits = (starts[:, None] - arr_kb).reshape(-1)
    w = _warm(waits)
    return {
        "mean_wait": float(w.mean()),
        "p95_wait": float(np.percentile(w, 95)),
        "waits": w,
    }


def simulate_fixed_batching_fast(lam: float, b: int,
                                 dist: Optional[TokenDistribution],
                                 lat: Optional[BatchLatencyModel] = None,
                                 batch_time: Optional[Callable] = None,
                                 num_requests: int = 200_000,
                                 seed: int = 0) -> dict:
    """Drop-in fast twin of simulate_fixed_batching. With an arbitrary
    ``batch_time`` callable the per-batch times cannot be vectorized, so that
    case delegates to the reference loop."""
    if batch_time is not None:
        return simulate_fixed_batching(lam, b, dist, lat,
                                       batch_time=batch_time,
                                       num_requests=num_requests, seed=seed)
    assert lat is not None
    return simulate_policy_fast(FixedPolicy(b=b), lam, dist, lat,
                                num_requests=num_requests, seed=seed)


# ----------------------------------------------------------------------------
# Batch-event loops (multi-bin / WAIT / SRPT): one while_loop step per BATCH
# ----------------------------------------------------------------------------

def _pow2_rows(values, pad):
    """Stack ragged rows into a (B, L) array with L the next power of two,
    padded with ``pad`` (the layout the batch-event kernels index)."""
    lens = np.array([len(v) for v in values], np.int32)
    L = max(1 << int(lens.max() - 1).bit_length(), 2)
    out = np.full((len(values), L), pad)
    for j, v in enumerate(values):
        out[j, :lens[j]] = v
    return out, lens, L


def _sparse_max_table(rows: np.ndarray) -> np.ndarray:
    """Sparse table for O(1) range max: table[k, j, i] = max rows[j, i:i+2^k].
    Rows must already be power-of-two length (``_pow2_rows``)."""
    B, L = rows.shape
    K = int(np.log2(L)) + 1
    table = np.empty((K, B, L))
    table[0] = rows
    for k in range(1, K):
        s = 1 << (k - 1)
        table[k, :, :L - s] = np.maximum(table[k - 1, :, :L - s],
                                         table[k - 1, :, s:])
        table[k, :, L - s:] = table[k - 1, :, L - s:]
    return table


# ----------------------------------------------------------------------------
# Multi-bin batching (jitted while_loop over batch events)
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _multibin_loop(B: int, L: int, K: int, M: int):
    """One iteration per BATCH: pick the non-empty bin with the earliest
    head arrival, count its waiting requests (vmapped searchsorted), pad
    the batch to its token range-max (sparse table), advance the server."""

    def run(arr_b, table, lens, k1, k2, k3, k4, b_max):
        def cond(c):
            return jnp.any(c[1] < lens)

        def row_search_right(j, v):
            # first index i in (sorted, inf-padded) row j with arr_b[j,i] > v
            def step(_, lohi):
                lo, hi = lohi
                mid = (lo + hi) // 2
                live = lo < hi
                right = live & (arr_b[j, mid] <= v)
                return (jnp.where(right, mid + 1, lo),
                        jnp.where(live & ~right, mid, hi))
            lo, _ = lax.fori_loop(0, L.bit_length() + 1, step,
                                  (jnp.int32(0), jnp.int32(L)))
            return lo

        def body(c):
            t_free, heads, nb, o_bin, o_lo, o_hi, o_start = c
            a_head = arr_b[jnp.arange(B), jnp.minimum(heads, L - 1)]
            a_head = jnp.where(heads < lens, a_head, jnp.inf)
            j = jnp.argmin(a_head).astype(jnp.int32)
            a = a_head[j]
            lo = heads[j]
            idle = a >= t_free
            hi_busy = jnp.minimum(row_search_right(j, t_free),
                                  jnp.minimum(lo + b_max, lens[j]))
            hi = jnp.where(idle, lo + 1, hi_busy)
            start = jnp.where(idle, a, t_free)
            m = hi - lo
            k = jnp.floor(jnp.log2(m.astype(jnp.float64))).astype(jnp.int32)
            p = jnp.left_shift(jnp.int32(1), k)
            rm = jnp.maximum(table[k, j, lo], table[k, j, hi - p])
            bf = m.astype(jnp.float64)
            h = k1 * bf + k2 + (k3 * bf + k4) * rm
            return (start + h, heads.at[j].set(hi), nb + 1,
                    o_bin.at[nb].set(j), o_lo.at[nb].set(lo),
                    o_hi.at[nb].set(hi), o_start.at[nb].set(start))

        init = (jnp.float64(0.0), jnp.zeros(B, jnp.int32), jnp.int32(0),
                jnp.zeros(M, jnp.int32), jnp.zeros(M, jnp.int32),
                jnp.zeros(M, jnp.int32), jnp.zeros(M, jnp.float64))
        t_free, heads, nb, o_bin, o_lo, o_hi, o_start = lax.while_loop(
            cond, body, init)
        return nb, o_bin, o_lo, o_hi, o_start

    return jax.jit(run)


@kernel("multibin")
def _multibin_kernel(policy, lam, dist, lat, num_requests, seed,
                     workload=None) -> dict:
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    arr, tok = wl.arrivals, wl.tokens
    n = len(arr)
    # bin ROUTING keys off the predicted column; the range-max table below
    # (the padded service law) stays on the true tokens
    bins = policy.bin_of(wl.predicted_or_true, dist)
    B = policy.num_bins
    members = [np.nonzero(bins == j)[0] for j in range(B)]
    arr_b, lens, L = _pow2_rows([arr[m] for m in members], np.inf)
    tok_b, _, _ = _pow2_rows([tok[m] for m in members], -np.inf)
    table = _sparse_max_table(tok_b)     # range max for the batch padding
    K = table.shape[0]
    b_max = np.int32(policy.b_max if policy.b_max is not None else L)
    # output buffers padded to a power of two: one compile serves every
    # nearby workload size (fleet replica sub-streams)
    M = max(1 << max(n - 1, 1).bit_length(), 2)
    with x64():
        nb, o_bin, o_lo, o_hi, o_start = _multibin_loop(B, L, K, M)(
            jnp.asarray(arr_b, jnp.float64), jnp.asarray(table, jnp.float64),
            jnp.asarray(lens, jnp.int32),
            jnp.float64(lat.k1), jnp.float64(lat.k2),
            jnp.float64(lat.k3), jnp.float64(lat.k4), b_max)
        nb = int(nb)
        o_bin = np.asarray(o_bin)[:nb]
        o_lo = np.asarray(o_lo)[:nb]
        o_hi = np.asarray(o_hi)[:nb]
        o_start = np.asarray(o_start)[:nb]
    starts_req = np.empty(n)
    for j, mem in enumerate(members):
        sel = o_bin == j
        starts_req[mem] = np.repeat(o_start[sel], (o_hi - o_lo)[sel])
    waits = starts_req - arr
    w = _warm(waits)
    return {
        "mean_wait": float(w.mean()),
        "p95_wait": float(np.percentile(w, 95)),
        "mean_batch": float(n / max(nb, 1)),
        "waits": w,
    }


# ----------------------------------------------------------------------------
# WAIT threshold admission (jitted while_loop over batch events)
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _wait_loop(L: int, K: int, M: int):
    """One iteration per WAIT batch: the trigger is the k-th buffered
    arrival or the head's timeout expiry (whichever first); the batch is
    everything arrived by start (cap b_max), padded to its token range-max
    (sparse table)."""

    def run(arr, table, n, k, timeout, b_max, k1, k2, k3, k4):
        def cond(c):
            return c[1] < n

        def body(c):
            t_free, head, nb, o_lo, o_hi, o_start = c
            kth = arr[jnp.minimum(head + k - 1, n - 1)]
            trigger = jnp.minimum(kth, arr[head] + timeout)
            start = jnp.maximum(t_free, trigger)
            hi = jnp.searchsorted(arr, start, side="right").astype(jnp.int32)
            hi = jnp.minimum(jnp.minimum(hi, head + b_max), n)
            m = hi - head
            kk = jnp.floor(jnp.log2(m.astype(jnp.float64))).astype(jnp.int32)
            p = jnp.left_shift(jnp.int32(1), kk)
            rm = jnp.maximum(table[kk, 0, head], table[kk, 0, hi - p])
            bf = m.astype(jnp.float64)
            h = k1 * bf + k2 + (k3 * bf + k4) * rm
            return (start + h, hi, nb + 1, o_lo.at[nb].set(head),
                    o_hi.at[nb].set(hi), o_start.at[nb].set(start))

        init = (jnp.float64(0.0), jnp.int32(0), jnp.int32(0),
                jnp.zeros(M, jnp.int32), jnp.zeros(M, jnp.int32),
                jnp.zeros(M, jnp.float64))
        t_free, head, nb, o_lo, o_hi, o_start = lax.while_loop(
            cond, body, init)
        return nb, o_lo, o_hi, o_start

    return jax.jit(run)


@kernel("wait")
def _wait_kernel(policy, lam, dist, lat, num_requests, seed,
                 workload=None) -> dict:
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    arr, tok = wl.arrivals, wl.tokens
    n = len(arr)
    arr_p, _, L = _pow2_rows([arr], np.inf)
    tok_p, _, _ = _pow2_rows([tok], -np.inf)
    table = _sparse_max_table(tok_p)
    with x64():
        nb, o_lo, o_hi, o_start = _wait_loop(L, table.shape[0], L)(
            jnp.asarray(arr_p[0], jnp.float64),
            jnp.asarray(table, jnp.float64), jnp.int32(n),
            jnp.int32(policy.k),
            jnp.float64(policy.timeout if policy.timeout is not None
                        else np.inf),
            jnp.int32(policy.b_max if policy.b_max is not None else L),
            jnp.float64(lat.k1), jnp.float64(lat.k2),
            jnp.float64(lat.k3), jnp.float64(lat.k4))
        nb = int(nb)
        o_lo = np.asarray(o_lo)[:nb]
        o_hi = np.asarray(o_hi)[:nb]
        o_start = np.asarray(o_start)[:nb]
    waits = np.repeat(o_start, o_hi - o_lo) - arr     # batches are contiguous
    w = _warm(waits)
    return {
        "mean_wait": float(w.mean()),
        "p95_wait": float(np.percentile(w, 95)),
        "mean_batch": float(n / max(nb, 1)),
        "waits": w,
    }


# ----------------------------------------------------------------------------
# SRPT shortest-predicted-first (jitted while_loop over a min-segment-tree)
# ----------------------------------------------------------------------------

def _srpt_core(L: int):
    """One iteration per SRPT batch.  Requests are laid out in rank order
    (PREDICTED token count, then arrival); a min-segment-tree over their
    arrival times (served leaves := +inf) answers 'leftmost rank with
    arrival <= start' in O(log L), which IS the shortest-predicted waiting
    request.  Each batch pops up to b_max such leaves (1 when the server
    was idle and the next arrival starts alone, exactly like dynamic
    batching).  ``tok_rank`` holds the TRUE token counts in rank order —
    the padded service law never sees predictions."""
    LOG = L.bit_length() - 1     # tree depth: root 1, leaves [L, 2L)

    def run(tree, tok_rank, n, b_max, k1, k2, k3, k4):
        def cond(c):
            return c[4] < n

        def body(c):
            t_free, tree, starts, nb, served = c
            root = tree[1]
            idle = root > t_free
            start = jnp.where(idle, root, t_free)
            cap = jnp.where(idle, jnp.int32(1), b_max)

            def pop_cond(s):
                tr, npop, _, _ = s
                return (npop < cap) & (tr[1] <= start)

            def pop_body(s):
                tr, npop, mx, st = s

                def down(_, i):
                    return jnp.where(tr[2 * i] <= start, 2 * i, 2 * i + 1)

                i = lax.fori_loop(0, LOG, down, jnp.int32(1))
                st = st.at[i - L].set(start)
                mx = jnp.maximum(mx, tok_rank[i - L])
                tr = tr.at[i].set(jnp.inf)

                def up(_, iv):
                    i2, tr2 = iv
                    i2 = i2 // 2
                    return i2, tr2.at[i2].set(
                        jnp.minimum(tr2[2 * i2], tr2[2 * i2 + 1]))

                _, tr = lax.fori_loop(0, LOG, up, (i, tr))
                return tr, npop + 1, mx, st

            tree, m, mx, starts = lax.while_loop(
                pop_cond, pop_body,
                (tree, jnp.int32(0), jnp.float64(-jnp.inf), starts))
            bf = m.astype(jnp.float64)
            h = k1 * bf + k2 + (k3 * bf + k4) * mx
            return (start + h, tree, starts, nb + 1, served + m)

        init = (jnp.float64(0.0), tree, jnp.zeros(L, jnp.float64),
                jnp.int32(0), jnp.int32(0))
        _, _, starts, nb, _ = lax.while_loop(cond, body, init)
        return starts, nb

    return run


@functools.lru_cache(maxsize=None)
def _srpt_loop(L: int):
    return jax.jit(_srpt_core(L))


@functools.lru_cache(maxsize=None)
def _srpt_loop_vmapped(L: int):
    """(lane, lane, shared...) vmap of the SRPT batch-event loop: every
    (λ, σ) cell of ``sweep_noise`` becomes one lane of a single jitted
    while_loop (lanes run until the slowest finishes, with masked bodies)."""
    return jax.jit(jax.vmap(
        _srpt_core(L), in_axes=(0, 0, None, None, None, None, None, None)))


def _srpt_rank_arrays(arr: np.ndarray, tok: np.ndarray, key: np.ndarray):
    """Host prep shared by the single-cell kernel and ``sweep_noise``:
    rank order by (predicted ``key``, arrival), power-of-two padded
    arrival/true-token rows, and the min-segment-tree over arrivals."""
    order = np.argsort(key, kind="stable")     # rank = (predicted, arrival)
    arr_rank, _, L = _pow2_rows([arr[order]], np.inf)
    tok_rank, _, _ = _pow2_rows([tok[order]], -np.inf)
    tree = np.full(2 * L, np.inf)
    tree[L:] = arr_rank[0]
    lvl, size = arr_rank[0], L
    while size > 1:
        lvl = np.minimum(lvl[0::2], lvl[1::2])
        size //= 2
        tree[size:2 * size] = lvl
    return order, tree, tok_rank[0], L


def _srpt_stats(starts_rank, nb, order, arr):
    n = len(arr)
    starts_req = np.empty(n)
    starts_req[order] = np.asarray(starts_rank)[:n]
    waits = starts_req - arr
    w = _warm(waits)
    return {
        "mean_wait": float(w.mean()),
        "p95_wait": float(np.percentile(w, 95)),
        "mean_batch": float(n / max(int(nb), 1)),
        "waits": w,
    }


@kernel("srpt")
def _srpt_kernel(policy, lam, dist, lat, num_requests, seed,
                 workload=None) -> dict:
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    arr, tok = wl.arrivals, wl.tokens
    n = len(arr)
    order, tree, tok_rank, L = _srpt_rank_arrays(arr, tok,
                                                 wl.predicted_or_true)
    with x64():
        starts_rank, nb = _srpt_loop(L)(
            jnp.asarray(tree, jnp.float64),
            jnp.asarray(tok_rank, jnp.float64), jnp.int32(n),
            jnp.int32(policy.b_max if policy.b_max is not None else L),
            jnp.float64(lat.k1), jnp.float64(lat.k2),
            jnp.float64(lat.k3), jnp.float64(lat.k4))
        return _srpt_stats(starts_rank, nb, order, arr)


# ----------------------------------------------------------------------------
# Prefill/decode tandem with a KV-memory budget (repro.core.memory)
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _tandem_loop(L: int, K: int, M: int):
    """One iteration per BATCH of the memory-gated tandem, DYNAMIC
    formation only (contiguous membership + whole-batch release at decode
    end => one release-ledger event per batch, O(1) carry growth).  The
    admission arithmetic mirrors :func:`repro.core.memory.tandem_oracle`
    operation for operation — 'right'-sided release search, delayed start
    via a 'left' search over the release prefix sums, longest admissible
    prefix via a 'right' search over the footprint prefix sums — so the
    event ORDER (membership, deferrals, blocked counts) matches the
    oracle exactly and the clocks agree to float rounding (XLA may fuse
    multiply-adds the NumPy loop keeps separate)."""

    def run(arr, table, fp_cum, n, b_max, cap, k1, k2, k3, k4):
        def cond(c):
            return c[0] < n

        def body(c):
            (head, t_pf, t_dec, nb, blocked, blocked_t, deferred,
             rel_t, rel_cum, o_start, o_end, o_dend) = c
            a = arr[head]
            idle = a >= t_pf
            start0 = jnp.where(idle, a, t_pf)
            hi_busy = jnp.searchsorted(arr, t_pf,
                                       side="right").astype(jnp.int32)
            hi = jnp.where(idle, head + 1,
                           jnp.minimum(hi_busy, head + b_max))
            # -- releases banked by the candidate start ----------------
            r = jnp.searchsorted(rel_t, start0, side="right")
            target = cap + rel_cum[r]
            first = fp_cum[head + 1]
            fits = first <= target
            # delayed start: earliest release instant freeing `need`
            need = first - cap
            rs = jnp.searchsorted(rel_cum, need, side="left")
            start = jnp.where(fits, start0,
                              rel_t[jnp.maximum(rs - 1, 0)])
            r2 = jnp.searchsorted(rel_t, start, side="right")
            target = jnp.where(fits, target, cap + rel_cum[r2])
            blocked = blocked + jnp.where(fits, 0, 1)
            blocked_t = blocked_t + jnp.where(fits, 0.0, start - start0)
            # -- longest admissible prefix over the footprint cumsum ---
            e = jnp.searchsorted(fp_cum, target,
                                 side="right").astype(jnp.int32) - 1
            e = jnp.maximum(jnp.minimum(hi, e), head + 1)
            deferred = deferred + (hi - e)
            # -- tandem service ----------------------------------------
            m = e - head
            kk = jnp.floor(jnp.log2(m.astype(jnp.float64))).astype(jnp.int32)
            p = jnp.left_shift(jnp.int32(1), kk)
            rm = jnp.maximum(table[kk, 0, head], table[kk, 0, e - p])
            bf = m.astype(jnp.float64)
            pf = k1 * bf + k2
            h = k1 * bf + k2 + (k3 * bf + k4) * rm
            p_end = start + pf
            d_start = jnp.maximum(p_end, t_dec)
            d_end = d_start + (h - pf)    # same op order as stage_split
            return (e, p_end, d_end, nb + 1, blocked, blocked_t, deferred,
                    rel_t.at[nb].set(d_end),
                    rel_cum.at[nb + 1].set(fp_cum[e]),
                    o_start.at[nb].set(start), o_end.at[nb].set(e),
                    o_dend.at[nb].set(d_end))

        init = (jnp.int32(0), jnp.float64(0.0), jnp.float64(0.0),
                jnp.int32(0), jnp.int32(0), jnp.float64(0.0), jnp.int32(0),
                jnp.full(M, jnp.inf), jnp.full(M + 1, jnp.inf).at[0].set(0.0),
                jnp.zeros(M, jnp.float64), jnp.zeros(M, jnp.int32),
                jnp.zeros(M, jnp.float64))
        (head, t_pf, t_dec, nb, blocked, blocked_t, deferred,
         rel_t, rel_cum, o_start, o_end, o_dend) = lax.while_loop(
            cond, body, init)
        return nb, blocked, blocked_t, deferred, o_start, o_end, o_dend

    return jax.jit(run)


def _tandem_dynamic_kernel(policy, lam, dist, lat, num_requests, seed,
                           budget, workload=None) -> dict:
    """Compiled twin of the tandem oracle for the ``batch_scan`` lane
    (dynamic formation, padded decode).  Elastic and the batch-event
    policies (multibin/wait/srpt/fixed) release KV per REQUEST or form
    non-contiguous batches — their memory runs fall back to the oracle,
    like ``fast_kernel=None`` policies do."""
    from repro.core.memory import occupancy_stats
    wl = workload if workload is not None else \
        policy.sample_workload(lam, dist, num_requests, seed)
    arr, tok = wl.arrivals, wl.tokens
    n = len(arr)
    fp = budget.footprint(tok)
    if n and float(fp.max()) > budget.capacity:
        raise ValueError(
            f"memory budget {budget.capacity} cannot hold the largest "
            f"single request (footprint {float(fp.max())}); no schedule "
            "exists")
    arr_p, _, L = _pow2_rows([arr], np.inf)
    tok_p, _, _ = _pow2_rows([tok], -np.inf)
    table = _sparse_max_table(tok_p)
    # prefix footprint sums on the HOST (np.cumsum accumulates in the same
    # sequential order as the oracle's running `A`), +inf beyond n so the
    # admission search never admits padded rows
    fp_cum = np.full(L + 1, np.inf)
    fp_cum[0] = 0.0
    fp_cum[1:n + 1] = np.cumsum(fp)
    M = max(1 << max(n - 1, 1).bit_length(), 2)
    with x64():
        nb, blocked, blocked_t, deferred, o_start, o_end, o_dend = \
            _tandem_loop(L, table.shape[0], M)(
                jnp.asarray(arr_p[0], jnp.float64),
                jnp.asarray(table, jnp.float64),
                jnp.asarray(fp_cum, jnp.float64), jnp.int32(n),
                jnp.int32(policy.b_max if policy.b_max is not None else L),
                jnp.float64(budget.capacity),
                jnp.float64(lat.k1), jnp.float64(lat.k2),
                jnp.float64(lat.k3), jnp.float64(lat.k4))
        nb = int(nb)
        o_start = np.asarray(o_start)[:nb]
        o_end = np.asarray(o_end)[:nb]
        o_dend = np.asarray(o_dend)[:nb]
    sizes = np.diff(o_end, prepend=0)
    starts_req = np.repeat(o_start, sizes)      # batches are contiguous
    comps_req = np.repeat(o_dend, sizes)
    waits = starts_req - arr
    w = _warm(waits)
    mem = occupancy_stats(starts_req, comps_req, fp, float(budget.capacity))
    mem["blocked_batches"] = int(blocked)
    mem["blocked_time"] = float(blocked_t)
    mem["deferred_requests"] = int(deferred)
    return {
        "mean_wait": float(w.mean()) if w.size else 0.0,
        "p95_wait": float(np.percentile(w, 95)) if w.size else 0.0,
        "mean_batch": float(n / max(nb, 1)),
        "waits": w,
        "memory": mem,
    }


# ----------------------------------------------------------------------------
# Uniform sweep: one vmapped scan for every batch_scan lane, kernels for rest
# ----------------------------------------------------------------------------

def sweep(policies: dict, lam_grid, dist, lat,
          num_requests: int = 100_000, seed: int = 0,
          lane_scan: Optional[Callable] = None) -> dict:
    """Mean wait for each policy over an arrival-rate grid — the uniform
    fast entry point.  ``policies``: name -> BatchPolicy (or legacy spec
    dict).  Policies riding the shared per-request batching scan
    (``scan_lane() is not None``) are stacked as lanes of ONE vmapped scan;
    every other policy dispatches through ``KERNELS`` per (λ, policy) cell
    (falling back to the oracle when it has no compiled kernel).

    ``lane_scan`` overrides the vmapped lane executor (same signature and
    bit-identical per-lane semantics as ``_batching_scan(True)``) —
    :mod:`repro.core.shardsweep` passes its ``shard_map`` twin to spread
    the lanes over a device mesh."""
    lam_grid = list(lam_grid)
    insts = {name: (p if isinstance(p, BatchPolicy) else policy_from_spec(p))
             for name, p in policies.items()}
    lanes = []          # (name, lam_idx, elastic, b_max)
    out = {name: [None] * len(lam_grid) for name in insts}
    for name, pol in insts.items():
        lane = pol.scan_lane()
        if lane is not None and pol.n_max is None:
            for li in range(len(lam_grid)):
                lanes.append((name, li) + lane)
        else:
            for li, lam in enumerate(lam_grid):
                r = simulate_policy_fast(pol, lam, dist, lat,
                                         num_requests=num_requests, seed=seed)
                out[name][li] = r["mean_wait"]
    if lanes:
        arrs, toks = [], []
        for lam in lam_grid:
            wl = DynamicPolicy().sample_workload(lam, dist, num_requests,
                                                 seed)
            arrs.append(wl.arrivals)
            toks.append(wl.tokens)
        arr_l = np.stack([arrs[li] for _, li, _, _ in lanes])
        tok_l = np.stack([toks[li] for _, li, _, _ in lanes])
        elas = np.array([e for _, _, e, _ in lanes])
        bmax = np.array([float(bm) if bm is not None else _NO_CAP
                         for _, _, _, bm in lanes])
        scan = _batching_scan(True) if lane_scan is None else lane_scan
        with x64():
            starts, closed = scan(
                jnp.asarray(arr_l, jnp.float64),
                jnp.asarray(tok_l, jnp.float64),
                jnp.float64(lat.k1), jnp.float64(lat.k2),
                jnp.float64(lat.k3), jnp.float64(lat.k4),
                jnp.asarray(elas), jnp.asarray(bmax, jnp.float64))
            starts = np.asarray(starts)
            closed = np.asarray(closed)
        for row, (name, li, _, _) in enumerate(lanes):
            stats = _batch_lane_stats(starts[row], closed[row], arrs[li])
            out[name][li] = stats["mean_wait"]
    return {k: np.asarray(v) for k, v in out.items()}


def simulate_policy_sweep_fast(lam_grid, dist, lat, policies: dict,
                               num_requests: int = 100_000,
                               seed: int = 0) -> dict:
    """Drop-in fast twin of simulate_policy_sweep (legacy argument order)."""
    return sweep(policies, lam_grid, dist, lat,
                 num_requests=num_requests, seed=seed)


# ----------------------------------------------------------------------------
# Noise-robustness sweep over the (arrival rate, prediction error) plane
# ----------------------------------------------------------------------------

def sweep_noise(policy_factory: Callable[[float], BatchPolicy], lam_grid,
                sigma_grid, dist, lat, num_requests: int = 50_000,
                seed: int = 0,
                srpt_loop: Optional[Callable] = None) -> dict:
    """Mean wait over the (λ, σ) grid: how a length-aware policy's win
    erodes as its predictor degrades.

    ``policy_factory(sigma)`` builds the policy at prediction-noise level
    ``sigma`` (typically with a
    :class:`repro.core.predictors.LogNormalNoisePredictor` of that sigma;
    sigma=0 must reproduce the oracle).  The workload stream per λ is
    identical across the σ row — the predictor rng is salted away from the
    workload rng — so the columns differ ONLY by prediction quality.

    When every produced policy rides the ``srpt`` kernel, all (λ, σ)
    cells become lanes of ONE vmapped jitted batch-event loop
    (``_srpt_loop_vmapped``); otherwise cells dispatch through
    ``simulate_policy_fast`` individually (multi-bin's per-bin row count
    varies with σ, so its kernel shapes cannot share a vmap).  Note the
    vmap trip count is the MAX over lanes (batch events, and pops within
    an event): lanes at loads where the server often idles (many
    singleton batches) drag every lane, so on CPU the single dispatch can
    cost more than per-cell calls — the lane layout pays off on
    accelerator backends where lanes are data-parallel, and keeps one
    compile for arbitrarily fine σ grids.

    ``srpt_loop`` overrides the vmapped lane executor factory (same
    ``L -> callable`` contract and bit-identical per-lane semantics as
    ``_srpt_loop_vmapped``) — :mod:`repro.core.shardsweep` passes its
    ``shard_map`` twin to spread the (λ, σ) lanes over a device mesh.

    Returns ``{"mean_wait": [len(lam_grid), len(sigma_grid)], "lams",
    "sigmas"}``.
    """
    lam_grid = [float(l) for l in lam_grid]
    sigma_grid = [float(s) for s in sigma_grid]
    pols = [policy_factory(s) for s in sigma_grid]
    out = np.empty((len(lam_grid), len(sigma_grid)))
    if all(p.fast_kernel == "srpt" for p in pols):
        b_maxes = {p.b_max for p in pols}
        assert len(b_maxes) == 1, "srpt lanes must share one b_max"
        b_max = b_maxes.pop()
        cells, trees, tok_ranks, orders, arrs = [], [], [], [], []
        L = None
        for li, lam in enumerate(lam_grid):
            for si, pol in enumerate(pols):
                wl = pol.sample_workload(lam, dist, num_requests, seed)
                order, tree, tok_rank, L = _srpt_rank_arrays(
                    wl.arrivals, wl.tokens, wl.predicted_or_true)
                cells.append((li, si))
                trees.append(tree)
                tok_ranks.append(tok_rank)
                orders.append(order)
                arrs.append(wl.arrivals)
        loop = _srpt_loop_vmapped if srpt_loop is None else srpt_loop
        with x64():
            starts, nbs = loop(L)(
                jnp.asarray(np.stack(trees), jnp.float64),
                jnp.asarray(np.stack(tok_ranks), jnp.float64),
                jnp.int32(num_requests),
                jnp.int32(b_max if b_max is not None else L),
                jnp.float64(lat.k1), jnp.float64(lat.k2),
                jnp.float64(lat.k3), jnp.float64(lat.k4))
            starts = np.asarray(starts)
            nbs = np.asarray(nbs)
        for c, (li, si) in enumerate(cells):
            out[li, si] = _srpt_stats(starts[c], nbs[c], orders[c],
                                      arrs[c])["mean_wait"]
    else:
        for li, lam in enumerate(lam_grid):
            for si, pol in enumerate(pols):
                r = simulate_policy_fast(pol, lam, dist, lat,
                                         num_requests=num_requests,
                                         seed=seed)
                out[li, si] = r["mean_wait"]
    return {"mean_wait": out, "lams": np.asarray(lam_grid),
            "sigmas": np.asarray(sigma_grid)}


# ----------------------------------------------------------------------------
# Fleet layer: jitted backlog routing + split-then-kernel per replica
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _backlog_scan(R: int):
    """The state-dependent routing recursion (jsq / least_work) as one
    ``lax.scan`` over arrivals with an O(R) carry: decay every replica's
    virtual backlog by the elapsed time, join the argmin (first index on
    ties, matching ``np.argmin``), add the request's work estimate.
    Elementary IEEE float64 ops only, so the assignments are bit-equal to
    the NumPy reference loop in ``repro.core.fleet``."""

    def run(arrivals, work):
        def step(carry, xs):
            v, t_prev = carry
            a, w = xs
            v = jnp.maximum(0.0, v - (a - t_prev))
            r = jnp.argmin(v).astype(jnp.int32)
            return (v.at[r].add(w), a), r

        _, rs = lax.scan(step, (jnp.zeros(R, jnp.float64), jnp.float64(0.0)),
                         (arrivals, work), unroll=_UNROLL)
        return rs

    return jax.jit(run)


def backlog_route(arrivals, work, R: int) -> np.ndarray:
    """Compiled twin of ``fleet._backlog_assign_np`` (replica id per
    request); arrays padded to a power of two so fleet sweeps share
    compiles across workload sizes."""
    n = len(arrivals)
    with x64():
        rs = _backlog_scan(int(R))(
            jnp.asarray(_pad_pow2_1d(arrivals, np.inf), jnp.float64),
            jnp.asarray(_pad_pow2_1d(work, 0.0), jnp.float64))
        return np.asarray(rs, np.int64)[:n]


@functools.lru_cache(maxsize=None)
def _masked_backlog_scan(R: int):
    """Availability-masked twin of :func:`_backlog_scan`: the replica
    up/down mask rides the scan inputs (one boolean row per arrival,
    failure epochs precomputed on host by :mod:`repro.core.faults`), and
    a down replica's virtual backlog is +inf in the argmin so it never
    receives work.  With every replica up, ``where(up, v, inf) == v``
    and the assignments are bit-equal to the unmasked scan."""

    def run(arrivals, work, up):
        def step(carry, xs):
            v, t_prev = carry
            a, w, u = xs
            v = jnp.maximum(0.0, v - (a - t_prev))
            r = jnp.argmin(jnp.where(u, v, jnp.inf)).astype(jnp.int32)
            return (v.at[r].add(w), a), r

        _, rs = lax.scan(step, (jnp.zeros(R, jnp.float64), jnp.float64(0.0)),
                         (arrivals, work, up), unroll=_UNROLL)
        return rs

    return jax.jit(run)


def masked_backlog_route(arrivals, work, up, R: int) -> np.ndarray:
    """Compiled twin of ``fleet._masked_backlog_assign_np``: replica id
    per request under an availability mask (padded rows are all-up, so
    padding is inert)."""
    n = len(arrivals)
    up = np.asarray(up, bool)
    m = len(_pad_pow2_1d(np.zeros(n), 0.0))
    up_pad = np.ones((m, up.shape[1]), bool)
    up_pad[:n] = up
    with x64():
        rs = _masked_backlog_scan(int(R))(
            jnp.asarray(_pad_pow2_1d(arrivals, np.inf), jnp.float64),
            jnp.asarray(_pad_pow2_1d(work, 0.0), jnp.float64),
            jnp.asarray(up_pad))
        return np.asarray(rs, np.int64)[:n]


def simulate_fleet_fast(router, policy: BatchPolicy, lam: float, R: int,
                        dist: Optional[TokenDistribution], lat,
                        num_requests: int = 100_000, seed: int = 0,
                        traffic=None, sessions=None,
                        prefix_discount: float = 0.0, memory=None) -> dict:
    """Fast twin of :func:`repro.core.fleet.route_oracle`: the router's
    split is identical (state-dependent assignment via the jitted backlog
    scan), and each replica's sub-workload runs through the policy's
    compiled single-server kernel (oracle fallback when it has none).
    ``traffic`` modulates the arrival stream before routing, exactly
    like the oracle twin's parameter.  ``sessions`` /
    ``prefix_discount`` re-enter completed turns through the fleet
    feedback fixed point
    (:func:`repro.core.sessions.simulate_fleet_sessions`) with the
    kernels as the inner pass — same control flow as the oracle twin.
    ``memory`` gives EACH replica its own KV budget (capacity is
    per-replica HBM, not a fleet pool) through the unchanged
    single-server tandem kernels."""
    from repro.core.fleet import router_from_spec, run_fleet
    router = router_from_spec(router)
    if sessions is not None:
        from repro.core.sessions import (session_from_spec,
                                         simulate_fleet_sessions)
        model = session_from_spec(sessions)
        if not model.is_null:
            return simulate_fleet_sessions(
                router, policy, lam, R, dist, lat, num_requests, seed,
                model, prefix_discount=prefix_discount, traffic=traffic,
                fast=True)
    fw = router.fleet_workload(policy, lam, dist, lat, num_requests, seed,
                               R, fast=True, traffic=traffic)
    return run_fleet(fw, policy, lat, dist,
                     lambda pol, wl: simulate_policy_fast(
                         pol, lam, dist, lat, workload=wl, memory=memory))


def run_controlled(policy, lam, dist, lat, **kw):
    """Closed-loop time-sliced control on the fast path: the compiled
    kernels run every window, the controller re-picks replicas / router /
    bin_edges / shed_prob between windows.  Thin wrapper over
    :func:`repro.core.control.simulate_controlled` with ``fast=True``
    (pass ``fast=False`` there for the reference-oracle twin)."""
    from repro.core.control import simulate_controlled
    kw.setdefault("fast", True)
    return simulate_controlled(policy, lam, dist, lat, **kw)
