"""Multi-device grid sweeps: ``shard_map`` twins of the fastsim lane scans.

:mod:`repro.core.fastsim` stacks every (λ, policy) / (λ, σ) / (R, λ,
replica) grid cell as a *lane* of one vmapped compiled loop.  This module
spreads those lanes over a 1-D ``"cells"`` device mesh
(:func:`repro.distributed.sharding.cells_mesh`) with ``shard_map``: each
device runs the UNCHANGED vmapped kernel on its shard of the lanes, no
collectives, so per-lane results are bit-equal to the single-device path —
lanes are elementwise-independent, and sharding only changes which device
computes which lane.

Two invariants make the equality exact rather than approximate:

  * **Lane padding duplicates real lanes** (``np.arange(Lp) % n``): the
    lane count pads to a power of two that divides the mesh (so every
    cell-count shares one compile per mesh and shards evenly), and a
    duplicated lane computes the identical trajectory of the lane it
    copies — sliced off the output, it can't perturb anything.
  * **Row padding appends inert tail entries** (arrivals at +inf, tokens
    0): a ``lax.scan`` carry at position i only sees inputs [0, i], so
    appending entries after a lane's true length never changes its first
    n outputs — fleet replica sub-streams of ragged lengths pad to ONE
    global power-of-two row length instead of per-replica lengths, and
    the sliced prefixes still match ``_batch_scan_kernel`` bit for bit.

Entry points mirror their single-device twins and accept ``mesh=None``
(-> all local devices):

  * :func:`sweep`        — ``fastsim.sweep`` with sharded batching lanes.
  * :func:`sweep_noise`  — ``fastsim.sweep_noise`` with sharded SRPT lanes.
  * :func:`fleet_sweep`  — the big win: ``fleet.sweep`` runs R separate
    kernel dispatches per (R, λ) cell; here EVERY replica sub-stream of
    EVERY cell becomes one lane of a single sharded scan (one dispatch
    for the whole grid), then aggregates per cell exactly like
    ``fleet.run_fleet``.  Policies without a ``batch_scan`` lane fall
    back to the per-cell path unchanged.

On a single-device host the mesh has size 1 and the shard_map path still
runs (CI forces ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
for a real 4-way CPU mesh); ``tests/test_shardsweep.py`` pins exact
equality against the single-device entry points in both regimes.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fastsim, fleet
from repro.core.fastsim import (
    _NO_CAP, _batch_lane_stats, _batching_core, _srpt_core)
from repro.core.fleet import (
    FleetWorkload, RoutingPolicy, _aggregate, _sub_workload,
    router_from_spec, served_slice)
from repro.core.policies import BatchPolicy
from repro.distributed.sharding import SWEEP_RULES, cells_mesh, logical_to_spec


def pad_lane_count(n: int, ndev: int) -> int:
    """Padded lane count: next power of two >= max(n, 2), rounded up to a
    multiple of ``ndev`` so shard_map splits evenly (for the usual
    power-of-two device counts the pow2 is already a multiple)."""
    L = max(1 << max(n - 1, 1).bit_length(), 2)
    if L % ndev:
        L = -(-L // ndev) * ndev
    return L


def _lane_spec(mesh: Mesh) -> P:
    """PartitionSpec for the lane axis via the shared rule machinery."""
    return logical_to_spec(("lanes",), SWEEP_RULES, mesh, None)


@functools.lru_cache(maxsize=None)
def _sharded_batching_scan(mesh: Mesh):
    """shard_map twin of ``fastsim._batching_scan(True)``: lanes shard
    over the "cells" axis, latency constants replicate, each device runs
    the unchanged vmapped per-request scan on its lane shard."""
    lane = _lane_spec(mesh)
    vmapped = jax.vmap(_batching_core,
                       in_axes=(0, 0, None, None, None, None, 0, 0))
    return jax.jit(jax.shard_map(
        vmapped, mesh=mesh,
        in_specs=(lane, lane, P(), P(), P(), P(), lane, lane),
        out_specs=(lane, lane), check_vma=False))


@functools.lru_cache(maxsize=None)
def lane_executor(mesh: Optional[Mesh] = None):
    """Drop-in replacement for ``fastsim._batching_scan(True)`` (the
    ``lane_scan`` hook of :func:`repro.core.fastsim.sweep`): pad the lane
    axis by duplicating real lanes, run the sharded scan, slice back."""
    mesh = cells_mesh() if mesh is None else mesh

    def scan(arr, tok, k1, k2, k3, k4, elas, bmax):
        n = arr.shape[0]
        Lp = pad_lane_count(n, mesh.size)
        if Lp != n:
            idx = np.arange(Lp) % n      # duplicate real lanes (inert)
            arr, tok = arr[idx], tok[idx]
            elas, bmax = elas[idx], bmax[idx]
        starts, closed = _sharded_batching_scan(mesh)(
            arr, tok, k1, k2, k3, k4, elas, bmax)
        return starts[:n], closed[:n]

    return scan


@functools.lru_cache(maxsize=None)
def _sharded_srpt_loop(mesh: Mesh, L: int):
    """shard_map twin of ``fastsim._srpt_loop_vmapped(L)``: each device
    runs the vmapped SRPT batch-event while_loop on its lane shard (the
    loops are data-local, so lanes on different devices run their own
    trip counts with no cross-device sync)."""
    lane = _lane_spec(mesh)
    vmapped = jax.vmap(_srpt_core(L),
                       in_axes=(0, 0, None, None, None, None, None, None))
    return jax.jit(jax.shard_map(
        vmapped, mesh=mesh,
        in_specs=(lane, lane, P(), P(), P(), P(), P(), P()),
        out_specs=(lane, lane), check_vma=False))


def srpt_executor(mesh: Optional[Mesh] = None):
    """``L -> callable`` factory matching ``fastsim._srpt_loop_vmapped``
    (the ``srpt_loop`` hook of :func:`repro.core.fastsim.sweep_noise`),
    with lane padding by duplication."""
    mesh = cells_mesh() if mesh is None else mesh

    def make(L: int):
        def loop(trees, tok_ranks, n, b_max, k1, k2, k3, k4):
            c = trees.shape[0]
            Lp = pad_lane_count(c, mesh.size)
            if Lp != c:
                idx = np.arange(Lp) % c
                trees, tok_ranks = trees[idx], tok_ranks[idx]
            starts, nbs = _sharded_srpt_loop(mesh, L)(
                trees, tok_ranks, n, b_max, k1, k2, k3, k4)
            return starts[:c], nbs[:c]
        return loop

    return make


def _backlog_core_padded(arrivals, work, v0):
    """One lane of the stacked state-dependent routing recursion
    (``fastsim._backlog_scan`` with the replica axis padded to a shared
    R_max): ``v0`` seeds real replicas at 0 and padding replicas at +inf —
    +inf survives the decay (``max(0, inf - dt) = inf``) and never wins
    the argmin, so assignments are bit-equal to the unpadded scan."""
    def step(carry, xs):
        v, t_prev = carry
        a, w = xs
        v = jnp.maximum(0.0, v - (a - t_prev))
        r = jnp.argmin(v).astype(jnp.int32)
        v = v.at[r].add(w)
        return (v, a), r

    _, rs = jax.lax.scan(step, (v0, jnp.float64(0.0)), (arrivals, work),
                         unroll=fastsim._UNROLL)
    return rs


@functools.lru_cache(maxsize=None)
def _sharded_backlog_scan(mesh: Mesh):
    """shard_map of the vmapped padded backlog recursion: every (R, λ)
    grid cell's routing becomes one lane (arrivals/work/v0 shard over
    "cells"), replacing fleet.sweep's per-cell ``backlog_route`` calls
    with ONE dispatch."""
    lane = _lane_spec(mesh)
    vmapped = jax.vmap(_backlog_core_padded, in_axes=(0, 0, 0))
    return jax.jit(jax.shard_map(
        vmapped, mesh=mesh, in_specs=(lane, lane, lane),
        out_specs=lane, check_vma=False))


def _stacked_assign(router, jobs, mesh: Mesh):
    """Run every state-dependent routing job ``(key, arrivals, work, R)``
    as one lane of the sharded backlog scan.  Arrivals pad with +inf /
    work with 0 (the exact fills of ``fastsim.backlog_route``) and the
    replica axis pads to the grid's R_max with +inf initial backlog.
    Returns {key: replica ids}, each bit-equal to ``router.assign(...,
    fast=True)``."""
    if not jobs:
        return {}
    r_max = max(R for _, _, _, R in jobs)
    rows = max(fastsim._pad_pow2_1d(a, np.inf).shape[0]
               for _, a, _, _ in jobs)
    nl = pad_lane_count(len(jobs), mesh.size)
    arr = np.full((nl, rows), np.inf)
    wrk = np.zeros((nl, rows))
    v0 = np.full((nl, r_max), np.inf)
    for j, (_, a, w, R) in enumerate(jobs):
        arr[j, :len(a)] = a
        wrk[j, :len(w)] = router._work_units(np.asarray(w, np.float64))
        v0[j, :R] = 0.0
    for j in range(len(jobs), nl):       # duplicate lane 0 (inert)
        arr[j], wrk[j], v0[j] = arr[0], wrk[0], v0[0]
    with fastsim.x64():
        rs = _sharded_backlog_scan(mesh)(
            jnp.asarray(arr, jnp.float64), jnp.asarray(wrk, jnp.float64),
            jnp.asarray(v0, jnp.float64))
        rs = np.asarray(rs, np.int64)
    return {key: rs[j, :len(a)]
            for j, (key, a, _, _) in enumerate(jobs)}


# ----------------------------------------------------------------------------
# Public entry points (signatures mirror the single-device twins + mesh)
# ----------------------------------------------------------------------------

def sweep(policies: dict, lam_grid, dist, lat, num_requests: int = 100_000,
          seed: int = 0, mesh: Optional[Mesh] = None) -> dict:
    """:func:`repro.core.fastsim.sweep` with the (λ, policy) batching
    lanes sharded over the device mesh — same return, bit-equal values."""
    return fastsim.sweep(policies, lam_grid, dist, lat,
                         num_requests=num_requests, seed=seed,
                         lane_scan=lane_executor(mesh))


def sweep_noise(policy_factory, lam_grid, sigma_grid, dist, lat,
                num_requests: int = 50_000, seed: int = 0,
                mesh: Optional[Mesh] = None) -> dict:
    """:func:`repro.core.fastsim.sweep_noise` with the (λ, σ) SRPT lanes
    sharded over the device mesh — same return, bit-equal values."""
    return fastsim.sweep_noise(policy_factory, lam_grid, sigma_grid, dist,
                               lat, num_requests=num_requests, seed=seed,
                               srpt_loop=srpt_executor(mesh))


def fleet_sweep(R_grid, lam_grid, router, policy: BatchPolicy, dist, lat,
                num_requests: int = 50_000, seed: int = 0,
                mesh: Optional[Mesh] = None) -> dict:
    """Sharded twin of :func:`repro.core.fleet.sweep`: route every (R, λ)
    cell on host (identical split machinery), then run EVERY replica
    sub-stream of EVERY cell as one lane of a single sharded scan and
    aggregate per cell exactly like ``fleet.run_fleet`` — one device
    dispatch for the whole grid instead of sum(R_grid)·len(lam_grid)
    kernel calls.  Values are bit-equal to ``fleet.sweep`` (same routing,
    same per-lane recursion, inert padding).  Policies without a
    ``batch_scan`` lane (or with an n_max admission cap) fall back to the
    per-cell path."""
    mesh = cells_mesh() if mesh is None else mesh
    router = router_from_spec(router)
    R_grid = [int(r) for r in R_grid]
    lam_grid = [float(l) for l in lam_grid]
    lane = policy.scan_lane() if policy.fast_kernel == "batch_scan" else None
    if lane is None or policy.n_max is not None:
        return fleet.sweep(R_grid, lam_grid, router, policy, dist, lat,
                           num_requests=num_requests, seed=seed)
    elastic, b_max = lane

    # ---- routing: one workload sample per λ, one stacked assign call ----
    # The base fleet_workload samples the SAME (λ, seed) stream for every
    # R and assigns per cell; here the sample is shared across the R
    # column and all state-dependent cells route as lanes of one sharded
    # backlog scan.  Routers that override fleet_workload (random's exact
    # per-replica superposition) keep their own per-cell construction.
    base_route = type(router).fleet_workload is RoutingPolicy.fleet_workload
    fws = {}
    if base_route:
        wl_of = {lam: policy.sample_workload(lam, dist, num_requests, seed)
                 for lam in lam_grid}
        work_of = {lam: router.routing_work(wl_of[lam], lat, seed)
                   for lam in lam_grid}
        if router.state_dependent:
            jobs = [((R, lam), wl_of[lam].arrivals, work_of[lam], R)
                    for R in R_grid for lam in lam_grid if R > 1]
            assigns = _stacked_assign(router, jobs, mesh)
        else:
            assigns = {(R, lam): np.asarray(
                router.assign(wl_of[lam].arrivals, work_of[lam], R, seed,
                              fast=True), np.int64)
                for R in R_grid for lam in lam_grid if R > 1}
        for R in R_grid:
            for lam in lam_grid:
                wl = wl_of[lam]
                if R == 1:
                    fws[(R, lam)] = FleetWorkload(
                        [wl], np.zeros(len(wl.arrivals), np.int64),
                        wl.arrivals, 1)
                    continue
                rep = assigns[(R, lam)]
                subs = [_sub_workload(wl, np.nonzero(rep == r)[0])
                        for r in range(R)]
                fws[(R, lam)] = FleetWorkload(subs, rep, wl.arrivals, R)
    else:
        for R in R_grid:
            for lam in lam_grid:
                fws[(R, lam)] = router.fleet_workload(
                    policy, lam, dist, lat, num_requests, seed, R, fast=True)

    # ---- collect one lane per non-empty replica sub-stream ----
    cells = []                      # (ri, li, fw, [None | (row, workload)])
    lane_wls = []
    for ri, R in enumerate(R_grid):
        for li, lam in enumerate(lam_grid):
            fw = fws[(R, lam)]
            slots = []
            for wl in fw.replicas:
                wl = served_slice(policy, wl)
                if len(wl.arrivals) == 0:
                    slots.append(None)      # run_fleet's empty-replica None
                    continue
                slots.append((len(lane_wls), wl))
                lane_wls.append(wl)
            cells.append((ri, li, fw, slots))

    # ---- one sharded scan per power-of-two row-length bucket ----
    # +inf arrivals / 0 tokens are inert past each lane's true length
    # (scan-prefix property), so the sliced prefixes match the
    # per-replica-padded kernel runs bit for bit.  Bucketing by the same
    # pow2 row length the single-lane kernel pads to avoids stretching
    # every short replica stream to the grid's longest lane.
    starts = [None] * len(lane_wls)
    closed = [None] * len(lane_wls)
    buckets = {}
    for j, wl in enumerate(lane_wls):
        rows = max(1 << max(len(wl.arrivals) - 1, 1).bit_length(), 2)
        buckets.setdefault(rows, []).append(j)
    scan = lane_executor(mesh)
    for rows, idxs in sorted(buckets.items()):
        nl = len(idxs)
        arr_l = np.full((nl, rows), np.inf)
        tok_l = np.zeros((nl, rows))
        for r, j in enumerate(idxs):
            wl = lane_wls[j]
            arr_l[r, :len(wl.arrivals)] = wl.arrivals
            tok_l[r, :len(wl.tokens)] = wl.tokens
        elas = np.full(nl, bool(elastic))
        bmax = np.full(nl, float(b_max) if b_max is not None else _NO_CAP)
        with fastsim.x64():
            s, c = scan(jnp.asarray(arr_l, jnp.float64),
                        jnp.asarray(tok_l, jnp.float64),
                        jnp.float64(lat.k1), jnp.float64(lat.k2),
                        jnp.float64(lat.k3), jnp.float64(lat.k4),
                        jnp.asarray(elas), jnp.asarray(bmax, jnp.float64))
            s, c = np.asarray(s), np.asarray(c)
        for r, j in enumerate(idxs):
            starts[j], closed[j] = s[r], c[r]

    out = np.empty((len(R_grid), len(lam_grid)))
    for ri, li, fw, slots in cells:
        per = []
        for slot in slots:
            if slot is None:
                per.append(None)
                continue
            row, wl = slot
            n = len(wl.arrivals)
            per.append(_batch_lane_stats(starts[row][:n], closed[row][:n],
                                         wl.arrivals))
        out[ri, li] = _aggregate(per, fw)["mean_wait"]
    return {"mean_wait": out, "R_grid": np.asarray(R_grid),
            "lams": np.asarray(lam_grid)}


__all__ = [
    "cells_mesh", "fleet_sweep", "lane_executor", "pad_lane_count",
    "srpt_executor", "sweep", "sweep_noise",
]
