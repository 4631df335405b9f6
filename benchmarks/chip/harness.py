"""One run of one cell: set-up, the timed window, the check, the metrics.

``run_cell`` is the whole run below the command line: ``run.py`` adds
the look for a chip and the printing.  Tests drive ``run_cell`` on the
CPU at a small size, with the timed path broken underneath.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

import e2e
import engine_io
import reference
import trace_reduce as tr
import traffic as tf
from serve_loop import serve, warm
from spec import HERE, REPO, Cell

TRACE_DIR = REPO / ".bench_trace"
# A traced run profiles the batches dispatched in the last this many
# seconds of its window, and stops the profiler only once the loop has
# ended: writing the trace out stalls the host for tens of seconds, and
# a stall inside the window would pass into every wait after it.  A trace
# of the whole window would take minutes to read back.
TRACE_SECONDS = 8.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The gaps a cell's ``limits`` may name, by the key ``reference.compare``
# gives them: the widest over the served positions, and the mean.
GAP_CHECKS = {"max_logit_gap": "", "mean_logit_gap": "_mean"}


@dataclasses.dataclass
class RunData:
    """What the metric readers (``metrics/<name>.py``) read."""
    cell: Cell
    peaks: Optional[dict]
    loop0: float
    t0: float
    t1: float
    reqs: list
    rec: engine_io.Recorder
    loop: dict
    step_log: list
    trace: Optional[dict] = None
    traced: Optional[tuple] = None      # (lo, hi) ns of the traced window

    @property
    def shape(self):
        return self.cell.shape

    @property
    def family(self):
        return self.cell.family

    def in_window(self, call) -> bool:
        return self.t0 <= call.t1 < self.t1

    def traced_calls(self, kind: str):
        """Calls of ``kind`` whose span lies inside the traced window,
        with that span."""
        spans = tr.spans_of(self.trace, kind)
        lo, hi = self.traced
        return [(c, spans[c.n]) for c in self.rec.calls
                if c.kind == kind and c.n in spans
                and spans[c.n][0] >= lo and spans[c.n][1] <= hi]


def metric_reader(name: str):
    """``metrics/<name>.py``: a module with ``read(RunData)`` that
    returns the metric, or None where it finds nothing to read."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _peaks(kind: str) -> Optional[dict]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    return table.get(kind)


class _Tracer:
    """Starts the JAX profiler before the first batch dispatched at or
    after ``lo``, with the span ``bench.window`` around what it traces;
    ``stop`` ends both once the loop has ended.  The Python tracer stays
    off: the reduction reads device operations and the harness's spans."""

    def __init__(self, trace_dir: Path, lo: float):
        self.dir, self.lo = trace_dir, lo
        self.span = None

    def __call__(self, now: float) -> None:
        if self.span is None and now >= self.lo:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()

    def stop(self) -> None:
        if self.span is None:
            raise RuntimeError("no batch was dispatched in the traced part "
                               "of the window")
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.span = None


class _CompileCounter:
    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.on and event == _COMPILE_EVENT:
            self.n += 1


def pick_sample(seed: int, served: dict, tokens_wanted: int,
                max_rows: int) -> list:
    """Request ids to check: the one with the most served tokens, then
    others in an order drawn from the seed, until ``tokens_wanted``."""
    ids = sorted(served)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (len(served[i]), -i))
    rest = [i for i in ids if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([0xC4EC, int(seed)]))
    rest = [rest[j] for j in rng.permutation(len(rest))]
    pick, total = [longest], len(served[longest])
    for i in rest:
        if total >= tokens_wanted or len(pick) >= max_rows:
            break
        pick.append(i)
        total += len(served[i])
    return pick


def reference_length(traffic: dict, block: int = 128) -> int:
    p, o = traffic["prompt"], traffic["output"]
    n_max = traffic["policy"].get("n_max")
    longest = int(p.get("hi", p.get("n", 0))) + min(
        int(o.get("hi", o.get("n", 0))), n_max or 1 << 30)
    return -(-longest // block) * block


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory in the
    checkout, whatever the environment says: only the first run of a
    cell in a checkout compiles."""
    jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def set_up(cell: Cell, seed: int, eng=None):
    """Weights drawn from the seed on the device, and the engine that
    serves them with every program the cell's traffic reaches warmed.
    Given an engine already warmed for this cell, the new weights go into
    it.  Returns ``(engine, reference weights, warm-up calls)``."""
    cfg, _ = engine_io.program_configs(cell)
    if eng is not None:
        eng.params = None          # the old weights go before new ones come
    ref_w, prog_w = cell.family.make_weights(cell.shape, cell.config, seed,
                                             cfg.padded_vocab)
    if eng is not None:
        eng.params = prog_w
        return eng, ref_w, 0
    eng = engine_io.build_engine(cell, prog_w)
    return eng, ref_w, warm(eng, cell.traffic)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, clock=time.perf_counter,
             log=print, control: bool = False,
             trace_dir: Path = TRACE_DIR, eng=None,
             rate: Optional[float] = None) -> dict:
    """Set up, serve the window, check and reduce.  Returns the result
    line's fields and the numbers compared (``checks``).

    ``control`` puts the control (the reference one precision below the
    configuration's) in the program's place: its first choice at every
    served position is judged by the same checks, so ``correct`` reads
    false.  ``eng`` (warmed for this cell) and ``rate`` (req/s, in place
    of the cell's) are for the calibration tool.  A traced run leaves its
    trace in ``trace_dir`` for the caller to remove."""
    m = cell.shape
    traffic = cell.traffic
    eng, ref_w, warm_calls = set_up(cell, seed, eng)
    rate = cell.cell.get("rate_rps") if rate is None else rate
    reqs = tf.make_requests(traffic, rate, seconds, seed, m.vocab)
    rec = engine_io.Recorder(eng, clock)
    fallbacks0 = eng.sample_fallbacks
    compiles = _CompileCounter()
    device = jax.devices()[0]

    compiles.on = True
    loop0 = clock()
    setup_s = loop0 - t_start
    t0 = loop0 + float(traffic["lead_in_s"])
    t1 = t0 + seconds
    tracer = _Tracer(trace_dir, max(t0, t1 - TRACE_SECONDS)) \
        if trace else None
    loop = serve(eng, rec, traffic, reqs, loop0, t0, t1, clock=clock,
                 between=tracer)
    if tracer is not None:
        tracer.stop()
    compiles.on = False
    rec.detach()
    stats = device.memory_stats() or {}
    fallbacks = eng.sample_fallbacks - fallbacks0
    log(f"[bench] setup_s={setup_s} warm_calls={warm_calls} "
        f"compiles_in_window={compiles.n} batches={len(loop['late_s'])} "
        f"loop_late_max_s={max(loop['late_s'], default=0.0)} "
        f"sample_fallbacks={fallbacks}")

    data = RunData(cell, _peaks(device.device_kind), loop0, t0, t1, reqs,
                   rec, loop, list(eng.step_log))
    metrics = {}
    if not trace:
        values = e2e.end_to_end(data)
        values["setup_s"] = setup_s
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": values[spec["name"]],
                                     "unit": spec["unit"]}
    # the program's state goes before the reference runs (a caller that
    # passed ``eng`` keeps its weights, which the reference reads too)
    del eng
    rec.eng = None

    # --- correctness -------------------------------------------------
    window_ids = [r.rid for r in reqs if t0 <= loop0 + r.due < t1]
    served = loop["served"]
    wrong = [i for i in served if len(served[i]) != rec._target[i]]
    refcfg = cell.config["reference"]
    pick = pick_sample(seed, served, int(refcfg.get("sample_tokens", 384)),
                       int(refcfg.get("max_rows", 16)))
    length = reference_length(traffic)
    t_ref = clock()
    cmp = reference.compare(cell.family.logits, m, ref_w,
                            [(reqs[i].prompt, served[i]) for i in pick],
                            length, int(refcfg["block_rows"]),
                            control=control)
    ref_s = clock() - t_ref
    judged = "control" if control else "program"
    checks, bad_rows = {}, set()
    for name, key in GAP_CHECKS.items():
        if name in cell.cell["limits"]:
            limit = cell.cell["limits"][name]
            checks[name] = {"value": cmp[judged + key], "limit": limit}
            # a limit holds over the whole sample; where it is passed, the
            # rows that read over it on their own are the failed requests
            if limit is None or cmp[judged + key] > limit:
                bad_rows |= {i for i, g in
                             zip(pick, cmp["rows"][judged + key])
                             if limit is None or g > limit}
    checks["wrong_token_counts"] = {"value": len(wrong), "limit": 0}
    checks["nonfinite_fallbacks"] = {"value": fallbacks, "limit": 0}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    log(f"[bench] checked {len(pick)} requests, {cmp['positions']} served "
        f"tokens, {sum(i in rec.compacted for i in pick)} moved by "
        f"compaction, reference {ref_s:.2f} s, program gap {cmp['program']}"
        + (f", control gap {cmp['control']}" if control else ""))
    failed = len(set(window_ids) & (set(wrong) | bad_rows))

    out = {"correct": bool(correct), "attempted": len(window_ids),
           "failed": failed, "metrics": metrics,
           "device": {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": stats.get("peak_bytes_in_use")},
           "checks": checks, "compiles_in_window": compiles.n,
           "gaps": {k: v for k, v in cmp.items()
                    if k.startswith(("program", "control"))},
           "checked": {"requests": len(pick), "positions": cmp["positions"],
                       "compacted": sum(i in rec.compacted for i in pick),
                       "reference_s": ref_s},
           "run": data}

    if trace:
        data.trace = tr.load(str(trace_dir))
        lo, hi = data.trace["spans"]["bench.window"]
        data.traced = (lo, hi)
        ops = data.trace["ops"]
        out["device"]["busy_s"] = tr.busy_per_chip(ops, lo, hi) * 1e-9
        out["device"]["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {
            "device_ops": tr.top_ops(ops, lo, hi),
            "idle_gaps": tr.idle_gaps(ops, data.trace["spans"], lo, hi)}
        for spec in cell.per_layer:
            v = metric_reader(spec["name"]).read(data)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return out
