"""The system under test, and the harness's probes around it.

The engine is built exactly as the program's serving launcher builds it
(``repro.launch.serve.model_config`` / ``engine_config``: the scatter
cache update, the KV cache in the model's dtype), from the configuration
file's ``program_arch`` and serving sizes, with the harness's weights.

``Recorder`` wraps the engine instance's ``prefill_batch``,
``decode_chunk`` and ``compact_fused``.  Each wrap stamps the wall clock,
opens a profiler span named ``bench.<kind>#<n>``, and keeps the host-side
facts the per-layer metrics need: which request sits in which slot,
tokens delivered, and each request's KV length at each decode step.  A
request's first token is on the host when ``prefill_batch`` returns; a
later token when the ``decode_chunk`` that made it returns.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from spec import Cell


def program_configs(cell: Cell):
    from repro.launch import serve
    serve_cfg = cell.config["serve"]
    args = serve.parse_args([
        "--arch", cell.config["program_arch"],
        "--max-batch", str(serve_cfg["max_batch"]),
        "--max-seq", str(serve_cfg["max_seq"]),
        *(["--smoke"] if cell.config.get("smoke") else [])])
    cfg = serve.model_config(args)
    return cfg, serve.engine_config(args, cfg)


def abstract_params(cfg):
    from repro.models.model import param_specs
    from repro.models.params import abstract_params as ap
    import jax.numpy as jnp
    return ap(param_specs(cfg), jnp.dtype(cfg.dtype))


def build_engine(cell: Cell, params):
    from repro.serving.engine import Engine
    cfg, ecfg = program_configs(cell)
    # the harness builds weights and the reference from the file, so the
    # program's configuration has to be the published one the file states
    bad = cell.family.check_program(cell.shape, cfg)
    if bad:
        raise SystemExit(f"program config differs from {cell.config_name}: "
                         f"{bad} (program, file)")
    return Engine(cfg, ecfg, params=params)


@dataclasses.dataclass
class Call:
    kind: str                  # prefill | decode_chunk | compact
    n: int                     # sequence number, also in the span's name
    t0: float
    t1: float
    batch: int                 # bucket
    size: int = 0              # prompt bucket, or decode steps
    # prefill: real prompt lengths; decode: per live slot, (KV length
    # before the chunk, tokens made in the chunk)
    work: list = dataclasses.field(default_factory=list)
    tokens: int = 0            # tokens delivered to the host by this call
    log: int = -1              # the engine's step_log entry of this call


class Recorder:
    def __init__(self, eng, clock=time.perf_counter):
        self.eng = eng
        self.clock = clock
        self.calls: List[Call] = []
        self.first: Dict[int, float] = {}
        self.last: Dict[int, float] = {}
        self.produced: Dict[int, int] = {}
        self.compacted: set = set()
        self._plen: Dict[int, int] = {}
        self._target: Dict[int, int] = {}
        self._live: List[int] = []
        self._orig = {k: getattr(eng, k) for k in
                      ("prefill_batch", "decode_chunk", "compact_fused")}
        eng.prefill_batch = self._prefill
        eng.decode_chunk = self._decode
        eng.compact_fused = self._compact

    def detach(self) -> None:
        """Give the engine its own methods back."""
        for k, fn in self._orig.items():
            setattr(self.eng, k, fn)

    def begin(self, rids, prompt_lens, targets) -> None:
        """The next ``generate`` call serves ``rids`` (targets already
        clipped at ``n_max``)."""
        self._live = list(rids)
        for g, p, t in zip(rids, prompt_lens, targets):
            self._plen[g], self._target[g] = int(p), int(t)
            self.produced[g] = 0

    def _span(self, kind: str):
        return jax.profiler.TraceAnnotation(f"bench.{kind}#{len(self.calls)}")

    def _prefill(self, prompts):
        n = len(self.calls)
        with self._span("prefill"):
            t0 = self.clock()
            out = self._orig["prefill_batch"](prompts)
            t1 = self.clock()
        lens = [len(p) for p in prompts]
        self.calls.append(Call("prefill", n, t0, t1, out[3],
                               self.eng.step_log[-1]["seq"], lens,
                               len(prompts)))
        for g in self._live[:len(prompts)]:
            self.first[g] = t1
            self.produced[g] = 1
            if self._target[g] <= 1:
                self.last[g] = t1
        return out

    def _decode(self, cache, kv_lens, tokens, produced, targets, steps,
                **kw):
        n = len(self.calls)
        with self._span("decode_chunk"):
            t0 = self.clock()
            out = self._orig["decode_chunk"](cache, kv_lens, tokens, produced,
                                             targets, steps, **kw)
            t1 = self.clock()
        # generate reads both right after; numpy copies are cached on the
        # arrays, so reading them here adds no transfer
        prod = np.asarray(out[3])
        act = np.asarray(out[6])
        work, made = [], 0
        for i, g in enumerate(self._live):
            a = int(act[:, i].sum())
            if a:
                work.append((self._plen[g] + self.produced[g] - 1, a))
                made += a
            self.produced[g] = int(prod[i])
            if a and self.produced[g] >= self._target[g]:
                self.last[g] = t1
        self.calls.append(Call("decode_chunk", n, t0, t1,
                               int(tokens.shape[0]), steps, work, made,
                               len(self.eng.step_log) - 1))
        return out

    def _compact(self, cache, kv_lens, tokens, produced, targets, n_live,
                 slot_keys=None):
        n = len(self.calls)
        # the engine keeps the slots whose counters say they are not done,
        # in slot order; read its counters, not the harness's targets
        still = np.asarray(produced) < np.asarray(targets)
        self._live = [g for i, g in enumerate(self._live) if still[i]]
        self.compacted.update(self._live)
        with self._span("compact"):
            t0 = self.clock()
            out = self._orig["compact_fused"](cache, kv_lens, tokens,
                                              produced, targets, n_live,
                                              slot_keys)
            t1 = self.clock()
        self.calls.append(Call("compact", n, t0, t1, out[3], int(n_live)))
        return out

