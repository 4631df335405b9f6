"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic mix and its cell file, each found by name.

    configs/<config>.json   the published configuration, as it is run
    traffic/<traffic>.json  arrivals, length distributions, batching policy
    cells/<cell>.json       the cell's offered rate and correctness limits
    metrics/<metric>.py     one reader per per-layer metric

A new cell, configuration, mix or metric is new files plus new entries in
BENCHMARK.json; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
# the system under test is the checkout's own package
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The published sizes the harness builds weights and the reference
    from; read from the configuration file alone."""
    d: int
    ffn: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    rope_theta: float
    tied: bool
    qkv_bias: bool
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "ModelShape":
        heads = int(c["num_attention_heads"])
        return cls(
            d=int(c["hidden_size"]), ffn=int(c["intermediate_size"]),
            layers=int(c["num_hidden_layers"]), heads=heads,
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
            vocab=int(c["vocab_size"]), eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]),
            tied=bool(c["tie_word_embeddings"]),
            qkv_bias=bool(c["qkv_bias"]),
            dtype=c["torch_dtype"])


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: tuple
    per_layer: tuple

    @property
    def shape(self) -> ModelShape:
        return ModelShape.from_config(self.config)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = REPO / "BENCHMARK.json",
              root: Path = HERE) -> Cell:
    """The cell ``name``; raises KeyError for a name the benchmark lacks.
    Configuration files are found from the benchmark file's directory,
    traffic and cell files under ``root``."""
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    return Cell(
        name=name, config_name=entry["config"],
        traffic_name=entry["traffic"], chips=int(entry["chips"]),
        config=json.loads(
            (bench_file.parent / cfg_entry["file"]).read_text()),
        traffic=json.loads(
            (root / "traffic" / f"{entry['traffic']}.json").read_text()),
        cell=json.loads((root / "cells" / f"{name}.json").read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))
