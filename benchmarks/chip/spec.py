"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its model family, its traffic mix and its cell file, each found by name.

    configs/<config>.json    the published configuration, as it is run;
                             its "family" key names the family
    families/<family>.py     sizes, weights, plain forward and work
                             counts of one kind of model
    traffic/<traffic>.json   arrivals, length distributions, batching
                             policy
    cells/<cell>.json        the cell's offered rate and correctness limits
    metrics/<metric>.py      one reader per per-layer metric

A new cell, configuration, family, mix or metric is new files plus new
entries in BENCHMARK.json; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
# the system under test is the checkout's own package
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


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
    # families/<family>.py: ``shape``, ``check_program``, ``make_weights``,
    # ``logits``, ``prefill_flops``, ``decode_flops``, ``kernels``
    family: ModuleType

    @property
    def shape(self):
        """The family's frozen sizes, read from the configuration file."""
        return self.family.shape(self.config)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@functools.cache
def _module(path: Path) -> ModuleType:
    """A family's module, loaded once per process, so that its jitted
    functions compile once."""
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the class is made
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_family(config: dict, config_file: Path, root: Path) -> ModuleType:
    """The family the configuration's "family" key names:
    ``families/<family>.py`` under ``root``, else beside the harness."""
    name = config.get("family")
    if not isinstance(name, str) or not name:
        raise ValueError(f"{config_file}: no \"family\" key naming the "
                         "model family")
    for d in (root, HERE):
        path = d / "families" / f"{name}.py"
        if path.is_file():
            return _module(path.resolve())
    raise ValueError(f"{config_file}: family {name!r} is unknown: no "
                     f"families/{name}.py under {root} or {HERE}")


def load_cell(name: str, bench_file: Path = REPO / "BENCHMARK.json",
              root: Path = HERE) -> Cell:
    """The cell ``name``; raises KeyError for a name the benchmark lacks,
    and ValueError for a configuration whose family is missing or
    unknown.  Configuration files are found from the benchmark file's
    directory; traffic, cell and family files under ``root``."""
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = bench_file.parent / configs[entry["config"]]["file"]
    config = json.loads(cfg_file.read_text())
    return Cell(
        name=name, config_name=entry["config"],
        traffic_name=entry["traffic"], chips=int(entry["chips"]),
        config=config,
        traffic=json.loads(
            (root / "traffic" / f"{entry['traffic']}.json").read_text()),
        cell=json.loads((root / "cells" / f"{name}.json").read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        family=load_family(config, cfg_file, root))
