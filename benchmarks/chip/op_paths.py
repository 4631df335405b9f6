"""The op path of each device operation of a traced run.

On the chip a device event names its HLO instruction (``%reshape.304 =
...``) and carries no op path (the ``tf_op`` that ``trace_reduce.load``
looks for is absent), so a scope the program opened with
``jax.named_scope`` (``jit(decode_chunk)/.../kv_cache/...``) cannot be
read from the event.  The profiler stores with the trace each program's
optimised HLO (the ``Hlo Proto`` stat of the ``/host:metadata`` plane,
one per ``<module>(<program id>)``), whose instructions carry their op
path (``OpMetadata.op_name``); each device plane's ``XLA Modules`` line
says which program ran when.  ``load`` reads both: the HLO with the
small protobuf reader below (the field numbers of ``xplane.proto`` and
``xla/service/hlo.proto``), the module events with ``ProfileData``.
"""

from __future__ import annotations

import bisect
import glob
from typing import Dict, Iterator, List, Tuple


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message; a
    length-delimited value is a memoryview into ``b``."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane")
        yield key >> 3, v


def _first(msg, number: int):
    return next((v for f, v in _fields(msg) if f == number), None)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{"<module>(<program id>)": {instruction: op path}}`` from the
    HLO protos of the ``/host:metadata`` plane."""
    out = {}
    for f, plane in _fields(xspace):                  # XSpace.planes
        if f != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        stat_names = {}
        for g, entry in _fields(plane):               # XPlane.stat_metadata
            if g == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1)] = _text(_first(meta, 2))
        for g, entry in _fields(plane):               # XPlane.event_metadata
            if g != 4:
                continue
            meta = _first(entry, 2)
            module = _text(_first(meta, 2))
            for h, stat in _fields(meta):             # XEventMetadata.stats
                if h == 5 and stat_names.get(_first(stat, 1)) == "Hlo Proto":
                    out[module] = _instructions(_first(stat, 6))
    return out


def _instructions(hlo_proto) -> Dict[str, str]:
    paths = {}
    hlo_module = _first(hlo_proto, 1)                 # HloProto.hlo_module
    for f, comp in _fields(hlo_module):               # .computations
        if f != 3:
            continue
        for g, inst in _fields(comp):                 # .instructions
            if g != 2:
                continue
            name = path = None
            for h, v in _fields(inst):
                if h == 1:                            # .name
                    name = _text(v)
                elif h == 7:                          # .metadata.op_name
                    path = _text(_first(v, 2))
            if name:
                paths[name] = path or ""
    return paths


class OpPaths:
    """Looks up the op path of an operation of ``trace_reduce.load``'s
    ``ops`` (``(start, end, label, chip)``)."""

    def __init__(self, modules: Dict[int, List[tuple]],
                 paths: Dict[str, Dict[str, str]]):
        self.modules = {c: sorted(m) for c, m in modules.items()}
        self.starts = {c: [m[0] for m in ms]
                       for c, ms in self.modules.items()}
        self.paths = paths

    def of(self, op) -> str:
        start, _, label, chip = op[:4]
        ms = self.modules.get(chip, [])
        i = bisect.bisect_right(self.starts.get(chip, []), start) - 1
        if i < 0 or start >= ms[i][1]:
            return ""
        instruction = label.split(" = ", 1)[0].split("|", 1)[0].lstrip("%")
        return self.paths.get(ms[i][2], {}).get(instruction, "")


def load(trace_dir: str) -> OpPaths:
    """The op paths of the newest trace under ``trace_dir`` (none where
    there is no trace).  Chips are numbered as ``trace_reduce.load``
    numbers them: device planes with operations, in plane order."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return OpPaths({}, {})
    with open(files[-1], "rb") as fh:
        raw = fh.read()
    modules: Dict[int, List[tuple]] = {}
    chip = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        runs, found = [], False
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
            elif line.name == "XLA Ops":
                found = found or any(True for _ in line.events)
        if found:
            modules[chip] = runs
            chip += 1
    return OpPaths(modules, hlo_op_paths(raw))
