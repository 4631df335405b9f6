"""The engine's own record of its host phases (``Engine.step_log``) and
the stable names of what it runs on the device.

Every entry carries the id ``gen`` of the ``generate`` call it belongs to
and its phases as ``(phase, start_ns, end_ns)`` on
``time.perf_counter_ns()``; every phase is also a profiler span
``engine.<phase>``, which one offset per trace maps onto the stamps."""

import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serving.engine import Engine, EngineConfig

ECFG = EngineConfig(max_batch=4, max_seq=128, prompt_bucket=16,
                    decode_chunk=4)
PROMPTS = [np.arange(5, dtype=np.int32) + i for i in range(3)]
# the short replies finish in the first chunk, so the rest compact 4 -> 1
TARGETS = [11, 2, 3]
# the chunk's own entry adds the fallback count's ``readback``
BOUNDARY = ["compact", "upload", "decode_chunk", "readback", "readback",
            "bookkeeping"]


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"), num_layers=2,
                              decode_cache_update="scatter")
    return Engine(cfg, ECFG)


def _generate(eng):
    return eng.generate(PROMPTS, TARGETS, elastic=True, return_tokens=True)


def _calls(eng, first):
    """The ``step_log`` entries from ``first`` on, grouped under the
    ``generate`` entry each call appended on return."""
    calls, inner = [], []
    for e in eng.step_log[first:]:
        if e["kind"] == "generate":
            calls.append((e, inner))
            inner = []
        else:
            inner.append(e)
    assert not inner
    return calls


def _records(entries):
    return [r for e in entries for r in e.get("phases", ())]


def test_every_phase_lies_inside_its_generate(engine):
    first = len(engine.step_log)
    _generate(engine)
    _generate(engine)
    for gen, inner in _calls(engine, first):
        (span,) = [r for r in gen["phases"] if r[0] == "generate"]
        _, lo, hi = span
        assert lo <= hi
        for name, start, end in _records([gen] + inner):
            assert lo <= start <= end <= hi, name


def test_entries_carry_the_id_of_their_generate(engine):
    first = len(engine.step_log)
    _generate(engine)
    _generate(engine)
    calls = _calls(engine, first)
    ids = [gen["gen"] for gen, _ in calls]
    assert len(set(ids)) == 2 and ids == sorted(ids)
    for gen, inner in calls:
        assert gen["requests"] == 3 and gen["batch"] == 4
        kinds = [e["kind"] for e in inner]
        assert kinds[0] == "prefill" and "compact" in kinds
        assert {e["gen"] for e in inner} == {gen["gen"]}
    engine.prefill_batch(PROMPTS)
    assert engine.step_log[-1]["gen"] is None


def test_phases_cover_the_call_in_order(engine):
    first = len(engine.step_log)
    _generate(engine)
    (gen, inner), = _calls(engine, first)
    names = [r[0] for r in sorted(_records([gen] + inner),
                                  key=lambda r: (r[1], -r[2]))]
    assert names[:3] == ["generate", "prefill", "first_token"]
    body = names[3:]
    chunks = sum(e["kind"] == "decode_chunk" for e in inner)
    assert chunks >= 2
    assert body == BOUNDARY * chunks + ["compact"]


def test_prefill_and_decode_chunk_leave_their_own_entry_last(engine):
    """A wrapper around either method, as the benchmark's recorder is,
    finds the call's own entry last when it returns, inside ``generate``
    too, with the fields it reads."""
    seen = []
    prefill, chunk = engine.prefill_batch, engine.decode_chunk

    def wrap(fn):
        def call(*a, **kw):
            out = fn(*a, **kw)
            seen.append(dict(engine.step_log[-1]))
            return out
        return call

    engine.prefill_batch, engine.decode_chunk = wrap(prefill), wrap(chunk)
    try:
        _generate(engine)
    finally:
        del engine.prefill_batch, engine.decode_chunk
    assert [e["kind"] for e in seen[:2]] == ["prefill", "decode_chunk"]
    pre = seen[0]
    assert pre["seq"] == 16 and pre["batch"] == 4 and pre["seconds"] > 0
    for e in seen[1:]:
        assert e["kind"] == "decode_chunk"
        assert e["steps"] >= 1 and e["batch"] in (1, 2, 4)
        (name, dispatched, ready), (then, *_) = e["phases"]
        assert (name, then) == ("decode_chunk", "readback")
        assert e["seconds"] == pytest.approx((ready - dispatched) * 1e-9)


def test_decode_chunk_entries_hold_no_seq(engine):
    first = len(engine.step_log)
    _generate(engine)
    chunks = [e for e in engine.step_log[first:]
              if e["kind"] == "decode_chunk"]
    assert chunks and all("seq" not in e for e in chunks)


def _engine_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name[len("engine."):], ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events
                           if ev.name.startswith("engine.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def test_spans_match_the_stamps_under_the_profiler(engine, tmp_path):
    """The same tokens with the profiler on; every ``engine.*`` event in
    the ``.xplane.pb`` is a phase of ``step_log``, and after one offset,
    fitted on the first event, starts and ends where its stamps say."""
    plain = _generate(engine)["tokens"]
    first = len(engine.step_log)
    with jax.profiler.trace(str(tmp_path)):
        traced = _generate(engine)["tokens"]
    assert traced == plain
    events = _engine_events(tmp_path)
    records = sorted(_records(engine.step_log[first:]),
                     key=lambda r: (r[1], -r[2]))
    assert [e[0] for e in events] == [r[0] for r in records]
    offset = events[0][1] - records[0][1]
    for (name, start, end), (_, lo, hi) in zip(events, records):
        assert abs(start - offset - lo) < 1e6, name
        assert abs(end - offset - hi) < 1e6, name


def test_the_decode_chunk_program_carries_stable_names(engine):
    """Op paths read ``jit(decode_chunk)/...`` with the model's scopes;
    the ragged kernel and its cache view sit under ``kv_cache``."""
    cfg = dataclasses.replace(engine.cfg, decode_attention_impl="ragged")
    eng = Engine(cfg, ECFG, params=engine.params)
    b = 2
    i32 = jnp.zeros((b,), jnp.int32)
    hlo = eng._get_decode_chunk(b, 2).lower(
        eng.params, eng.new_cache(b), i32, i32, i32, i32,
        jnp.zeros((b, 2), jnp.uint32)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any(p.startswith("jit(decode_chunk)/while/") for p in paths)
    assert not any("jit(fn)" in p for p in paths)
    scopes = {s for p in paths for s in p.split("/")}
    assert {"kv_cache", "ffn", "logits", "sample"} <= scopes
    kernel = [p for p in paths if "jit(ragged_decode_attention)" in p]
    assert kernel and all("/kv_cache/" in p for p in kernel)
    assert any(p.endswith("kv_cache/scatter") for p in paths)
