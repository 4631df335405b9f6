"""The readers of the engine's own record, against counts made by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

``decode_host_gap_ms`` reads the ``decode_chunk`` phase stamps (dispatch,
ready) of the engine's ``step_log``; ``decode_kv_ms`` reads the device
operations under the program's ``kv_cache`` scope.  The trace below, in
nanoseconds, labels operations as the TPU trace does (the HLO
instruction's text, and no op path after the ``|``); their op paths come
from the program's HLO (``op_paths``), here one program over the window:

    spans  window [0,100]  decode_chunk#1 [4,60] (4 steps)
           decode_chunk#2 [62,90] (2 steps)
    ops    while.1 [10,50], unscoped, around the next three
           scatter.2 [12,16] kv_cache
           ragged_decode_attention.3 [14,20] kv_cache    dot.4 [20,30] ffn
           reshape.5 [32,35] kv_cache
           fused_compact.6 [5,9], a gather under the scope, in chunk 1
           scatter.7 [64,68] kv_cache    scatter.8 [92,96] kv_cache,
           outside every chunk

The kv_cache union inside the chunks: [12,20] + [32,35] + [64,68] = 15 ns
over 6 steps.
"""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import op_paths  # noqa: E402
import trace_reduce as tr  # noqa: E402
from engine_io import Call, Recorder  # noqa: E402
from harness import RunData, metric_reader  # noqa: E402
from spec import load_cell  # noqa: E402

DATA = HERE / "data"
PATH = "jit(decode_chunk)/while/body/while/body/"
KERNEL = "kv_cache/jit(ragged_decode_attention)/"
PATHS = {"while.1": "jit(decode_chunk)/while",
         "scatter.2": f"{PATH}kv_cache/scatter",
         "ragged_decode_attention.3": f"{PATH}{KERNEL}pallas_call",
         "dot.4": f"{PATH}ffn/dot_general",
         "reshape.5": f"{PATH}{KERNEL}reshape",
         "fused_compact.6": "jit(fused_compact)/kv_cache/pallas_call",
         "scatter.7": f"{PATH}kv_cache/scatter",
         "scatter.8": f"{PATH}kv_cache/scatter"}
SPANS = {1: (10, 50), 2: (12, 16), 3: (14, 20), 4: (20, 30), 5: (32, 35),
         6: (5, 9), 7: (64, 68), 8: (92, 96)}
OPS = [(*SPANS[int(i.rsplit(".", 1)[1])],
        f"%{i} = bf16[4,128]{{1,0}} op()|", 0) for i in PATHS]


@pytest.fixture
def tiny():
    return load_cell("tiny.chat", DATA / "BENCHMARK.json", DATA)


@pytest.fixture(autouse=True)
def program_paths(monkeypatch):
    """The op paths the trace's HLO would give, for one program that ran
    over the whole window."""
    paths = op_paths.OpPaths({0: [(0, 100, "jit_decode_chunk(7)")]},
                             {"jit_decode_chunk(7)": PATHS})
    monkeypatch.setattr(op_paths, "load", lambda trace_dir: paths)


def _run(cell, calls, step_log, ops=OPS, spans=None):
    rec = Recorder.__new__(Recorder)
    rec.calls = calls
    ops = sorted(ops)
    trace = {"ops": ops, "busy": tr.busy(ops),
             "spans": spans or {"bench.window": (0, 100),
                                "bench.decode_chunk#1": (4, 60),
                                "bench.decode_chunk#2": (62, 90)}}
    return RunData(cell, None, 0.0, 0.0, 1.0, [], rec, {}, step_log, trace,
                   (0, 100))


def _chunk(gen, dispatch, ready):
    return {"kind": "decode_chunk", "batch": 1, "steps": 1,
            "seconds": (ready - dispatch) * 1e-9, "gen": gen,
            "phases": [("decode_chunk", dispatch, ready)]}


def _host_gap_run(tiny, step_log=None):
    """Chunks 1-3 of generate 0, 5-6 of generate 1 (6 traced), 7-8 of
    generate 2 (8 returns after the window)."""
    if step_log is None:
        step_log = [
            {"kind": "prefill", "batch": 1, "seq": 16, "seconds": 1e-7,
             "gen": 0, "phases": [("prefill", 0, 90)]},
            _chunk(0, 100, 200), _chunk(0, 250, 400), _chunk(0, 470, 500),
            {"kind": "compact", "gen": 0},
            _chunk(1, 1000, 1100), _chunk(1, 1200, 1300),
            _chunk(2, 2000, 2100), _chunk(2, 2500, 2600)]
    t1 = {8: 1.5}
    calls = [Call("prefill", 0, 0.0, 0.1, 1, 16, [3], 1, log=0)] + [
        Call("decode_chunk", n, 0.1, t1.get(n, 0.2), 1, 1, [(3, 1)], 1,
             log=n) for n in (1, 2, 3, 5, 6, 7, 8)]
    spans = {"bench.window": (0, 100), "bench.decode_chunk#6": (10, 20)}
    return _run(tiny, calls, step_log, spans=spans)


def test_host_gap_reads_boundaries_inside_one_generate(tiny):
    """1->2 and 2->3 count (50 and 70 ns); 3->5 crosses generate calls;
    5->6 touches a traced chunk; 7->8 ends after the window."""
    run = _host_gap_run(tiny)
    assert [c.n for c, _ in run.traced_calls("decode_chunk")] == [6]
    assert metric_reader("decode_host_gap_ms").read(run) == \
        pytest.approx(1e-6 * 60)


def test_host_gap_leaves_out_a_boundary_next_to_a_traced_chunk(tiny):
    run = _host_gap_run(tiny)
    reader = metric_reader("decode_host_gap_ms")
    assert reader.boundaries(run, traced=True) == []
    run.trace["spans"]["bench.decode_chunk#5"] = (5, 9)
    assert reader.boundaries(run, traced=True) == [100]
    run.trace["spans"].pop("bench.decode_chunk#5")
    untraced = reader.read(run)
    run.trace["spans"].pop("bench.decode_chunk#6")
    # 5->6 (100 ns) now counts too: (50 + 70 + 100) / 3
    assert metric_reader("decode_host_gap_ms").read(run) == \
        pytest.approx(1e-6 * 220 / 3) != untraced


def test_kv_time_per_step_counts_nested_operations_once(tiny):
    calls = [Call("decode_chunk", 1, 0.1, 0.2, 1, 4, [(3, 4)], 4, log=0),
             Call("decode_chunk", 2, 0.2, 0.3, 1, 2, [(7, 2)], 2, log=1)]
    run = _run(tiny, calls, [])
    assert metric_reader("decode_kv_ms").read(run) == \
        pytest.approx(1e-6 * 15 / 6)


def test_kv_time_leaves_out_compaction(tiny):
    calls = [Call("decode_chunk", 1, 0.1, 0.2, 1, 4, [(3, 4)], 4, log=0)]
    gather_only = [o for o in OPS if o[2].startswith(("%while", "%fused"))]
    assert metric_reader("decode_kv_ms").read(
        _run(tiny, calls, [], ops=gather_only)) is None
    # chunk 1 alone: [12,20] + [32,35] over 4 steps, the gather's [5,9]
    # left out
    assert metric_reader("decode_kv_ms").read(_run(tiny, calls, [])) == \
        pytest.approx(1e-6 * 11 / 4)


@pytest.mark.parametrize("name", ["decode_host_gap_ms", "decode_kv_ms"])
def test_a_batch_cells_reader_reads_as_its_namesake(tiny, name):
    run = _host_gap_run(tiny)
    run.trace["spans"]["bench.decode_chunk#1"] = (4, 60)
    base = metric_reader(name).read(run)
    assert base is not None
    assert metric_reader(name + ".batch").read(run) == base


def test_readers_that_find_nothing_return_none(tiny):
    # a program that records no phases (as before the engine had them)
    # and has no kv_cache scope
    bare = [{k: v for k, v in e.items() if k not in ("gen", "phases")}
            for e in _host_gap_run(tiny).step_log]
    run = _host_gap_run(tiny, bare)
    run.trace["ops"] = [o for o in OPS
                        if "kv_cache" not in PATHS[o[2][1:].split(" ")[0]]]
    for name in ("decode_host_gap_ms", "decode_host_gap_ms.batch",
                 "decode_kv_ms", "decode_kv_ms.batch"):
        assert metric_reader(name).read(run) is None


def test_op_paths_come_from_the_hlo_stored_with_the_trace(tmp_path):
    """The profiler keeps each program's HLO in the ``/host:metadata``
    plane, with the op path of every instruction."""
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("kv_cache"):
            return jnp.sin(x) * 2

    step.__name__ = "decode_chunk"
    fn = jax.jit(step)
    fn(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        fn(jnp.ones(8)).block_until_ready()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    programs = op_paths.hlo_op_paths(path.read_bytes())
    mine = [p for name, p in programs.items()
            if name.startswith("jit_decode_chunk(")]
    assert mine and any("jit(decode_chunk)/kv_cache/" in v
                        for v in mine[0].values())


def test_an_operation_outside_every_program_has_no_path():
    paths = op_paths.OpPaths({0: [(10, 20, "m(1)")]},
                             {"m(1)": {"copy.1": "jit(f)/kv_cache/copy"}})
    assert paths.of((12, 13, "%copy.1 = f32[2] copy()|", 0)) == \
        "jit(f)/kv_cache/copy"
    assert paths.of((25, 26, "%copy.1 = f32[2] copy()|", 0)) == ""
    assert paths.of((12, 13, "%copy.2 = f32[2] copy()|", 0)) == ""
