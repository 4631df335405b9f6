"""The trace reduction and the operations/bytes functions, against counts
made by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

``data/small_trace.json`` holds six device operations and four host
spans, in nanoseconds, labelled as the TPU trace labels them (the HLO
instruction, then ``|`` and the op path):

    ops    fusion.1 [0,10]  ragged_decode_attention.1 [12,20]
           fusion.2 [15,25]  fused_compact.4 [30,36]
           ragged_decode_attention.2 [40,50]  copy.3 [60,70]
    spans  window [0,100]  generate#0 [5,80]  prefill#0 [5,9]
           decode_chunk#1 [10,55]

The compaction gather ``fused_compact.4`` was dispatched before the chunk
and runs inside its span, as on the chip.  Busy union: [0,10] [12,25]
[30,36] [40,50] [60,70] = 49 ns.  Inside the decode chunk: 13 + 6 + 10 =
29 ns, 23 ns without the gather, of which the ragged kernel 8 + 10 = 18
ns.  Inside generate [5,80]: 5 + 13 + 6 + 10 + 10 = 44 of 75 ns.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import e2e  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic as tf  # noqa: E402
import work  # noqa: E402
from engine_io import Call  # noqa: E402
from harness import RunData, metric_reader  # noqa: E402
from spec import load_cell  # noqa: E402

DATA = HERE / "data"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def trace():
    t = json.loads((DATA / "small_trace.json").read_text())
    t["ops"] = sorted(tuple(o) for o in t["ops"])
    t["spans"] = {k: tuple(v) for k, v in t["spans"].items()}
    t["busy"] = tr.busy(t["ops"])
    return t


@pytest.fixture
def tiny():
    return load_cell("tiny.chat", DATA / "BENCHMARK.json", DATA)


def test_busy_union_and_overlap(trace):
    assert trace["busy"] == [(0, 10), (12, 25), (30, 36), (40, 50),
                             (60, 70)]
    assert tr.length(trace["busy"]) == 49
    assert tr.overlap(trace["busy"], 10, 55) == 29
    assert tr.overlap(trace["busy"], 5, 80) == 44
    assert tr.busy_per_chip(trace["ops"], 0, 100) == 49
    no_gather = tr.busy(tr.excluding(trace["ops"], metric_reader(
        "decode_mfu").COMPACTION))
    assert tr.overlap(no_gather, 10, 55) == 23


def test_kernel_time_inside_a_span(trace):
    """The ragged kernel's time leaves out the compaction gather, the
    other Pallas call that runs inside a decode chunk's span."""
    kernel = tr.matching(trace["ops"], metric_reader(
        "ragged_attn_roofline").KERNEL)
    assert tr.overlap(kernel, 10, 55) == 18
    assert tr.overlap(kernel, 0, 15) == 3
    assert tr.overlap(kernel, 28, 38) == 0


def test_spans_and_breakdown(trace):
    assert tr.spans_of(trace, "decode_chunk") == {1: (10, 55)}
    tops = dict(tr.top_ops(trace["ops"], 0, 100))
    ragged = "ragged_decode_attention|jit(fn)/while/body/" \
        "jit(ragged_decode_attention)/pallas_call"
    assert tops == pytest.approx({
        "fusion": 20e-9, ragged: 18e-9, "copy": 10e-9,
        "fused_compact|jit(fused_compact)/pallas_call": 6e-9})
    gaps = tr.idle_gaps(trace["ops"], trace["spans"], 0, 100)
    assert [g[0] for g in gaps] == ["bench.generate"] + \
        ["bench.decode_chunk"] * 4
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 10e-9, 5e-9, 4e-9,
                                                  2e-9])


def _run(cell, trace, calls):
    from engine_io import Recorder
    rec = Recorder.__new__(Recorder)
    rec.calls = calls
    run = RunData(cell, PEAKS, 0.0, 0.0, 1.0, [], rec, {}, [], trace,
                  (0, 100))
    return run


def test_metric_readers_on_the_small_trace(tiny, trace):
    calls = [Call("prefill", 0, 0.1, 0.2, 1, 16, [3], 1),
             Call("decode_chunk", 1, 0.2, 0.3, 1, 1, [(9, 1)], 1)]
    run = _run(tiny, trace, calls)
    idle = metric_reader("device_idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - 44 / 75))
    # one layer, one live request at KV length 10: bytes bound,
    # (2*4*16*2 + 2*10*2*16*2) B / 819 GB/s over 18 ns of kernel
    share = metric_reader("ragged_attn_roofline").read(run)
    assert share == pytest.approx(100 * (1536 / 819e9) / 18e-9)
    # prefill of a 3-token prompt over 4 ns of busy device time
    mfu = metric_reader("prefill_mfu").read(run)
    assert mfu == pytest.approx(100 * 288256 / (197e12 * 4e-9))
    # one decode step at KV length 10 over the 23 ns the chunk's own ops
    # took (the gather's 6 ns left out): 2*36864 + 2*64*512 + 4*4*16*10
    # FLOPs
    dec = metric_reader("decode_mfu").read(run)
    assert dec == pytest.approx(100 * 141824 / (197e12 * 23e-9))


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_mfu",
                                  "ragged_attn_roofline",
                                  "device_idle_share"])
def test_a_batch_cells_reader_reads_as_its_namesake(tiny, trace, name):
    calls = [Call("prefill", 0, 0.1, 0.2, 1, 16, [3], 1),
             Call("decode_chunk", 1, 0.2, 0.3, 1, 1, [(9, 1)], 1)]
    run = _run(tiny, trace, calls)
    run.step_log = [{"seconds": 0.1, "steps": 1}] * 2
    base = metric_reader(name).read(run)
    assert base is not None
    assert metric_reader(name + ".batch").read(run) == base


def test_a_reader_that_finds_nothing_returns_none(tiny, trace):
    trace["spans"] = {"bench.window": (0, 100)}
    run = _run(tiny, trace, [])
    for name in ("device_idle_share", "ragged_attn_roofline", "prefill_mfu",
                 "decode_mfu", "device_idle_share.batch",
                 "ragged_attn_roofline.batch", "decode_mfu.batch",
                 "tpot_p95_ms.batch"):
        assert metric_reader(name).read(run) is None


def test_work_at_smoke_size(tiny):
    fam, m = tiny.family, tiny.shape
    assert m == fam.ModelShape(64, 128, 1, 4, 2, 16, 512, 1e-6, 1e6, True,
                               True, "float32")
    # 64*(4+2+2)*16 + 4*16*64 + 3*64*128
    assert fam.matmul_params(m) == 36864
    # 2*36864*3 + 2*64*512 + 4*4*16*(3*4/2)
    assert fam.prefill_flops(m, [3]) == 288256
    # one step from KV length 4: 2*36864 + 2*64*512 + 4*4*16*5
    assert fam.decode_flops(m, [(4, 1)]) == 140544
    # flops 4*4*16*5; bytes q+out 2*4*16*2, K+V 2*5*2*16*2
    assert fam.kernels["ragged_decode_attention"](m, 5) == (1280, 896)
    assert list(work.chunk_kv_lens([(9, 2), (4, 1)])) == [10, 11, 5]


def test_every_seed_offers_the_same_work(tiny):
    a = tf.make_requests(tiny.traffic, 8.0, 2.0, 1, 512)
    b = tf.make_requests(tiny.traffic, 8.0, 2.0, 2 ** 31 + 99, 512)
    for field in (lambda r: len(r.prompt), lambda r: r.target):
        assert sorted(map(field, a)) == sorted(map(field, b))
    def gaps(reqs):
        return sorted(np.diff([0.0] + [r.due for r in reqs]))

    assert gaps(a) == pytest.approx(gaps(b))
    assert a[-1].due == pytest.approx(b[-1].due)
    assert [r.due for r in a] != [r.due for r in b]


def test_reachable_programs():
    from types import SimpleNamespace
    ecfg = SimpleNamespace(max_batch=16, max_seq=1024, prompt_bucket=16,
                           min_bucket=1, decode_chunk=32)
    chat = json.loads((HERE.parent / "traffic" / "chat_poisson.json")
                      .read_text())
    shapes = tf.prefill_shapes(chat, ecfg)
    assert (16, 256) in shapes and (1, 16) in shapes
    assert (16, 32) not in shapes          # 9+ prompts all <= 32: never
    assert tf.decode_shapes(chat, ecfg)[:6] == [
        (1, 1), (1, 2), (1, 4), (1, 8), (1, 16), (1, 32)]
    assert (16, 1) in tf.compaction_shapes(chat, ecfg)
    offline = json.loads((HERE.parent / "traffic" / "offline_fixed.json")
                         .read_text())
    assert tf.prefill_shapes(offline, ecfg) == [(16, 256)]
    assert tf.decode_shapes(offline, ecfg) == [
        (16, 1), (16, 2), (16, 4), (16, 8), (16, 16), (16, 32)]
    assert tf.compaction_shapes(offline, ecfg) == []


def test_end_to_end_arithmetic(tiny):
    from engine_io import Recorder
    reqs = [tf.Request(0, 0.0, None, 3), tf.Request(1, 0.5, None, 1),
            tf.Request(2, 0.9, None, 2)]
    rec = Recorder.__new__(Recorder)
    rec.first = {0: 0.2, 1: 0.7}
    rec.last = {0: 0.6, 1: 0.7}
    rec.produced = {0: 3, 1: 1}
    rec.calls = [Call("prefill", 0, 0.1, 0.2, 1, 16, [], 1),
                 Call("decode_chunk", 1, 0.2, 0.6, 1, 2, [], 2),
                 Call("prefill", 2, 0.6, 0.7, 1, 16, [], 1)]
    run = RunData(tiny, None, 0.0, 0.0, 1.0, reqs, rec, {"stop": 1.2}, [])
    # TTFT 0.2, 0.2 and (stop 1.2 - due 0.9) for the one never served
    assert e2e.ttft_s(run, reqs) == pytest.approx([0.2, 0.2, 0.3])
    assert e2e.tpot_s(run, reqs) == pytest.approx([0.2])
    assert e2e.tokens_per_s(run) == 4.0
