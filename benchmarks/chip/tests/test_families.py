"""The model family: the one seam between a configuration file and what
the harness does that depends on the model.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

``data/golden_tiny.json`` holds what the dense code gave for the tiny
configuration before it moved behind the seam, read on that commit: a
checksum of every leaf drawn at two seeds (the reference's tree and the
program's), of the reference's and the control's logits on a fixed
token block, and the work counts at fixed lengths.  Through
``cell.family`` the same seeds give the same bits and the same counts.

``data/families/dense_fused.py`` is a second dense family that exists
only as a file of the test data, named by ``data/configs/tiny_fused.json``
and run through ``run_cell`` as a cell of ``data/BENCHMARK.new_family.json``:
a family is added with no file of the harness edited.
"""

import hashlib
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import run_cell  # noqa: E402
from spec import load_cell  # noqa: E402

DATA = HERE / "data"
GOLDEN = json.loads((DATA / "golden_tiny.json").read_text())
TOKENS = (np.arange(48).reshape(2, 24) * 37 + 11) % 512
NEW = DATA / "BENCHMARK.new_family.json"
SEED = 2 ** 31 + 12345


def _sum(x) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def tiny():
    return load_cell("tiny.chat", DATA / "BENCHMARK.json", DATA)


@pytest.mark.parametrize("seed", sorted(GOLDEN["seeds"]))
def test_weights_and_reference_are_those_of_before(tiny, seed):
    want = GOLDEN["seeds"][seed]
    m = tiny.shape
    ref, prog = tiny.family.make_weights(m, tiny.config, int(seed),
                                         GOLDEN["padded_vocab"])
    assert {k: _sum(v) for k, v in ref.items()} == want["reference"]
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    assert {jax.tree_util.keystr(p): _sum(v) for p, v in flat} == \
        want["program"]
    assert _sum(tiny.family.logits(m, False, ref, TOKENS)) == want["logits"]
    assert _sum(tiny.family.logits(m, True, ref, TOKENS)) == \
        want["control_logits"]


def test_counts_are_those_of_before(tiny):
    want = GOLDEN["counts"]
    fam, m = tiny.family, tiny.shape
    assert fam.prefill_flops(m, want["prefill_lens"]) == want["prefill_flops"]
    work = [tuple(w) for w in want["decode_work"]]
    assert fam.decode_flops(m, work) == want["decode_flops"]
    for n, fb in want["ragged_decode_attention"].items():
        assert list(fam.kernels["ragged_decode_attention"](m, int(n))) == fb


def test_a_family_in_a_new_file_counts_as_the_dense_one(tiny):
    """Two families written apart agree on the work of one model."""
    new = load_cell("tiny_fused.chat", NEW, DATA)
    assert new.family is not tiny.family
    a, b = tiny.family, new.family
    lens, work = [1, 3, 17, 100], [(9, 2), (4, 1), (99, 3)]
    assert b.prefill_flops(new.shape, lens) == a.prefill_flops(tiny.shape,
                                                               lens)
    assert b.decode_flops(new.shape, work) == a.decode_flops(tiny.shape, work)
    for n in (1, 5, 100):
        k = "ragged_decode_attention"
        assert b.kernels[k](new.shape, n) == a.kernels[k](tiny.shape, n)


def _run_new():
    cell = load_cell("tiny_fused.chat", NEW, DATA)
    return run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                    log=lambda s: None)


def test_a_family_in_a_new_file_runs_a_cell():
    out = _run_new()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["checked"]["positions"] > 0


def test_a_fault_in_the_new_familys_forward_is_not_correct(monkeypatch):
    """The new family's forward with RoPE left out: the program serves
    what the configuration states, the reference does not, and the run
    comes out not correct."""
    fam = load_cell("tiny_fused.chat", NEW, DATA).family
    monkeypatch.setattr(fam, "_rope", lambda x, pos, theta: x)
    # a new function, so the fault is traced and not read from the sound
    # forward's trace (JAX caches traces by the Python function)
    forward = fam.logits.__wrapped__
    monkeypatch.setattr(fam, "logits", jax.jit(
        lambda m, control, w, tokens: forward(m, control, w, tokens),
        static_argnums=(0, 1)))
    out = _run_new()
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["failed"] > 0


def _bench_with(tmp_path, config: dict) -> Path:
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "x.json").write_text(json.dumps(config))
    bench = json.loads(NEW.read_text())
    bench["configs"] = [{"name": "x", "file": "configs/x.json"}]
    bench["workloads"] = [{"name": "tiny_fused.chat", "config": "x",
                           "traffic": "chat", "chips": 1}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


@pytest.mark.parametrize("family", [None, "", "no_such_family"])
def test_a_config_without_a_known_family_names_its_file(tmp_path, family):
    config = json.loads((DATA / "configs" / "tiny_fused.json").read_text())
    config.pop("family")
    if family is not None:
        config["family"] = family
    bench = _bench_with(tmp_path, config)
    with pytest.raises(ValueError, match=r"configs/x\.json"):
        load_cell("tiny_fused.chat", bench, DATA)
