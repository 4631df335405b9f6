"""A run with the timed path broken underneath must come out not correct.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

Each test drives a whole run of the tiny cell (``data/``: the qwen2.5-3b
smoke variant, float32, one layer) on the CPU through ``run_cell``,
which is everything ``run.py`` does after its look for a chip, with one
fault planted in the program.  The tiny cell checks every request it
served, so a fault that touches any request shows.  The exchange between
chips has no fault to plant: every cell runs on one chip.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import run_cell  # noqa: E402
from spec import load_cell  # noqa: E402

DATA = HERE / "data"
SEED = 2 ** 31 + 12345


def _run(name="tiny.chat", control=False):
    cell = load_cell(name, DATA / "BENCHMARK.json", DATA)
    return run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                    log=lambda s: None, control=control)


@pytest.mark.parametrize("name", ["tiny.chat", "tiny.offline"])
def test_a_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("name", ["tiny.chat", "tiny.offline"])
def test_the_control_in_the_programs_place_is_not_correct(name):
    """The reference one precision below the configuration's (bfloat16
    for this float32 cell) in the program's place: the run's own checks
    judge its first choices, and the run comes out not correct, while
    the program's gap on the same positions stays within the limit."""
    out = _run(name, control=True)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] == out["gaps"]["control"] > gap["limit"]
    assert out["gaps"]["program"] <= gap["limit"]
    assert out["failed"] > 0


def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    import repro.serving.engine as engine
    decode_step = engine.decode_step

    def stale(cfg, params, cache, tokens, kv_lens, ctx=None, **kw):
        logits, _ = decode_step(cfg, params, cache, tokens, kv_lens, **kw)
        return logits, cache

    monkeypatch.setattr(engine, "decode_step", stale)
    out = _run()
    assert not out["correct"]
    assert out["failed"] > 0


def test_half_of_the_batch_left_out(monkeypatch):
    """The second half of every batch is prefilled with the first half's
    prompts: its rows are never computed from their own inputs.  The
    offline cell's batches are all full."""
    import repro.serving.engine as engine
    prefill_batch = engine.Engine.prefill_batch

    def half(self, prompts):
        h = (len(prompts) + 1) // 2
        return prefill_batch(self, prompts[:h] + prompts[:len(prompts) - h])

    monkeypatch.setattr(engine.Engine, "prefill_batch", half)
    out = _run("tiny.offline")
    assert not out["correct"]
    assert out["failed"] > 0


def test_a_token_altered_where_it_is_produced(monkeypatch):
    import jax.numpy as jnp
    import repro.serving.engine as engine
    guarded_argmax = engine._guarded_argmax

    def off_by_one(logits):
        tok, bad = guarded_argmax(logits)
        return ((tok + 1) % logits.shape[-1]).astype(jnp.int32), bad

    monkeypatch.setattr(engine, "_guarded_argmax", off_by_one)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_one_token_short(monkeypatch):
    import repro.serving.engine as engine
    generate = engine.Engine.generate

    def short(self, prompts, targets, **kw):
        return generate(self, prompts, [max(int(t) - 1, 1) for t in targets],
                        **kw)

    monkeypatch.setattr(engine.Engine, "generate", short)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["wrong_token_counts"]["value"] > 0
    assert np.isfinite(out["checks"]["max_logit_gap"]["value"])
