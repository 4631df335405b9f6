"""The traffic generator: every seed offers the same work.

    python3 -m pytest benchmarks/chip/tests/test_traffic.py -q
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import traffic as tf  # noqa: E402

MIX = {"arrivals": {"kind": "backlog", "count": 256},
       "prompt": {"kind": "lognormal", "median": 512, "sigma": 0.6,
                  "lo": 128, "hi": 1024},
       "output": {"kind": "lognormal", "median": 32, "sigma": 0.8,
                  "lo": 1, "hi": 4096},
       "policy": {"kind": "elastic", "b_max": 4, "n_max": 128},
       "lead_in_s": 0}
SEEDS = (1, 2 ** 31 + 7, 1843981681)


def _lengths(mix, seed):
    reqs = tf.make_requests(mix, None, 50.0, seed, 1000)
    return (np.array([len(r.prompt) for r in reqs]),
            np.array([r.target for r in reqs]))


@pytest.mark.parametrize("order", ["shuffle", "blocks"])
def test_every_seed_offers_the_same_lengths(order):
    mix = dict(MIX, order=order, block=16)
    p0, o0 = _lengths(mix, SEEDS[0])
    for seed in SEEDS[1:]:
        p, o = _lengths(mix, seed)
        assert sorted(p) == sorted(p0) and sorted(o) == sorted(o0)
        assert not np.array_equal(p, p0)      # another order


def test_blocked_order_gives_every_prefix_the_whole_mix():
    """A backlog serves a prefix of its requests in the window: under
    ``blocks`` each prefix of whole blocks has a mean length within 3% of
    the whole mix's, for every seed."""
    whole_p = tf.lengths(MIX["prompt"], 256).mean()
    whole_o = tf.lengths(MIX["output"], 256).clip(max=128).mean()
    for seed in SEEDS:
        p, o = _lengths(dict(MIX, order="blocks", block=16), seed)
        for k in (48, 80, 128):
            assert abs(p[:k].mean() / whole_p - 1) < 0.03
            assert abs(o[:k].clip(max=128).mean() / whole_o - 1) < 0.03


def test_blocked_order_deals_one_value_of_each_stratum_per_block():
    v = np.arange(96)
    out = tf.blocked_order(v, 8, np.random.default_rng(3))
    assert sorted(out) == list(v)
    for run in out.reshape(-1, 8):
        assert sorted(run // 12) == list(range(8))
    with pytest.raises(ValueError):
        tf.blocked_order(np.arange(97), 8, np.random.default_rng(3))


def test_a_compacting_backlog_warms_every_smaller_bucket():
    """A backlog starts every batch at ``b_max``, but elastic compaction
    carries a batch down to the smaller buckets: their decode chunks and
    compactions are warmed too."""
    ecfg = SimpleNamespace(min_bucket=1, max_batch=4, prompt_bucket=64,
                           max_seq=8192, decode_chunk=32)
    assert {b for b, _ in tf.prefill_shapes(MIX, ecfg)} == {4}
    assert {b for b, _ in tf.decode_shapes(MIX, ecfg)} == {1, 2, 4}
    assert tf.compaction_shapes(MIX, ecfg) == [(2, 1), (4, 1), (4, 2)]
    fixed = dict(MIX, output={"kind": "fixed", "n": 16})
    assert {b for b, _ in tf.decode_shapes(fixed, ecfg)} == {4}
    assert tf.compaction_shapes(fixed, ecfg) == []
