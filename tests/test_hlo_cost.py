"""The trip-count-corrected HLO cost model vs XLA's own cost_analysis on
unrolled graphs (where cost_analysis is trustworthy)."""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.utils.hlo import analyze_hlo_text, parse_hlo_module


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def _xla_flops(compiled) -> float:
    return float(compiled.cost_analysis()["flops"])


def test_scan_flops_match_unrolled():
    n, steps = 64, 10

    def body(c, _):
        return c @ c, None

    def f_scan(x):
        return lax.scan(body, x, None, length=steps)[0]

    def f_unroll(x):
        for _ in range(steps):
            x = x @ x
        return x

    x = jnp.ones((n, n), jnp.float32)
    cs, cu = _compile(f_scan, x), _compile(f_unroll, x)
    ps = analyze_hlo_text(cs.as_text())
    pu = analyze_hlo_text(cu.as_text())
    truth = steps * 2 * n ** 3
    assert abs(ps.flops - truth) / truth < 0.01
    assert abs(pu.flops - truth) / truth < 0.01
    # XLA's own analysis undercounts the scan (documents why we parse):
    assert _xla_flops(cs) < truth / 2


def test_nested_scan_flops():
    n, outer, inner = 32, 4, 6

    def inner_body(c, _):
        return c @ c, None

    def outer_body(c, _):
        c2, _ = lax.scan(inner_body, c, None, length=inner)
        return c2, None

    def f(x):
        return lax.scan(outer_body, x, None, length=outer)[0]

    x = jnp.ones((n, n), jnp.float32)
    cost = analyze_hlo_text(_compile(f, x).as_text())
    truth = outer * inner * 2 * n ** 3
    assert abs(cost.flops - truth) / truth < 0.02


def test_unrolled_flops_match_cost_analysis():
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    c = _compile(f, a, b)
    mine = analyze_hlo_text(c.as_text())
    theirs = _xla_flops(c)
    assert abs(mine.flops - theirs) / theirs < 0.2


def test_dynamic_slice_bytes_not_full_operand():
    """Slicing one layer from a stacked [G, ...] param must charge the slice,
    not the stack (the bug class this parser exists to avoid)."""
    big = jnp.ones((64, 256, 256), jnp.float32)

    def f(x, i):
        return lax.dynamic_slice(x, (i, 0, 0), (1, 256, 256)).sum()

    cost = analyze_hlo_text(_compile(f, big, jnp.int32(3)).as_text())
    # full operand would be 64 MB; slice accounting must stay ~2x256KB
    assert cost.bytes_accessed < 4e6


def test_while_trip_count_parsed():
    def f(x):
        return lax.scan(lambda c, _: (c + 1, None), x, None, length=17)[0]

    comps = parse_hlo_module(_compile(f, jnp.zeros((8,))).as_text())
    trips = [i.trip_count for c in comps.values()
             for i in c.instructions.values() if i.opcode == "while"]
    assert 17 in trips
