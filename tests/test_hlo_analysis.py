"""HLO cost parser: validated against XLA cost_analysis and analytics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.launch import hlo_analysis as H


def test_flops_match_cost_analysis_loop_free():
    """On a loop-free program the parser's dot FLOPs == XLA's count."""
    def f(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in [(64, 128), (128, 256), (256, 32)]]
    c = jax.jit(f).lower(*args).compile()
    want = c.cost_analysis()["flops"]
    got = H.analyze(c.as_text())["flops"]
    # the parser counts dots only; elementwise tanh adds a small delta
    assert abs(got - want) / want < 0.01, (got, want)


def test_scan_flops_multiply_by_trip_count():
    def body(c, _):
        return jnp.tanh(c @ c.T @ c), ()

    def f(x):
        out, _ = lax.scan(body, x, None, length=7)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    got = H.analyze(c.as_text())["flops"]
    per_iter = 2 * 2 * 64 ** 3
    assert abs(got - 7 * per_iter) / (7 * per_iter) < 0.01

    def g(x):
        for _ in range(7):
            x, _ = body(x, None)
        return x

    c2 = jax.jit(g).lower(x).compile()
    got2 = H.analyze(c2.as_text())["flops"]
    assert abs(got - got2) / got2 < 0.01


def test_nested_scan_multipliers_compose():
    def inner(c, _):
        return c @ c, ()

    def outer(c, _):
        c, _ = lax.scan(inner, c, None, length=3)
        return c, ()

    def f(x):
        out, _ = lax.scan(outer, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    got = H.analyze(c.as_text())["flops"]
    want = 15 * 2 * 32 ** 3
    assert abs(got - want) / want < 0.01


def test_roofline_terms_dominance():
    parsed = {"flops": 197e12, "bytes": 819e9 * 2, "collective_bytes": 0.0}
    t = H.roofline_terms(parsed, model_flops_per_device=197e12 * 0.5)
    assert t["dominant"] == "memory"
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["roofline_fraction"] == pytest.approx(0.25)


def test_shape_bytes_parses_tuples_and_comments():
    b, e = H._shape_bytes_elems("(f32[2,3]{1,0}, bf16[4], pred[8])")
    assert b == 24 + 8 + 8 and e == 6 + 4 + 8
