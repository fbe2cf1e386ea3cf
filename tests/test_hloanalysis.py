"""Trip-count-aware HLO walker: validated against programs with known costs."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import hloanalysis, rmetric


def _cost_of(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return hloanalysis.analyse_hlo_text(txt)


class TestFlops:
    def test_plain_matmul(self):
        m, k, n = 64, 128, 32
        x = jnp.ones((m, k))
        y = jnp.ones((k, n))
        cost = _cost_of(lambda a, b: a @ b, x, y)
        assert cost.flops == pytest.approx(2 * m * k * n, rel=0.01)

    def test_scan_multiplies_flops(self):
        """The whole point: XLA's cost analysis counts the body once; the
        walker multiplies by the trip count."""
        m = 32
        x = jnp.ones((m, m))
        trips = 17

        def fn(x):
            def body(c, _):
                return c @ x, None
            out, _ = jax.lax.scan(body, x, None, length=trips)
            return out

        cost = _cost_of(fn, x)
        want = 2 * m ** 3 * trips
        assert cost.flops == pytest.approx(want, rel=0.05)
        # and XLA's own analysis under-reports (it counts a while body
        # once):
        xla_cost = jax.jit(fn).lower(x).compile().cost_analysis()
        xla_flops, _ = rmetric.cost_analysis_scalars(xla_cost)
        assert xla_flops < want * 0.2

    def test_nested_scan(self):
        m = 16
        x = jnp.ones((m, m))

        def fn(x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ x, None
                ci, _ = jax.lax.scan(inner, c, None, length=3)
                return ci, None
            out, _ = jax.lax.scan(outer, x, None, length=5)
            return out

        cost = _cost_of(fn, x)
        assert cost.flops == pytest.approx(2 * m ** 3 * 15, rel=0.05)

    def test_grad_adds_backward_dots(self):
        m = 32
        x = jnp.ones((m, m))
        w = jnp.ones((m, m))
        cost_f = _cost_of(lambda w: (x @ w).sum(), w)
        cost_g = _cost_of(jax.grad(lambda w: ((x @ w) ** 2).sum()), w)
        assert cost_g.flops >= 2 * cost_f.flops


class TestBytes:
    def test_scan_body_slice_accounting(self):
        """Reading one (m, m) slice per iteration must count slice bytes,
        not the full stacked buffer, per iteration."""
        t, m = 8, 32
        stack = jnp.ones((t, m, m))

        def fn(stack):
            def body(c, sl):
                return c + sl, None
            out, _ = jax.lax.scan(body, jnp.zeros((m, m)), stack)
            return out

        cost = _cost_of(fn, stack)
        # traffic should be O(t * m*m * 4 * const), far below t * full-stack
        assert cost.bytes < t * stack.size * 4 * 0.75
        assert cost.bytes > t * m * m * 4  # at least reads each slice


class TestDtypes:
    def test_shape_bytes(self):
        f = hloanalysis._shape_bytes_from_str
        assert f("f32[2,3]") == 24
        assert f("bf16[10]") == 20
        assert f("pred[8]") == 8
        assert f("(f32[2], s32[4])") == 8 + 16
        assert f("token[]") == 0
