"""Strassen block matmul (paper C4): correctness, FLOP economy, engine leaves."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st  # property tests skip w/o hypothesis

from repro.core import Mode, mp_matmul
from repro.core.strassen import flops_ratio, leaf_products, strassen_matmul


class TestCorrectness:
    @pytest.mark.parametrize("depth", [0, 1, 2])
    def test_matches_classical(self, rng, depth):
        a = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((64, 80)).astype(np.float32))
        out = np.asarray(strassen_matmul(a, b, depth=depth, align=8))
        ref = np.asarray(a) @ np.asarray(b)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-4)

    @given(st.integers(1, 50), st.integers(1, 50), st.integers(1, 50))
    @settings(max_examples=15, deadline=None)
    def test_arbitrary_shapes_padded(self, m, k, n):
        rng = np.random.default_rng(m + 100 * k + 10000 * n)
        a = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        out = np.asarray(strassen_matmul(a, b, depth=1, align=4))
        ref = np.asarray(a) @ np.asarray(b)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_rmpm_leaf(self, rng):
        # paper's full stack: Strassen outside, multi-precision engine inside
        a = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
        out = np.asarray(mp_matmul(a, b, Mode.M16, strassen_depth=1))
        ref = np.asarray(a) @ np.asarray(b)
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert rel < 2**-12  # M16 ladder with Strassen conditioning slack


class TestEconomy:
    def test_leaf_products(self):
        assert [leaf_products(d) for d in range(4)] == [1, 7, 49, 343]

    def test_flops_ratio(self):
        assert flops_ratio(1) == pytest.approx(7 / 8)
        assert flops_ratio(2) == pytest.approx(49 / 64)

    @pytest.mark.parametrize("depth", [1, 2])
    def test_hlo_dot_count_is_7_pow_depth(self, depth):
        # The compiled graph must contain exactly 7^depth leaf dots —
        # the paper's "7 multiplications instead of 8" at every level.
        a = jax.ShapeDtypeStruct((64 * 2**depth, 64 * 2**depth), jnp.float32)
        def fn(x, y):
            return strassen_matmul(x, y, depth=depth, align=64)

        hlo = jax.jit(fn).lower(a, a).as_text()
        assert hlo.count("dot_general") == 7**depth

    def test_hlo_flops_reduced(self):
        # cost_analysis FLOPs at depth 1 must be < classical (adds overhead
        # included) — the compute-roofline lever used in section Perf.
        n = 512
        a = jax.ShapeDtypeStruct((n, n), jnp.float32)
        classical = jax.jit(lambda x, y: jnp.dot(x, y)).lower(a, a).compile()
        strassen = (
            jax.jit(lambda x, y: strassen_matmul(x, y, depth=1, align=64))
            .lower(a, a)
            .compile()
        )
        def flops(compiled):
            return compiled.cost_analysis()["flops"]

        fc = flops(classical)
        fs = flops(strassen)
        assert fs < fc
        # 7/8 on the dots plus O(n^2) adds: allow [0.85, 0.95]
        assert 0.80 < fs / fc < 0.95
