"""Per-kernel shape/dtype sweeps vs ref.py oracles (interpret mode on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.limb_matmul.limb_matmul import limb_matmul_dd_pallas
from repro.kernels.limb_matmul.ops import limb_matmul
from repro.kernels.limb_matmul.ref import limb_matmul_ref
from repro.kernels.quantize_mantissa.ops import quantize_mantissa_op
from repro.kernels.quantize_mantissa.ref import quantize_mantissa_ref


class TestLimbMatmulKernel:
    @pytest.mark.parametrize("k", [1, 2, 3])
    @pytest.mark.parametrize(
        "m,kd,n", [(32, 64, 32), (100, 300, 70), (17, 33, 9), (128, 128, 128)]
    )
    def test_vs_ref_shapes(self, rng, k, m, kd, n):
        a = jnp.asarray(rng.standard_normal((m, kd)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((kd, n)).astype(np.float32))
        out = np.asarray(limb_matmul(a, b, k, interpret=True, bm=32, bn=32, bk=64))
        ref = np.asarray(limb_matmul_ref(a, b, k))
        # K-tiling reorders the f32 accumulation; tolerance is a few ULP of
        # the result magnitude, not of the mode's precision.
        scale = max(np.abs(ref).max(), 1e-6)
        np.testing.assert_allclose(out / scale, ref / scale, atol=2e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_input_dtypes(self, rng, dtype):
        a = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32)).astype(dtype)
        b = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32)).astype(dtype)
        out = np.asarray(limb_matmul(a, b, 2, interpret=True, bm=32, bn=32, bk=32))
        ref = np.asarray(
            limb_matmul_ref(a.astype(jnp.float32), b.astype(jnp.float32), 2)
        )
        scale = max(np.abs(ref).max(), 1e-6)
        np.testing.assert_allclose(out / scale, ref / scale, atol=2e-6)

    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_grad_within_mode_error(self, rng, k):
        # the backward pass runs the kernel at the forward's limb count, so
        # both cotangents carry that mode's error, no more
        from repro.core.precision import Mode
        from repro.plan.cost import MODE_REL_ERROR

        a = jnp.asarray(rng.standard_normal((2, 20, 96)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((96, 40)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((2, 20, 40)).astype(np.float32))
        got = jax.grad(lambda a_, b_: (limb_matmul(
            a_, b_, k, interpret=True, bm=16, bn=32, bk=32) * w).sum(), (0, 1))(a, b)
        ref = jax.grad(lambda a_, b_: (jnp.einsum(
            "bmk,kn->bmn", a_, b_, precision="highest") * w).sum(), (0, 1))(a, b)
        for g, r in zip(got, ref):
            g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
            assert np.abs(g - r).max() / np.abs(r).max() < MODE_REL_ERROR[Mode(k)]

    def test_batched_lhs(self, rng):
        a = jnp.asarray(rng.standard_normal((2, 3, 48)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((48, 24)).astype(np.float32))
        out = limb_matmul(a, b, 3, interpret=True, bm=8, bn=8, bk=16)
        assert out.shape == (2, 3, 24)
        ref = np.asarray(jnp.einsum("bsk,kn->bsn", a, b))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    def test_grte_rounded_inputs(self, rng):
        a = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
        out = limb_matmul(a, b, 2, rounding="grte", interpret=True, bm=16, bn=16, bk=16)
        ref = np.asarray(a) @ np.asarray(b)
        rel = np.abs(np.asarray(out) - ref).max() / np.abs(ref).max()
        assert rel < 2**-13

    def test_mode_error_ladder_through_kernel(self, rng):
        a = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        scale = np.abs(ref).max()
        errs = []
        for k in (1, 2, 3):
            out = np.asarray(
                limb_matmul(a, b, k, interpret=True, bm=32, bn=32, bk=64), np.float64
            )
            errs.append(np.abs(out - ref).max() / scale)
        assert errs[0] > errs[1] > errs[2]

    def test_dd_variant_returns_pair(self, rng):
        a = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
        hi, lo = limb_matmul_dd_pallas(a, b, 3, bm=32, bn=32, bk=64, interpret=True)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        out = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert rel < 2**-22  # MXU-accumulator-limited (DESIGN.md assumption 8)
        assert np.abs(np.asarray(lo)).max() < np.abs(np.asarray(hi)).max() * 2**-20


class TestQuantizeMantissaKernel:
    @pytest.mark.parametrize("rounding", ["trunc", "rne", "grte"])
    @pytest.mark.parametrize("keep", [1, 5, 7, 15, 20, 22])
    def test_bit_exact_vs_ref(self, rng, rounding, keep):
        x = (rng.standard_normal((57, 131)) * 10 ** rng.integers(-3, 3)).astype(
            np.float32
        )
        out = np.asarray(quantize_mantissa_op(jnp.asarray(x), keep, rounding, interpret=True))
        ref = quantize_mantissa_ref(x, keep, rounding)
        assert np.array_equal(out, ref), f"keep={keep} rounding={rounding}"

    def test_nd_shapes(self, rng):
        x = rng.standard_normal((3, 5, 7, 11)).astype(np.float32)
        out = np.asarray(quantize_mantissa_op(jnp.asarray(x), 7, "grte", interpret=True))
        ref = quantize_mantissa_ref(x, 7, "grte")
        assert out.shape == x.shape
        assert np.array_equal(out, ref.reshape(x.shape))

    def test_specials_passthrough(self):
        x = np.array([np.inf, -np.inf, np.nan, 0.0], np.float32)
        out = np.asarray(quantize_mantissa_op(jnp.asarray(x), 7, "grte", interpret=True))
        assert np.isinf(out[0]) and np.isinf(out[1]) and np.isnan(out[2]) and out[3] == 0

    @pytest.mark.parametrize("keep", [0, -1, -8])
    def test_nonpositive_keep_rejected(self, rng, keep):
        # satellite regression: keep <= 0 used to make drop > 23 so the
        # kept-mask and rounding carry reached the exponent/sign fields and
        # returned garbage instead of an error
        x = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        with pytest.raises(ValueError, match="keep must be >= 1"):
            quantize_mantissa_op(x, keep, "grte", interpret=True)
