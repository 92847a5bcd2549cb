"""Core precision engine: mode ladder, limb algebra, auto-mode, rounding.

Validates the paper's central claims at the numeric level:
  * error decreases monotonically with precision mode (Table 9 / Fig 17)
  * k-limb mode error ~ 2^-8k on well-conditioned inputs
  * auto-mode picks cheap modes for integer-valued data (Fig 7)
  * GRTE rounding (Eq. 10) behaves between truncation and RNE
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypothesis_compat import given, settings, st  # property tests skip w/o hypothesis

from repro.core import (
    MODE_LIMBS,
    MODE_PASSES,
    DoubleF32,
    Mode,
    auto_mode,
    classify,
    df32_from_f32,
    mode_mismatch_error,
    mp_einsum,
    mp_matmul,
    mp_matmul_runtime,
    quantize_mantissa,
)
from repro.core import limb as limb_lib
from repro.core.rmpm import one_limb_stats

F32_LADDER = (Mode.M8, Mode.M16, Mode.M24)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


class TestLimbSplit:
    def test_three_limbs_reconstruct_f32_exactly(self, rng):
        x = _rand(rng, 128, 64)
        rec = limb_lib.reconstruct(limb_lib.split_limbs(x, 3))
        assert np.array_equal(np.asarray(rec), np.asarray(x))

    def test_limb_residual_shrinks_geometrically(self, rng):
        x = _rand(rng, 256)
        errs = []
        for k in (1, 2, 3):
            rec = limb_lib.reconstruct(limb_lib.split_limbs(x, k))
            errs.append(float(jnp.max(jnp.abs(rec - x)) / jnp.max(jnp.abs(x))))
        assert errs[0] < 2**-7
        assert errs[1] < 2**-15
        assert errs[2] == 0.0

    @pytest.mark.parametrize("fn", ["split_limbs", "auto_mode"])
    def test_limbs_round_in_integer_domain(self, fn):
        # On the TPU, XLA may keep a bf16 cast at f32 inside a fusion, which
        # zeroes x - f32(bf16(x)) and silently turns M24 into M8.  Limbs are
        # therefore rounded on the bit pattern: no bf16 -> f32 round trip may
        # feed a residual, and each limb costs one bitcast.
        x = jnp.ones((8, 16), jnp.float32)
        trace = {
            "split_limbs": lambda: jax.make_jaxpr(
                lambda v: limb_lib.split_limbs(v, 3))(x),
            "auto_mode": lambda: jax.make_jaxpr(auto_mode)(x, x),
        }[fn]()
        prims = [e.primitive.name for e in trace.jaxpr.eqns]
        widen = [e for e in trace.jaxpr.eqns
                 if e.primitive.name == "convert_element_type"
                 and e.invars[0].aval.dtype == jnp.bfloat16]
        assert not widen
        assert prims.count("bitcast_convert_type") >= 3

    def test_product_terms_karatsuba_truncation(self):
        # |{(i,j): i+j<k}| = k(k+1)/2 — the retained Karatsuba economy.
        for k in (1, 2, 3, 4, 6):
            terms = limb_lib.limb_product_terms(k)
            assert len(terms) == k * (k + 1) // 2
            assert all(i + j < k for i, j in terms)
            # ordered high-order (small magnitude) first
            orders = [i + j for i, j in terms]
            assert orders == sorted(orders, reverse=True)

    def test_df32_limbs_extend_past_f32(self, rng):
        hi = _rand(rng, 64)
        lo = hi * np.float32(2**-26) * _rand(rng, 64)
        x = DoubleF32(hi, lo)
        limbs = limb_lib.split_limbs(x, 6)
        assert limbs.shape == (6, 64)
        # 6 limbs must reconstruct hi+lo past f32 fidelity (sum in f64 —
        # reconstruct() itself returns f32 and would cap at 2^-24).
        rec6 = np.asarray(limbs.astype(jnp.float32), np.float64).sum(axis=0)
        err = np.abs(rec6 - (np.asarray(hi, np.float64) + np.asarray(lo, np.float64)))
        assert (err / np.abs(np.asarray(hi, np.float64))).max() < 2**-38


class TestModeLadder:
    def test_error_monotone_in_mode(self, rng):
        a, b = _rand(rng, 96, 128), _rand(rng, 128, 80)
        ref = np.asarray(jnp.dot(a, b)).astype(np.float64)
        scale = np.abs(ref).max()
        errs = {}
        for mode in F32_LADDER:
            out = np.asarray(mp_matmul(a, b, mode), np.float64)
            errs[mode] = np.abs(out - ref).max() / scale
        assert errs[Mode.M8] > errs[Mode.M16] > errs[Mode.M24]
        assert errs[Mode.M8] < 2**-7
        assert errs[Mode.M16] < 2**-15
        assert errs[Mode.M24] < 2**-21  # f32-accumulation limited

    def test_high_modes_beat_f32(self, rng):
        a, b = _rand(rng, 48, 256), _rand(rng, 256, 32)
        A, B = df32_from_f32(a), df32_from_f32(b)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        scale = np.abs(ref).max()
        prev = np.abs(np.asarray(mp_matmul(a, b, Mode.M24), np.float64) - ref).max() / scale
        for mode, bound in ((Mode.M32, 2**-28), (Mode.M48, 2**-35)):
            out = mp_matmul(A, B, mode)
            assert isinstance(out, DoubleF32)
            o64 = np.asarray(out.hi, np.float64) + np.asarray(out.lo, np.float64)
            err = np.abs(o64 - ref).max() / scale
            assert err < bound
            assert err < prev
            prev = err

    def test_einsum_matches_matmul(self, rng):
        a, b = _rand(rng, 32, 64), _rand(rng, 64, 16)
        out_e = mp_einsum("mk,kn->mn", a, b, Mode.M16)
        out_m = mp_matmul(a, b, Mode.M16)
        np.testing.assert_array_equal(np.asarray(out_e), np.asarray(out_m))

    def test_native_traces_highest_precision(self):
        # a default-precision f32 dot is one bf16 pass on the TPU; 'native'
        # promises f32 fidelity, so its dot must ask for HIGHEST
        a, b = jnp.ones((4, 8)), jnp.ones((8, 3))
        jaxpr = jax.make_jaxpr(
            lambda a_, b_: mp_einsum("mk,kn->mn", a_, b_, impl="native"))(a, b)
        dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
        assert len(dots) == 1
        assert dots[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2

    def test_batched_lhs(self, rng):
        a = _rand(rng, 4, 6, 32)
        b = _rand(rng, 32, 24)
        out = mp_matmul(a, b, Mode.M24)
        assert out.shape == (4, 6, 24)
        ref = np.asarray(jnp.einsum("bsk,kn->bsn", a, b))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


class TestOneLimbOperand:
    """A bf16 operand is its own single exact limb: the engine splits only
    the other operand and contracts its stacked limbs in one pass."""

    #: (equation, shape of a, shape of b): the plain matmul and the two
    #: decode attention contractions (q x K cache, probabilities x V cache)
    EQS = [
        ("mk,kn->mn", (24, 64), (64, 16)),
        ("bqhgd,bkhd->bhgqk", (3, 1, 2, 2, 32), (3, 48, 2, 32)),
        ("bhgqk,bkhd->bhgqd", (3, 2, 2, 1, 48), (3, 48, 2, 32)),
    ]

    @staticmethod
    def _dots(fn, *args):
        eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
        return ([e for e in eqns if e.primitive.name == "dot_general"],
                [e for e in eqns if e.primitive.name == "bitcast_convert_type"])

    @pytest.mark.parametrize("k", [1, 2, 3, 4, 6])
    @pytest.mark.parametrize("eq,sa,sb", EQS)
    def test_bf16_split_is_itself_then_zeros(self, rng, eq, sa, sb, k):
        x = _rand(rng, *sb).astype(jnp.bfloat16)
        limbs = limb_lib.split_limbs(x, k)
        assert np.array_equal(np.asarray(limbs[0]).view(np.uint16),
                              np.asarray(x).view(np.uint16))
        assert not np.asarray(limbs[1:]).view(np.uint16).any()  # +0 only

    @pytest.mark.parametrize("side", ["a", "b"])
    @pytest.mark.parametrize("mode", F32_LADDER)
    @pytest.mark.parametrize("eq,sa,sb", EQS)
    def test_matches_f32_upcast(self, rng, eq, sa, sb, mode, side):
        a, b = _rand(rng, *sa), _rand(rng, *sb)
        if side == "a":
            a = a.astype(jnp.bfloat16)
        else:
            b = b.astype(jnp.bfloat16)
        out = mp_einsum(eq, a, b, mode)
        ref = mp_einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32), mode)
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        assert err <= 2**-21
        dots, _ = self._dots(lambda x, y: mp_einsum(eq, x, y, mode), a, b)
        assert len(dots) == 1  # the other operand's limbs, stacked

    @pytest.mark.parametrize("mode", F32_LADDER)
    @pytest.mark.parametrize("eq,sa,sb", EQS)
    def test_bf16_times_bf16_is_one_plain_pass(self, rng, eq, sa, sb, mode):
        a = _rand(rng, *sa).astype(jnp.bfloat16)
        b = _rand(rng, *sb).astype(jnp.bfloat16)
        dots, bitcasts = self._dots(lambda x, y: mp_einsum(eq, x, y, mode), a, b)
        assert len(dots) == 1 and not bitcasts  # no split at all
        ref = mp_einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32), mode)
        np.testing.assert_array_equal(np.asarray(mp_einsum(eq, a, b, mode)),
                                      np.asarray(ref))

    @pytest.mark.parametrize("mode", F32_LADDER)
    def test_counts_sites_and_saved_passes(self, rng, mode):
        eq, sa, sb = self.EQS[1]
        a, b = _rand(rng, *sa), _rand(rng, *sb).astype(jnp.bfloat16)
        k = MODE_LIMBS[mode]
        snap = dataclasses.replace(one_limb_stats())
        jax.make_jaxpr(lambda x, y: mp_einsum(eq, x, y, mode))(a, b)
        got = one_limb_stats().since(snap)
        n_terms = k * (k + 1) // 2
        assert (got.sites, got.einsums_saved, got.passes_saved) == (
            1, n_terms - 1, n_terms - k)

    @pytest.mark.parametrize("mode", [Mode.M32, Mode.M48])
    @pytest.mark.parametrize("eq,sa,sb", EQS[1:])
    def test_compensated_modes_keep_the_full_split(self, rng, eq, sa, sb, mode):
        hi = _rand(rng, *sa)
        a = DoubleF32(hi, hi * np.float32(2**-26))
        b = _rand(rng, *sb).astype(jnp.bfloat16)
        snap = dataclasses.replace(one_limb_stats())
        out = mp_einsum(eq, a, b, mode)
        ref = mp_einsum(eq, a, b.astype(jnp.float32), mode)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        dots, _ = self._dots(lambda y: mp_einsum(eq, a, y, mode), b)
        assert len(dots) == MODE_PASSES[mode]
        assert one_limb_stats().since(snap).sites == 0


class TestRuntimeReconfiguration:
    def test_switch_equals_static(self, rng):
        a, b = _rand(rng, 32, 48), _rand(rng, 48, 16)
        for mode in F32_LADDER:
            rt = mp_matmul_runtime(a, b, jnp.int32(int(mode)))
            static = mp_matmul(a, b, mode)
            np.testing.assert_array_equal(np.asarray(rt), np.asarray(static))

    def test_one_executable_no_recompile(self, rng):
        # Mode is a traced scalar: one lowering serves every mode (the FPGA
        # paper's "no re-synthesis at run time").
        a, b = _rand(rng, 16, 32), _rand(rng, 32, 8)
        fn = jax.jit(mp_matmul_runtime)
        outs = [np.asarray(fn(a, b, jnp.int32(m))) for m in (1, 2, 3)]
        assert fn._cache_size() == 1
        ref = np.asarray(jnp.dot(a, b))
        errs = [np.abs(o - ref).max() for o in outs]
        assert errs[0] > errs[1] > errs[2]

    def test_auto_mode_integers_select_m8(self, rng):
        ai = jnp.asarray(rng.integers(0, 127, (32, 32)).astype(np.float32))
        bi = jnp.asarray(rng.integers(0, 127, (32, 32)).astype(np.float32))
        assert int(auto_mode(ai, bi)) == int(Mode.M8)
        # and the M8 product of small integers is EXACT (paper's
        # "integer-level precision" claim for low modes)
        out = mp_matmul_runtime(ai, bi, Mode.AUTO)
        ref = np.asarray(ai, np.float64) @ np.asarray(bi, np.float64)
        np.testing.assert_array_equal(np.asarray(out, np.float64), ref)

    def test_auto_mode_full_precision_floats(self, rng):
        a, b = _rand(rng, 32, 32), _rand(rng, 32, 32)
        assert int(auto_mode(a, b)) == int(Mode.M24)

    def test_auto_mode_with_tolerance_relaxes(self, rng):
        a, b = _rand(rng, 32, 32), _rand(rng, 32, 32)
        assert int(auto_mode(a, b, tol=2**-6)) < int(Mode.M24)


class TestRounding:
    @pytest.mark.parametrize("keep", [0, -1, -5])
    def test_nonpositive_keep_bits_rejected(self, keep):
        # satellite regression: the oracle clamped keep_bits from above
        # (min(keep_bits, 23)) but not from below — keep_bits <= 0 made
        # drop > 23 and the mask/carry corrupted exponent and sign
        x = jnp.asarray(np.float32([1.5, -2.25, 3.0]))
        with pytest.raises(ValueError, match="keep_bits must be >= 1"):
            quantize_mantissa(x, keep)

    def test_keep_one_bit_stays_a_float(self):
        # the smallest legal width must still return a sane coarse float
        # (sign and exponent untouched up to the documented rounding carry)
        x = jnp.asarray(np.float32([1.9, -1.9, 0.7]))
        q = np.asarray(quantize_mantissa(x, 1, "trunc"))
        assert np.all(np.sign(q) == np.sign(np.asarray(x)))
        assert np.all(np.abs(q) <= np.abs(np.asarray(x)))
        assert np.all(np.isfinite(q))

    @given(st.integers(1, 22), st.sampled_from(["trunc", "rne", "grte"]))
    @settings(max_examples=30, deadline=None)
    def test_error_bounded_by_kept_bits(self, keep, rounding):
        rng = np.random.default_rng(keep)
        x = jnp.asarray(rng.standard_normal(256).astype(np.float32))
        q = quantize_mantissa(x, keep, rounding)
        rel = np.abs(np.asarray(q) - np.asarray(x)) / np.abs(np.asarray(x))
        # trunc: < 2^-keep ; round-up/RNE: <= 2^-keep (worst case one ULP)
        assert rel.max() <= 2.0**-keep

    def test_grte_matches_paper_truth_table(self):
        # Eq. 10: rnd = G & (R | T | E).  Craft mantissa patterns directly.
        def f32_from_bits(mant23):
            return np.uint32((127 << 23) | mant23).view(np.float32)  # 1.mant

        keep = 7
        drop = 23 - keep
        cases = [
            # (dropped-field bits, expect round-up)
            (0b1000000000000000, True),   # G=1 R=0 E=0 T=0 -> G&(R|T|E)=0? No:
            (0b1100000000000000, True),   # G=1 R=1 -> up
            (0b1010000000000000, True),   # G=1 E=1 -> up
            (0b1000000000000001, True),   # G=1 T=1 -> up
            (0b0111111111111111, False),  # G=0 -> never up
            (0b0000000000000000, False),
        ]
        # correction: first case G=1, R=T=E=0 -> rnd = 0 (no round-up)
        cases[0] = (0b1000000000000000, False)
        for dropped, expect_up in cases:
            mant = (0b0101010 << drop) | dropped
            x = jnp.asarray([f32_from_bits(mant)])
            q = np.asarray(quantize_mantissa(x, keep, "grte")).view(np.uint32)[0]
            kept = (int(q) >> drop) & 0x7F
            base = 0b0101010
            assert kept == base + (1 if expect_up else 0), (
                f"dropped={dropped:016b} expect_up={expect_up} kept={kept:07b}"
            )

    def test_rounding_preserves_specials(self):
        x = jnp.asarray([np.inf, -np.inf, np.nan, 0.0, -0.0], jnp.float32)
        q = np.asarray(quantize_mantissa(x, 7, "grte"))
        assert np.isinf(q[0]) and q[0] > 0
        assert np.isinf(q[1]) and q[1] < 0
        assert np.isnan(q[2])
        assert q[3] == 0 and q[4] == 0

    @given(st.sampled_from([3, 7, 11, 15, 19]))
    @settings(max_examples=10, deadline=None)
    def test_grte_error_at_most_one_ulp_worse_than_rne(self, keep):
        rng = np.random.default_rng(keep)
        x = jnp.asarray((rng.standard_normal(512) * 10).astype(np.float32))
        q_rne = np.asarray(quantize_mantissa(x, keep, "rne"), np.float64)
        q_grte = np.asarray(quantize_mantissa(x, keep, "grte"), np.float64)
        x64 = np.asarray(x, np.float64)
        # GRTE is a cheap scheme; its error must stay within 1 ULP of RNE's.
        ulp = 2.0**-keep * np.abs(x64)
        assert (np.abs(q_grte - x64) <= np.abs(q_rne - x64) + ulp + 1e-30).all()


class TestExceptionSignals:
    def test_classify_flags(self):
        x = jnp.asarray([0.0, np.inf, np.nan, 1e-40, 1.0], jnp.float32)
        c = classify(x)
        assert bool(c["zero"][0]) and bool(c["infinity"][1]) and bool(c["nan"][2])
        assert bool(c["denormal"][3]) and not bool(c["denormal"][4])

    def test_mode_mismatch_signal(self):
        assert bool(mode_mismatch_error(1, 2))
        assert not bool(mode_mismatch_error(3, 3))


class TestPropertyBased:
    @given(
        st.integers(1, 3),
        st.integers(1, 64),
        st.integers(1, 64),
        st.integers(1, 64),
    )
    @settings(max_examples=20, deadline=None)
    def test_limb_matmul_error_bound_random_shapes(self, k, m, kd, n):
        rng = np.random.default_rng(m * 1000 + kd * 10 + n)
        a = jnp.asarray(rng.standard_normal((m, kd)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((kd, n)).astype(np.float32))
        out = np.asarray(mp_matmul(a, b, Mode(k)), np.float64)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        # Frobenius-relative bound: c * 2^-8k * ||a|| ||b|| per entry
        row = np.linalg.norm(np.asarray(a, np.float64), axis=1)[:, None]
        col = np.linalg.norm(np.asarray(b, np.float64), axis=0)[None, :]
        bound = 4.0 * 2.0 ** (-8 * k) * row * col + 1e-6
        assert (np.abs(out - ref) <= bound).all()

    @given(st.integers(0, 10))
    @settings(max_examples=10, deadline=None)
    def test_scaling_invariance(self, p):
        # Limb split is exponent-aligned per element: scaling by 2^p is exact.
        rng = np.random.default_rng(p)
        a = jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32))
        s = np.float32(2.0**p)
        out1 = np.asarray(mp_matmul(a * s, b, Mode.M16))
        out2 = np.asarray(mp_matmul(a, b, Mode.M16)) * s
        np.testing.assert_array_equal(out1, out2)
