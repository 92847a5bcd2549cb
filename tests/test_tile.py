"""Partitioned-SIMD tile kernel: exactness, maps, dispatch, blocking.

Pins the three contracts the tile path is built on (DESIGN.md section
Partitioned tile kernels):

  * uniform maps are BIT-identical to ``mp_matmul(impl='pallas')`` at the
    same blocks, for every f32-ladder mode, every rounding, and degenerate
    shapes on every axis;
  * mixed maps match an independent per-tile oracle bitwise, and
    magnitude-statistics maps stay inside their error budget while using
    cheaper modes for small-magnitude tiles;
  * runtime-bound call sites run ONE fused dispatch (no ``lax.switch``) and
    never retrace across mode changes.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.precision import F32_MODES, MODE_LIMBS, Mode
from repro.core.rmpm import mp_matmul, mp_matmul_runtime, mp_einsum_runtime
from repro.kernels.blocking import ceil_to, clamp_block, pad_to_block
from repro.kernels.tile_matmul.ops import (
    tile_grid,
    tile_matmul,
    tile_matmul_auto,
    tile_matmul_mode,
    tile_matmul_runtime,
)
from repro.kernels.tile_matmul.ref import tile_matmul_ref
from repro.kernels.tile_matmul.tile_policy import (
    dispatch_stats,
    magnitude_map,
    table_map,
    uniform_map,
)

BLK = dict(bm=32, bn=32, bk=64)
BLOCK = (32, 32, 64)


def _ab(rng, m, kd, n):
    a = jnp.asarray(rng.standard_normal((m, kd)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((kd, n)).astype(np.float32))
    return a, b


class TestUniformExactness:
    @pytest.mark.parametrize("mode", F32_MODES)
    @pytest.mark.parametrize(
        "m,kd,n",
        [
            (64, 128, 64),  # block multiples
            (100, 300, 70),  # non-multiple on every axis
            (1, 96, 48),  # M=1 decode row
            (48, 96, 1),  # N=1 vector
            (16, 24, 16),  # K smaller than bk
        ],
    )
    def test_bitwise_vs_pallas(self, rng, mode, m, kd, n):
        a, b = _ab(rng, m, kd, n)
        t = np.asarray(mp_matmul(a, b, mode, impl="tile", block=BLOCK))
        p = np.asarray(mp_matmul(a, b, mode, impl="pallas", block=BLOCK))
        assert (t == p).all()

    @pytest.mark.parametrize("mode", F32_MODES)
    def test_batched_lhs_bitwise(self, rng, mode):
        a = jnp.asarray(rng.standard_normal((2, 3, 48)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((48, 40)).astype(np.float32))
        t = np.asarray(mp_matmul(a, b, mode, impl="tile", block=BLOCK))
        assert t.shape == (2, 3, 40)
        p = np.asarray(
            mp_matmul(a.reshape(6, 48), b, mode, impl="pallas", block=BLOCK)
        )
        assert (t.reshape(6, 40) == p).all()

    @pytest.mark.parametrize("rounding", ["grte", "trunc"])
    def test_grte_prepass_composition(self, rng, rounding):
        # kmax=2 (M16): the pre-pass quantizes to 15 mantissa bits — a real
        # transformation (kmax=3 keeps 23 bits, the f32 identity), so this
        # pins that the tile path composes the rounding pre-pass exactly as
        # the uniform kernel does.
        a, b = _ab(rng, 40, 80, 56)
        t = np.asarray(
            tile_matmul_mode(a, b, Mode.M16, rounding=rounding, **BLK)
        )
        p = np.asarray(
            mp_matmul(a, b, Mode.M16, rounding=rounding, impl="pallas", block=BLOCK)
        )
        assert (t == p).all()

    def test_uniform_map_constructor_matches_mode_path(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        mm = uniform_map(a.shape, b.shape, Mode.M16, **BLK)
        t = np.asarray(tile_matmul(a, b, mm, kmax=2, **BLK))
        p = np.asarray(tile_matmul_mode(a, b, Mode.M16, **BLK))
        assert (t == p).all()


class TestMixedMaps:
    @pytest.mark.parametrize("per_k", [False, True])
    def test_mixed_vs_independent_oracle(self, rng, per_k):
        m, kd, n = 96, 192, 64
        a, b = _ab(rng, m, kd, n)
        grid, (bm, bn, bk) = tile_grid(m, n, kd, **BLK)
        shape = grid if per_k else grid[:2]
        mm = jnp.asarray(rng.integers(1, 4, size=shape), jnp.int32)
        out = np.asarray(tile_matmul(a, b, mm, **BLK))
        ref = np.asarray(
            tile_matmul_ref(
                pad_to_block(a, bm, bk), pad_to_block(b, bk, bn),
                np.asarray(mm), bm=bm, bn=bn, bk=bk,
            )
        )[:m, :n]
        assert (out == ref).all()

    def test_map_shape_validated(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        bad = jnp.ones((5, 5), jnp.int32)
        with pytest.raises(ValueError, match="mode_map shape"):
            tile_matmul(a, b, bad, **BLK)

    def test_magnitude_map_isolates_outlier_tile(self, rng):
        # background ~1e-3, one hot row-tile ~1: only tiles fed by the hot
        # rows need the expensive mode; the budget still holds globally.
        m, kd, n = 96, 128, 64
        a = jnp.asarray(rng.standard_normal((m, kd)).astype(np.float32)) * 1e-3
        a = a.at[:32].set(a[:32] * 1e3)
        b = jnp.asarray(rng.standard_normal((kd, n)).astype(np.float32))
        budget = 2.0**-12
        mm = np.asarray(magnitude_map(a, b, budget, **BLK))
        assert mm.shape == tile_grid(m, n, kd, **BLK)[0][:2]
        assert len(np.unique(mm)) >= 2, "mixed-precision map expected"
        assert mm[0].max() > mm[1:].max(), "hot tiles must get more limbs"
        out = np.asarray(tile_matmul_auto(a, b, budget, **BLK), np.float64)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        # budget is relative to the magnitude envelope S = amax*bmax*K,
        # which upper-bounds max|ref|; measured error must sit inside it
        scale = float(np.abs(a).max()) * float(np.abs(b).max()) * kd
        assert np.abs(out - ref).max() <= budget * scale

    def test_magnitude_map_uniform_data_meets_budget(self, rng):
        a, b = _ab(rng, 128, 128, 128)
        for budget in (2.0**-6, 2.0**-12, 2.0**-20):
            out = np.asarray(tile_matmul_auto(a, b, budget, **BLK), np.float64)
            ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
            scale = float(np.abs(a).max()) * float(np.abs(b).max()) * 128
            assert np.abs(out - ref).max() <= budget * scale

    def test_table_map_broadcasts_scalar(self):
        mm = table_map((64, 128), (128, 64), jnp.int32(2), **BLK)
        assert mm.shape == (2, 2)
        assert (np.asarray(mm) == 2).all()


class TestGradients:
    """The custom VJP replays each forward block product at its own mode."""

    @pytest.mark.parametrize("mode", F32_MODES)
    def test_uniform_grad_bitwise_vs_limb(self, rng, mode):
        a = jnp.asarray(rng.standard_normal((2, 20, 96)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((96, 40)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((2, 20, 40)).astype(np.float32))

        def loss(impl):
            return lambda a_, b_: (mp_matmul(a_, b_, mode, impl=impl,
                                             block=BLOCK) * w).sum()

        gt = jax.grad(loss("tile"), (0, 1))(a, b)
        gp = jax.grad(loss("pallas"), (0, 1))(a, b)
        for t, p in zip(gt, gp):
            assert (np.asarray(t) == np.asarray(p)).all()

    @pytest.mark.parametrize("per_k", [False, True])
    def test_mixed_map_grad_vs_independent_oracle(self, rng, per_k):
        m, kd, n = 64, 192, 96
        a, b = _ab(rng, m, kd, n)
        g = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        grid, (bm, bn, bk) = tile_grid(m, n, kd, **BLK)
        mm = rng.integers(1, 4, size=grid if per_k else grid[:2])
        map3 = mm if per_k else np.broadcast_to(mm[:, :, None], grid)
        _, vjp = jax.vjp(lambda a_, b_: tile_matmul(
            a_, b_, jnp.asarray(mm, jnp.int32), **BLK), a, b)
        da, db = vjp(g)
        # da[i, kk] contracts over j at map[i, j, kk]; db[kk, j] over i
        da_ref = tile_matmul_ref(
            pad_to_block(g, bm, bn), pad_to_block(b.T, bn, bk),
            np.transpose(map3, (0, 2, 1)), bm=bm, bn=bk, bk=bn)[:m, :kd]
        db_ref = tile_matmul_ref(
            pad_to_block(a.T, bk, bm), pad_to_block(g, bm, bn),
            np.transpose(map3, (2, 1, 0)), bm=bk, bn=bn, bk=bm)[:kd, :n]
        assert (np.asarray(da) == np.asarray(da_ref)).all()
        assert (np.asarray(db) == np.asarray(db_ref)).all()


class TestRuntimeDispatch:
    def test_runtime_tile_matches_switch_all_modes(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        for mv in (1, 2, 3):
            t = np.asarray(
                mp_matmul_runtime(a, b, jnp.int32(mv), impl="tile",
                                  block=BLOCK, allow_auto=False)
            )
            p = np.asarray(
                mp_matmul_runtime(a, b, jnp.int32(mv), impl="pallas",
                                  block=BLOCK, allow_auto=False)
            )
            assert (t == p).all()

    def test_single_dispatch_no_switch(self, rng):
        a, b = _ab(rng, 64, 128, 64)

        def tile_fn(a_, b_, s):
            return mp_matmul_runtime(a_, b_, s, impl="tile", block=BLOCK,
                                     allow_auto=False)

        def switch_fn(a_, b_, s):
            return mp_matmul_runtime(a_, b_, s, impl="pallas", block=BLOCK,
                                     allow_auto=False)

        t_stats = dispatch_stats(tile_fn, a, b, jnp.int32(2))
        s_stats = dispatch_stats(switch_fn, a, b, jnp.int32(2))
        assert t_stats == {"switches": 0, "pallas_calls": 1}
        assert s_stats["switches"] == 1

    def test_zero_recompile_across_modes(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        calls = jax.jit(
            lambda a_, b_, s: mp_matmul_runtime(
                a_, b_, s, impl="tile", block=BLOCK, allow_auto=False
            )
        )
        outs = [calls(a, b, jnp.int32(mv)) for mv in (1, 2, 3, 2, 1)]
        jax.block_until_ready(outs)
        assert calls._cache_size() == 1

    def test_runtime_map_changes_zero_recompile(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        grid, _ = tile_grid(64, 64, 128, **BLK)
        f = jax.jit(lambda a_, b_, mm: tile_matmul(a_, b_, mm, **BLK))
        for seed in range(3):
            mm = jnp.asarray(
                np.random.default_rng(seed).integers(1, 4, size=grid[:2]),
                jnp.int32,
            )
            jax.block_until_ready(f(a, b, mm))
        assert f._cache_size() == 1

    def test_einsum_runtime_tile_2d_and_fallback(self, rng):
        a, b = _ab(rng, 64, 128, 64)
        t = np.asarray(
            mp_einsum_runtime("mk,kn->mn", a, b, jnp.int32(2), impl="tile",
                              block=BLOCK)
        )
        p = np.asarray(
            mp_matmul_runtime(a, b, jnp.int32(2), impl="pallas", block=BLOCK,
                              allow_auto=False)
        )
        assert (t == p).all()
        # non-2D contraction: tile falls back to the xla switch, same result
        a3 = jnp.asarray(rng.standard_normal((2, 8, 16)).astype(np.float32))
        b3 = jnp.asarray(rng.standard_normal((2, 16, 8)).astype(np.float32))
        t3 = np.asarray(
            mp_einsum_runtime("bmk,bkn->bmn", a3, b3, jnp.int32(2), impl="tile")
        )
        x3 = np.asarray(
            mp_einsum_runtime("bmk,bkn->bmn", a3, b3, jnp.int32(2), impl="xla")
        )
        assert (t3 == x3).all()

    def test_bound_pmm_sites_fuse_dispatch(self, rng):
        # >= 2 lax.switch call sites replaced by single fused dispatches:
        # two runtime-bound pmm sites -> 0 switches, 2 pallas calls, one
        # compiled executable across all mode pairs, bit-identical to the
        # static pallas execution the switch would have selected.
        from repro.adapt.runtime_policy import bind_modes
        from repro.core.policy import PrecisionPolicy
        from repro.models.layers import pmm
        from repro.plan import clear_plan_cache

        clear_plan_cache()
        pol = PrecisionPolicy(default=Mode.M16, impl="pallas")
        x = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
        w1 = jnp.asarray(rng.standard_normal((128, 96)).astype(np.float32))
        w2 = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))

        def step(x_, w1_, w2_, s1, s2):
            with bind_modes({"mlp_up": s1, "mlp_down": s2}):
                h = pmm(x_, w1_, "mlp_up", pol)
                return pmm(h, w2_, "mlp_down", pol)

        stats = dispatch_stats(step, x, w1, w2, jnp.int32(2), jnp.int32(1))
        assert stats == {"switches": 0, "pallas_calls": 2}

        f = jax.jit(step)
        for m1 in (1, 2, 3):
            for m2 in (1, 2, 3):
                out = f(x, w1, w2, jnp.int32(m1), jnp.int32(m2))
                h = mp_matmul(x, w1, Mode(m1), impl="pallas")
                ref = mp_matmul(h, w2, Mode(m2), impl="pallas")
                assert (np.asarray(out) == np.asarray(ref)).all(), (m1, m2)
        assert f._cache_size() == 1

    def test_xla_plans_keep_switch(self, rng):
        from repro.adapt.runtime_policy import bind_modes
        from repro.core.policy import PrecisionPolicy
        from repro.models.layers import pmm
        from repro.plan import clear_plan_cache

        clear_plan_cache()
        pol = PrecisionPolicy(default=Mode.M16, impl="xla")
        x = jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))

        def step(x_, w_, s):
            with bind_modes({"mlp_up": s}):
                return pmm(x_, w_, "mlp_up", pol)

        stats = dispatch_stats(step, x, w, jnp.int32(2))
        assert stats["switches"] == 1 and stats["pallas_calls"] == 0


class TestBlocking:
    def test_clamp_block_pins(self):
        assert clamp_block(128, 1) == 8  # M=1 decode row -> quantum block
        assert clamp_block(128, 100) == 104  # next multiple of 8, not 100
        assert clamp_block(128, 128) == 128
        assert clamp_block(128, 256) == 128  # dim fills the block: keep it
        assert clamp_block(512, 300) == 304
        assert ceil_to(1, 8) == 8 and ceil_to(16, 8) == 16

    def test_tile_grid_degenerate_shapes(self):
        grid, blocks = tile_grid(1, 64, 128, bm=128, bn=128, bk=512)
        assert blocks == (8, 64, 128)
        assert grid == (1, 1, 1)

    def test_pad_to_block_zero_exact(self, rng):
        x = jnp.asarray(rng.standard_normal((10, 20)).astype(np.float32))
        p = pad_to_block(x, 8, 16)
        assert p.shape == (16, 32)
        assert (np.asarray(p[:10, :20]) == np.asarray(x)).all()
        assert float(np.abs(np.asarray(p[10:])).max()) == 0.0


class TestInterpretDefault:
    """Backend-aware interpret default, verified with a spy on the kernel."""

    def _spy(self, monkeypatch, module, name):
        seen = {}
        import importlib

        mod = importlib.import_module(module)
        orig = getattr(mod, name)

        def wrapper(*args, **kwargs):
            seen["interpret"] = kwargs.get("interpret")
            return orig(*args, **kwargs)

        monkeypatch.setattr(mod, name, wrapper)
        return seen

    def test_limb_matmul_interprets_on_cpu(self, rng, monkeypatch):
        from repro.kernels.limb_matmul import ops as limb_ops

        seen = self._spy(monkeypatch, "repro.kernels.limb_matmul.ops",
                         "limb_matmul_pallas")
        # unique shape so the jitted inner body re-traces and the spy fires
        a, b = _ab(rng, 24, 40, 24)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        limb_ops.limb_matmul(a, b, 2, bm=8, bn=8, bk=8)
        assert seen["interpret"] is True

    def test_limb_matmul_compiles_off_cpu(self, rng, monkeypatch):
        from repro.kernels.limb_matmul import ops as limb_ops

        seen = self._spy(monkeypatch, "repro.kernels.limb_matmul.ops",
                         "limb_matmul_pallas")
        a, b = _ab(rng, 24, 40, 24)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # trace only: Mosaic lowering cannot run on this host, but the
        # interpret flag is resolved OUTSIDE jit, at trace time
        jaxpr = jax.make_jaxpr(
            lambda a_, b_: limb_ops.limb_matmul(a_, b_, 2, bm=8, bn=8, bk=8)
        )(a, b)
        assert seen["interpret"] is False
        assert "pallas_call" in str(jaxpr)

    def test_explicit_override_wins(self, rng, monkeypatch):
        from repro.kernels.limb_matmul import ops as limb_ops

        seen = self._spy(monkeypatch, "repro.kernels.limb_matmul.ops",
                         "limb_matmul_pallas")
        a, b = _ab(rng, 16, 40, 24)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        limb_ops.limb_matmul(a, b, 2, bm=8, bn=8, bk=8, interpret=True)
        assert seen["interpret"] is True

    def test_quantize_interprets_on_cpu(self, rng, monkeypatch):
        from repro.kernels.quantize_mantissa import ops as q_ops

        seen = self._spy(monkeypatch, "repro.kernels.quantize_mantissa.ops",
                         "quantize_mantissa_pallas")
        x = jnp.asarray(rng.standard_normal((9, 11)).astype(np.float32))
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        q_ops.quantize_mantissa_op(x, 7)
        assert seen["interpret"] is True

    def test_tile_matmul_interprets_on_cpu(self, rng, monkeypatch):
        from repro.kernels.tile_matmul import ops as tile_ops

        seen = self._spy(monkeypatch, "repro.kernels.tile_matmul.ops",
                         "tile_matmul_pallas")
        a, b = _ab(rng, 24, 48, 24)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        tile_ops.tile_matmul_mode(a, b, Mode.M16, bm=8, bn=8, bk=16)
        assert seen["interpret"] is True


class TestPlannerTile:
    def test_impl_validation_admits_tile(self):
        from repro.plan import plan_matmul

        p = plan_matmul((64, 64), (64, 64), impl="tile", mode=Mode.M16)
        assert p.impl == "tile"
        with pytest.raises(ValueError, match="unknown impl"):
            plan_matmul((64, 64), (64, 64), impl="mosaic")

    def test_tile_in_tpu_candidates_but_ties_keep_pallas(self):
        from repro.plan import plan_matmul
        from repro.plan.planner import _impl_candidates

        cands = _impl_candidates(Mode.M16, None, "tpu", 2**-12, False, "rne")
        assert "tile" in cands and cands.index("pallas") < cands.index("tile")
        p = plan_matmul((4096, 4096), (4096, 4096), accuracy=2**-12,
                        backend="tpu")
        assert p.impl == "pallas"  # committed baselines stay stable on ties

    def test_map_source_validation(self):
        from repro.plan import plan_matmul

        with pytest.raises(ValueError, match="map_source"):
            plan_matmul((64, 64), (64, 64), map_source="entropy")
        with pytest.raises(ValueError, match="accuracy"):
            plan_matmul((64, 64), (64, 64), map_source="magnitude")
        with pytest.raises(ValueError, match="impl='tile'"):
            plan_matmul((64, 64), (64, 64), accuracy=2**-12,
                        map_source="magnitude", impl="xla")

    def test_magnitude_plan_cache_key_and_execution(self, rng):
        from repro.plan import clear_plan_cache, execute, plan_matmul

        clear_plan_cache()
        uni = plan_matmul((128, 128), (128, 128), accuracy=2**-12)
        mag = plan_matmul((128, 128), (128, 128), accuracy=2**-12,
                          map_source="magnitude")
        assert uni is not mag  # map_source is part of the plan-cache key
        assert mag.impl == "tile" and mag.map_source == "magnitude"
        assert mag.strassen_depth == 0
        a, b = _ab(rng, 128, 128, 128)
        out = np.asarray(execute(mag, a, b), np.float64)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        scale = float(np.abs(a).max()) * float(np.abs(b).max()) * 128
        assert np.abs(out - ref).max() <= 2**-12 * scale

    def test_tile_plan_executes_bitwise_vs_pallas(self, rng):
        from repro.plan import execute, plan_matmul

        a, b = _ab(rng, 96, 128, 64)
        pt = plan_matmul((96, 128), (128, 64), mode=Mode.M24, impl="tile")
        pp = plan_matmul((96, 128), (128, 64), mode=Mode.M24, impl="pallas")
        assert (np.asarray(execute(pt, a, b)) == np.asarray(execute(pp, a, b))).all()

    def test_tune_candidates_include_tile(self):
        from repro.tune.runner import candidates

        cands = candidates(512, 512, 512, "tpu")
        tile = [c for c in cands if c.impl == "tile"]
        assert tile and all(c.block is not None for c in tile)
        assert {int(c.mode) for c in tile} == {int(m) for m in F32_MODES}

    def test_tune_measure_tile(self):
        from repro.tune.runner import Candidate, measure

        rec = measure(64, 64, 64, Candidate(Mode.M16, "tile", 0, (32, 32, 32)),
                      iters=1)
        assert rec.impl == "tile" and rec.rel_err < 2.0**-12
