"""Substrate tests: optimizer, checkpoint manager, data pipeline, train loop,
serving engine, gradient compression round-trip."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import PackedReader, Prefetcher, SyntheticLM
from repro.optim import adamw
from repro.distributed import compress


class TestAdamW:
    def _quad(self, quantize):
        cfg = adamw.AdamWConfig(
            lr=0.1, warmup_steps=0, total_steps=100, schedule="const",
            weight_decay=0.0, quantize_moments=quantize,
        )
        params = {"w": jnp.asarray([2.0, -3.0, 1.5])}
        state = adamw.init_state(params, cfg)

        @jax.jit
        def step(params, state):
            grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
            return adamw.apply_updates(params, grads, state, cfg)

        for _ in range(60):
            params, state, metrics = step(params, state)
        return params, metrics

    def test_converges(self):
        params, metrics = self._quad(False)
        assert float(jnp.abs(params["w"]).max()) < 0.15
        assert metrics["lr"] == pytest.approx(0.1)

    def test_quantized_moments_converge(self):
        params, _ = self._quad(True)
        assert float(jnp.abs(params["w"]).max()) < 0.3

    def test_quantized_state_is_int8(self):
        cfg = adamw.AdamWConfig(quantize_moments=True)
        params = {"w": jnp.zeros((4, 512))}
        st = adamw.init_state(params, cfg)
        q, scale = st["m"]["w"]
        assert q.dtype == jnp.int8 and q.shape == (4, 2, 256)
        assert scale.shape == (4, 2, 1)

    def test_schedule_warmup_cosine(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110)
        assert float(adamw.schedule_lr(cfg, jnp.int32(5))) == pytest.approx(0.5)
        assert float(adamw.schedule_lr(cfg, jnp.int32(10))) == pytest.approx(1.0)
        assert float(adamw.schedule_lr(cfg, jnp.int32(110))) == pytest.approx(0.0, abs=1e-6)

    def test_grad_clipping(self):
        cfg = adamw.AdamWConfig(grad_clip=1.0, warmup_steps=0, schedule="const")
        params = {"w": jnp.zeros(3)}
        state = adamw.init_state(params, cfg)
        huge = {"w": jnp.asarray([1e4, 1e4, 1e4])}
        _, _, m = adamw.apply_updates(params, huge, state, cfg)
        assert float(m["grad_norm"]) > 1e4  # reported pre-clip


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}, "opt": {"step": jnp.int32(7)}}
        mgr.save(3, state)
        step, restored = mgr.restore()
        assert step == 3
        np.testing.assert_array_equal(restored["params"]["w"], np.arange(6.0).reshape(2, 3))
        assert int(restored["opt"]["step"]) == 7

    def test_keep_k_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        st = {"x": jnp.zeros(1)}
        for s in (1, 2, 3, 4):
            mgr.save(s, st)
        assert mgr.all_steps() == [3, 4]

    def test_async_save_then_wait(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
        mgr.save(1, {"x": jnp.ones(8)})
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_atomicity_no_partial_dirs(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(5, {"x": jnp.ones(2)})
        names = os.listdir(tmp_path)
        assert all(not n.startswith(".tmp") for n in names)

    def test_tuple_state_roundtrip(self, tmp_path):
        # quantized optimizer states contain tuples
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        st = {"m": {"w": (jnp.ones((2, 4), jnp.int8), jnp.ones((2, 1)))}}
        mgr.save(1, st)
        _, r = mgr.restore()
        assert isinstance(r["m"]["w"], tuple) and r["m"]["w"][0].dtype == np.int8


class TestData:
    def test_synthetic_deterministic_and_learnable(self):
        d1 = SyntheticLM(vocab=64, seq_len=16, batch=4, seed=1)
        d2 = SyntheticLM(vocab=64, seq_len=16, batch=4, seed=1)
        b1, b2 = d1.next_batch(), d2.next_batch()
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        # labels shifted by one vs tokens
        np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])

    def test_skip_ahead_restart_consistency(self):
        d = SyntheticLM(vocab=64, seq_len=8, batch=2, seed=3)
        batches = [d.next_batch() for _ in range(5)]
        d2 = SyntheticLM(vocab=64, seq_len=8, batch=2, seed=3)
        d2.skip_to(3)
        np.testing.assert_array_equal(d2.next_batch()["tokens"], batches[3]["tokens"])

    def test_packed_reader_roundtrip(self, tmp_path):
        path = str(tmp_path / "tokens.bin")
        recs = np.arange(20 * 9, dtype=np.uint32).reshape(20, 9)
        PackedReader.write(path, recs)
        r = PackedReader(path, batch=4, rank=0, world=2, seed=0)
        b = r.next_batch()
        assert b["tokens"].shape == (4, 8) and b["labels"].shape == (4, 8)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_packed_reader_rank_disjoint(self, tmp_path):
        path = str(tmp_path / "t.bin")
        recs = np.arange(16 * 5, dtype=np.uint32).reshape(16, 5)
        PackedReader.write(path, recs)
        r0 = PackedReader(path, batch=4, rank=0, world=2, seed=0)
        r1 = PackedReader(path, batch=4, rank=1, world=2, seed=0)
        t0 = r0.next_batch()["tokens"][:, 0]
        t1 = r1.next_batch()["tokens"][:, 0]
        assert set(t0.tolist()).isdisjoint(t1.tolist())

    def test_prefetcher(self):
        d = SyntheticLM(vocab=16, seq_len=4, batch=2, seed=0)
        ref = SyntheticLM(vocab=16, seq_len=4, batch=2, seed=0)
        pf = Prefetcher(d, depth=2)
        try:
            for _ in range(3):
                np.testing.assert_array_equal(next(pf)["tokens"], ref.next_batch()["tokens"])
        finally:
            pf.close()


class TestCompression:
    def test_roundtrip_error_bounded(self, rng):
        x = jnp.asarray(rng.standard_normal(3000).astype(np.float32))
        approx, resid = compress.compress_decompress(x)
        np.testing.assert_allclose(np.asarray(approx + resid), np.asarray(x), rtol=1e-6)
        block_max = np.abs(np.asarray(x)).max()
        assert float(jnp.abs(resid).max()) <= block_max / 127.0

    def test_error_feedback_accumulates(self, rng):
        # with EF, the *accumulated* quantization error stays bounded and the
        # mean of compressed gradients tracks the true mean over steps
        g = jnp.asarray(rng.standard_normal(512).astype(np.float32)) * 0.01
        resid = jnp.zeros_like(g)
        total_sent = jnp.zeros_like(g)
        for _ in range(20):
            approx, resid = compress.compress_decompress(g + resid)
            total_sent = total_sent + approx
        np.testing.assert_allclose(
            np.asarray(total_sent) / 20, np.asarray(g), atol=float(jnp.abs(g).max()) / 100
        )


class TestStragglerMonitor:
    def test_flags_outlier(self):
        from repro.train.loop import StragglerMonitor

        mon = StragglerMonitor(alpha=0.3, z_threshold=3.0)
        for i in range(20):
            mon.observe(i, 0.1 + 0.001 * (i % 3))
        assert not mon.flagged
        assert mon.observe(99, 1.5)  # 15x step time -> straggler
        assert mon.flagged and mon.flagged[-1][0] == 99


class TestCompileCache:
    def _updates(self, monkeypatch):
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: seen.__setitem__(name, value))
        return seen

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        seen = self._updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert seen == {}

    def test_default_dir_is_fixed_inside_the_checkout(self, monkeypatch):
        from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

        seen = self._updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert enable_compile_cache() == os.path.join(repo, ".jax_cache")
        assert seen == {"jax_compilation_cache_dir": str(DEFAULT_DIR)}
