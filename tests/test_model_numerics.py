"""Numerical correctness of the model building blocks against naive
references: flash attention vs dense softmax, SSD chunked vs sequential
recurrence, RG-LRU associative scan vs step loop, MoE routing invariants."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.policy import NATIVE_F32, PAPER_BASELINE
from repro.models import layers
from repro.models.layers import flash_attention
from repro.models import griffin as griffin_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

POLICY = NATIVE_F32


def _naive_attention(q, k, v, causal=True, window=0):
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (hd**-0.5)
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    valid = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        valid &= kp <= qp
    if window:
        valid &= kp > qp - window
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, hd)


class TestFlashAttention:
    @pytest.mark.parametrize("sq,skv,chunk", [(32, 32, 8), (17, 17, 16), (64, 64, 64)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_naive(self, rng, sq, skv, chunk, causal):
        q = jnp.asarray(rng.standard_normal((2, sq, 4, 16)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((2, skv, 2, 16)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((2, skv, 2, 16)).astype(np.float32))
        out = flash_attention(q, k, v, POLICY, causal=causal, chunk=chunk)
        ref = _naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_sliding_window(self, rng):
        q = jnp.asarray(rng.standard_normal((1, 48, 2, 8)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((1, 48, 2, 8)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((1, 48, 2, 8)).astype(np.float32))
        out = flash_attention(q, k, v, POLICY, causal=True, window=8, chunk=16)
        ref = _naive_attention(q, k, v, causal=True, window=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_decode_position_mask(self, rng):
        # q at offset: only kv positions <= offset attend
        k = jnp.asarray(rng.standard_normal((1, 16, 1, 8)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((1, 16, 1, 8)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal((1, 1, 1, 8)).astype(np.float32))
        out = flash_attention(q, k, v, POLICY, causal=True, q_offset=7, kv_len=16, chunk=4)
        ref = _naive_attention(
            jnp.pad(q, ((0, 0), (7, 8), (0, 0), (0, 0))), k, v, causal=True
        )[:, 7:8]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def _walk_scoped(jaxpr, stack=""):
    """Every equation of a jaxpr and its sub-jaxprs, with the name-scope
    stack it runs under."""
    from jax.extend import core as jcore

    for e in jaxpr.eqns:
        ns = f"{stack}/{e.source_info.name_stack}"
        yield e, ns
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _walk_scoped(sub.jaxpr, ns)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _walk_scoped(sub, ns)


def _tiny_lm(arch, policy=PAPER_BASELINE):
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke_config(arch).with_policy(policy),
                              n_layers=2)
    return cfg, build_model(cfg)


class TestDecodeOverCache:
    """Decode attention reads a bf16 cache as the one exact limb it is
    (core.rmpm one-limb rule); the int8 cache keeps its f32 dequant path."""

    B, CAP, HKV, G, HD, FILLED = 2, 24, 2, 2, 16, 13

    def _filled(self, kind, rng):
        b, cap, hkv, hd = self.B, self.CAP, self.HKV, self.HD
        if kind == "paged":
            cache = layers.paged_cache_init(b, cap, hkv, hd, "bfloat16",
                                            n_pages=12, page_size=4)
            cache = dataclasses.replace(
                cache, page_tbl=jnp.arange(1, 13, dtype=jnp.int32).reshape(b, 6))
        else:
            dtype = "int8" if kind == "int8" else "bfloat16"
            cache = layers.kv_cache_init(b, cap, hkv, hd, dtype, per_slot=True)
        kv = [jnp.asarray(rng.standard_normal((b, self.FILLED, hkv, hd))
                          .astype(np.float32)) for _ in range(2)]
        return layers.kv_append(cache, *kv)

    @pytest.mark.parametrize("kind", ["dense", "paged", "int8"])
    def test_matches_f32_upcast_path(self, rng, kind):
        cache = self._filled(kind, rng)
        k, v, ks, vs = layers.kv_view(cache)
        assert k.dtype == (jnp.int8 if kind == "int8" else jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal(
            (self.B, 1, self.HKV * self.G, self.HD)).astype(np.float32))
        at = dict(q_offset=cache.length - 1, kv_positions=cache.pos, chunk=8)
        out = flash_attention(q, k, v, PAPER_BASELINE, k_scale=ks, v_scale=vs,
                              **at)

        def upcast(x, scale):  # the parent's chunk read, done up front
            return x.astype(jnp.float32) * (1 if scale is None else scale)

        ref = flash_attention(q, upcast(k, ks), upcast(v, vs), PAPER_BASELINE,
                              **at)
        if kind == "int8":
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        else:
            err = float(jnp.max(jnp.abs(out - ref)))
            assert err <= 2**-21 * float(jnp.max(jnp.abs(ref)))

    @pytest.mark.parametrize("read", ["storage_dtype", "f32_upcast"])
    def test_no_limb_split_of_the_cache(self, monkeypatch, read):
        if read == "f32_upcast":  # control: the parent's read, which splits
            monkeypatch.setattr(layers, "_dequant_chunk", lambda x, s: (
                x.astype(jnp.float32) if s is None
                else x.astype(jnp.float32) * s))
        cfg, model = _tiny_lm("qwen1.5-0.5b")
        b, max_len = 2, 48
        params = jax.eval_shape(model.init, jax.random.key(0))
        state = jax.eval_shape(
            lambda: model.init_decode_state(b, max_len, per_slot=True))
        tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(model.decode_step)(params, tokens, state).jaxpr
        chunk_shape = (b, min(cfg.attn_chunk, max_len), cfg.n_kv_heads,
                       cfg.head_dim)
        splits = [e for e, ns in _walk_scoped(jaxpr)
                  if e.primitive.name == "bitcast_convert_type"
                  and "attention" in ns
                  and e.invars[0].aval.dtype == jnp.float32
                  and e.invars[0].aval.shape == chunk_shape]
        if read == "f32_upcast":
            assert splits  # the scan finds the split where there is one
        else:
            assert not splits

    @pytest.mark.parametrize("arch,sites", [("qwen1.5-0.5b", 2),
                                            ("mamba2-2.7b", 0)])
    def test_one_limb_sites_of_the_decode_program(self, arch, sites):
        from repro.serve import Request, ServeConfig, ServeEngine

        _, model = _tiny_lm(arch)
        eng = ServeEngine(model, model.init(jax.random.key(0)),
                          config=ServeConfig(batch_slots=2, max_len=32,
                                             trace=True))
        eng.submit(Request(prompt=np.arange(1, 6, dtype=np.int32), max_new=3))
        eng.drain()
        got = eng.decode_one_limb
        assert got.sites == sites
        assert got.einsums_saved == 5 * sites  # 6 M24 einsums -> 1
        assert eng.tracer.gauges["one_limb_sites"] == sites
        # prefill attends over fresh f32 K/V: no site of its own
        assert eng.metrics.summary()["one_limb"]["sites"] == sites


class TestSSD:
    def test_chunked_matches_sequential(self, rng):
        cfg = get_smoke_config("mamba2-2.7b").with_policy(POLICY)
        b, s, h, p, n = 2, 32, 4, 8, 16
        xh = jnp.asarray(rng.standard_normal((b, s, h, p)).astype(np.float32))
        dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((b, s, h)).astype(np.float32)))
        a = -jnp.exp(jnp.asarray(rng.standard_normal(h).astype(np.float32)))
        bm = jnp.asarray(rng.standard_normal((b, s, n)).astype(np.float32))
        cm = jnp.asarray(rng.standard_normal((b, s, n)).astype(np.float32))
        cfg = dataclasses.replace(cfg, ssm_chunk=8)
        y_chunk, final = ssm_lib._ssd_chunked(xh, dt, a, bm, cm, cfg)
        # sequential recurrence reference
        hstate = np.zeros((b, h, p, n))
        ys = np.zeros((b, s, h, p))
        for t in range(s):
            decay = np.exp(np.asarray(dt[:, t]) * np.asarray(a)[None, :])
            hstate = hstate * decay[..., None, None] + (
                np.asarray(dt[:, t])[..., None] * np.asarray(xh[:, t])
            )[..., None] * np.asarray(bm[:, t])[:, None, None, :]
            ys[:, t] = np.einsum("bhpn,bn->bhp", hstate, np.asarray(cm[:, t]))
        np.testing.assert_allclose(np.asarray(y_chunk), ys, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(final), hstate, rtol=2e-3, atol=2e-3)

    def test_train_decode_agree_end_to_end(self, rng):
        cfg = get_smoke_config("mamba2-2.7b").with_policy(POLICY)
        p = ssm_lib.mamba2_init(jax.random.key(0), cfg)
        x = jnp.asarray(rng.standard_normal((1, 16, cfg.d_model)).astype(np.float32) * 0.2)
        y_train, _ = ssm_lib.mamba2_apply(p, x, cfg, state=None)
        st = ssm_lib.ssm_state_init(cfg, 1)
        y_dec, _ = ssm_lib.mamba2_apply(p, x, cfg, state=st)
        np.testing.assert_allclose(
            np.asarray(y_train), np.asarray(y_dec), rtol=5e-3, atol=5e-4
        )


class TestRGLRU:
    def test_scan_matches_step_loop(self, rng):
        b, s, w = 2, 24, 8
        x = jnp.asarray(rng.standard_normal((b, s, w)).astype(np.float32))
        r = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((b, s, w)).astype(np.float32)))
        i = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((b, s, w)).astype(np.float32)))
        lam = jnp.asarray(rng.standard_normal(w).astype(np.float32))
        h_seq, h_last = griffin_lib._rglru_scan(x, r, i, lam, None)
        log_a = griffin_lib._C * np.asarray(r) * np.log(
            1 / (1 + np.exp(-np.asarray(lam)))
        )[None, None, :]
        a = np.exp(log_a)
        href = np.zeros((b, w))
        out = np.zeros((b, s, w))
        for t in range(s):
            href = a[:, t] * href + np.sqrt(1 - a[:, t] ** 2) * (
                np.asarray(i[:, t]) * np.asarray(x[:, t])
            )
            out[:, t] = href
        np.testing.assert_allclose(np.asarray(h_seq), out, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last), href, rtol=1e-4, atol=1e-5)

    def test_state_carrying_decode(self, rng):
        # split the sequence: scan(all) == scan(first half) then scan(second, h0)
        b, s, w = 1, 16, 4
        x = jnp.asarray(rng.standard_normal((b, s, w)).astype(np.float32))
        r = jax.nn.sigmoid(x * 0.3)
        i = jax.nn.sigmoid(-x * 0.2)
        lam = jnp.ones(w)
        full, _ = griffin_lib._rglru_scan(x, r, i, lam, None)
        h1, last1 = griffin_lib._rglru_scan(x[:, :8], r[:, :8], i[:, :8], lam, None)
        h2, _ = griffin_lib._rglru_scan(x[:, 8:], r[:, 8:], i[:, 8:], lam, last1)
        np.testing.assert_allclose(
            np.asarray(full), np.concatenate([h1, h2], axis=1), rtol=1e-5, atol=1e-6
        )


class TestMoE:
    def _cfg(self):
        return get_smoke_config("phi3.5-moe-42b-a6.6b").with_policy(POLICY)

    def test_output_shape_and_aux(self, rng):
        cfg = self._cfg()
        p = moe_lib.moe_init(jax.random.key(0), cfg)
        x = jnp.asarray(rng.standard_normal((2, 64, cfg.d_model)).astype(np.float32))
        out, aux = moe_lib.moe_apply(p, x, cfg)
        assert out.shape == x.shape
        assert float(aux) >= 1.0 - 1e-3  # Switch aux loss lower bound is 1

    def test_dispatch_respects_capacity(self, rng):
        ids = jnp.asarray(rng.integers(0, 4, (1, 32, 2)), jnp.int32)
        w = jnp.ones((1, 32, 2), jnp.float32) * 0.5
        dispatch, combine = moe_lib._dispatch_combine(ids, w, e=4, capacity=3)
        # each (expert, slot) holds at most one token
        per_slot = np.asarray(dispatch, np.float32).sum(axis=1)  # (G, E, C)
        assert per_slot.max() <= 1.0 + 1e-6
        # combine weight mass never exceeds dispatch mass
        assert float(combine.sum()) <= float(dispatch.sum()) + 1e-6

    def test_identical_tokens_get_identical_outputs(self, rng):
        cfg = self._cfg()
        p = moe_lib.moe_init(jax.random.key(0), cfg)
        x0 = rng.standard_normal((1, 1, cfg.d_model)).astype(np.float32)
        x = jnp.asarray(np.repeat(x0, 8, axis=1))
        out, _ = moe_lib.moe_apply(p, x, cfg)
        out = np.asarray(out)
        # first token (guaranteed within capacity) defines the reference;
        # tokens beyond capacity may be dropped (zero) — allowed by GShard
        ref = out[0, 0]
        for t in range(1, 8):
            ok_same = np.allclose(out[0, t], ref, rtol=1e-4, atol=1e-5)
            ok_dropped = np.allclose(out[0, t], 0, atol=1e-6) or (
                "shared" in p and True
            )
            assert ok_same or ok_dropped

    def test_decode_batch_grouping(self, rng):
        cfg = self._cfg()
        p = moe_lib.moe_init(jax.random.key(0), cfg)
        x = jnp.asarray(rng.standard_normal((8, 1, cfg.d_model)).astype(np.float32))
        out, _ = moe_lib.moe_apply(p, x, cfg)
        assert out.shape == (8, 1, cfg.d_model)
        assert bool(jnp.isfinite(out).all())
