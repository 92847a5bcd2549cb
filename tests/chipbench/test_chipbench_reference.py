"""Each plain reference against the program at smoke size, and the
control a step below in precision reading clearly worse."""
from __future__ import annotations

import dataclasses
import types

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as smoke
from chipbench import check
from chipbench.spec import arch_fields
from chipbench.weights import make_params


def program(family):
    from repro.configs import get_config
    from repro.core.policy import NATIVE_F32
    from repro.models import build_model

    conf = smoke.config(family)
    cfg = dataclasses.replace(get_config(conf["arch"]), **arch_fields(conf))
    model = build_model(cfg.with_policy(NATIVE_F32))
    return cfg, model, make_params(model.init, 11)


def reference(family, cfg):
    return check.Reference(family, check.ref_dims(cfg, smoke.config(family)), 48)


@pytest.mark.parametrize("family", smoke.FAMILIES)
def test_reference_matches_program_prefill_and_decode(family):
    cfg, model, params = program(family)
    prompt, extra = 16, 5
    toks = np.random.default_rng(0).integers(0, 200, prompt + extra).astype(np.int32)
    state = model.init_decode_state(1, 48, per_slot=True)
    logits, state = model.decode_step(params, jnp.asarray(toks[None, :prompt]), state)
    prog = [np.asarray(logits[0])]
    for t in toks[prompt:]:  # decode through the cache, one token at a time
        lg, state = model.decode_step(params, jnp.asarray([[t]]), state)
        prog.append(np.asarray(lg[0]))
    prog = np.concatenate(prog)
    ref = reference(family, cfg)
    h = ref.hidden(params, toks, prompt)
    want = ref.logits(params, h, np.arange(prompt + extra))
    err = np.abs(prog - want).max(axis=1) / np.abs(want).max()
    # prompt rows: f32 against f32 at HIGHEST
    assert err[:prompt].max() < 2e-6
    # decoded rows attend over keys and values the cache rounded to bf16, as
    # the reference does; the two sides' f32 keys differ in the last bits,
    # which now and then lands an element on the other side of a bf16
    # rounding boundary (without the rounding the gap is 2e-3)
    assert err[prompt:].max() < 2e-4


@pytest.mark.parametrize("family,control", [("dense", "high"), ("dense", "bf16"),
                                            ("ssm", "fp8")])
def test_control_reads_far_worse_than_reference(family, control):
    cfg, model, params = program(family)
    toks = np.random.default_rng(1).integers(0, 200, 24).astype(np.int32)
    ref = reference(family, cfg)
    rows = np.arange(24)
    exact = ref.logits(params, ref.hidden(params, toks, 24), rows)
    low = ref.logits(params, ref.hidden(params, toks, 24, control), rows, control)
    err = np.abs(low - exact).max() / np.abs(exact).max()
    floor = {"high": 1e-6, "bf16": 1e-3, "fp8": 1e-2}[control]
    assert err > floor


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_references_carry_the_published_file_and_ignore_it(family):
    """``dims.published`` is the cell's configuration file as loaded; the
    dense and SSM references take every size from the program's ArchConfig
    fields and read the same logits without it."""
    cfg, _, params = program(family)
    conf = smoke.config(family)
    dims = check.ref_dims(cfg, conf)
    assert dims.published is conf
    toks = np.random.default_rng(5).integers(0, 200, 24).astype(np.int32)
    rows = np.arange(24)
    bare = types.SimpleNamespace(**{k: v for k, v in vars(dims).items() if k != "published"})
    got = {}
    for name, d in (("published", dims), ("bare", bare)):
        ref = check.Reference(family, d, 48)
        got[name] = np.asarray(ref.logits(params, ref.hidden(params, toks, 16), rows))
    np.testing.assert_array_equal(got["published"], got["bare"])


def test_ssm_reference_adds_a_conv_bias_where_the_tree_has_one():
    """A bias of zero reads as no bias at all, and a drawn one moves the
    logits: the reference reads the ``conv_b`` leaf."""
    cfg, _, params = program("ssm")
    toks = np.random.default_rng(2).integers(0, 200, 24).astype(np.int32)
    ref = reference("ssm", cfg)
    rows = np.arange(24)

    def logits(conv_b):
        p = params
        if conv_b is not None:
            lay = dict(p["layers"]["seg0_ssm"], ssm=dict(p["layers"]["seg0_ssm"]["ssm"],
                                                         conv_b=conv_b))
            p = dict(p, layers={"seg0_ssm": lay})
        return np.asarray(ref.logits(p, ref.hidden(p, toks, 24), rows))

    shape = params["layers"]["seg0_ssm"]["ssm"]["conv_w"].shape
    bias_shape = shape[:1] + shape[2:]
    none = logits(None)
    np.testing.assert_array_equal(logits(jnp.zeros(bias_shape, jnp.float32)), none)
    drawn = np.random.default_rng(3).uniform(-0.5, 0.5, bias_shape).astype(np.float32)
    assert np.abs(logits(jnp.asarray(drawn)) - none).max() > 1e-2 * np.abs(none).max()


def test_attention_by_hand():
    """The dense reference's attention, as a reference of mixed layers
    composes it, against numpy: biased q/k/v projections, rotate-half RoPE,
    grouped heads, causal softmax of q k^T / sqrt(hd), the output
    projection.  A tree without the q/k/v biases is refused."""
    from chipbench.reference.dense import attention

    rng = np.random.default_rng(4)
    t, d, hq, hkv, hd, theta = 5, 8, 4, 2, 4, 10000.0
    w = {n: rng.normal(size=(d, e)).astype(np.float32)
         for n, e in (("wq", hq * hd), ("wk", hkv * hd), ("wv", hkv * hd))}
    b = {n: rng.normal(size=(x.shape[1],)).astype(np.float32) for n, x in w.items()}
    wo = rng.normal(size=(hq * hd, d)).astype(np.float32)
    h = rng.normal(size=(t, d)).astype(np.float32)
    dims = types.SimpleNamespace(n_heads=hq, n_kv_heads=hkv, head_dim=hd,
                                 kv_cache_dtype="bfloat16", rope_theta=theta)
    a = {n: {"w": jnp.asarray(w[n]), "b": jnp.asarray(b[n])} for n in w}
    a["wo"] = {"w": jnp.asarray(wo)}
    got = np.asarray(attention(a, jnp.asarray(h), dims, t, "highest"))

    def rope(x):
        half = hd // 2
        ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    q, k, v = ((h.astype(np.float64) @ w[n] + b[n]).reshape(t, -1, hd)
               for n in ("wq", "wk", "wv"))
    q, k = rope(q), rope(k)
    k, v = np.repeat(k, hq // hkv, axis=1), np.repeat(v, hq // hkv, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    s = s + np.where(np.tri(t, dtype=bool), 0, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v).reshape(t, hq * hd)
    np.testing.assert_allclose(got, o @ wo, rtol=1e-5, atol=1e-5)
    a["wk"] = {"w": a["wk"]["w"]}
    with pytest.raises(KeyError):
        attention(a, jnp.asarray(h), dims, t, "highest")
