"""Each plain reference against the program at smoke size, and the
control a step below in precision reading clearly worse."""
from __future__ import annotations

import dataclasses

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

    conf = smoke.DENSE if family == "dense" else smoke.SSM
    cfg = dataclasses.replace(get_config(conf["arch"]), **arch_fields(conf))
    model = build_model(cfg.with_policy(NATIVE_F32))
    return cfg, model, make_params(model.init, 11)


@pytest.mark.parametrize("family", ["dense", "ssm"])
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
    ref = check.Reference(family, check.ref_dims(cfg), 48)
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
    ref = check.Reference(family, check.ref_dims(cfg), 48)
    rows = np.arange(24)
    exact = ref.logits(params, ref.hidden(params, toks, 24), rows)
    low = ref.logits(params, ref.hidden(params, toks, 24, control), rows, control)
    err = np.abs(low - exact).max() / np.abs(exact).max()
    floor = {"high": 1e-6, "bf16": 1e-3, "fp8": 1e-2}[control]
    assert err > floor
