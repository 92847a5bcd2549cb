"""Named scopes in the compiled decode step: the policy-routed GEMMs
(``gemm/<op>``), the attention core (``attention``), the Mamba2 mixer
(``ssm``) and the masked step's row merge (``select``) name their ops in
the HLO ``op_name`` metadata, and change nothing else the compiler emits."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.policy import NATIVE_F32
from repro.models import build_model
from repro.serve import ServeConfig, ServeEngine

#: the sections of the compiled text that hold source locations
DEBUG_SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
FAMILY_SCOPE = {"qwen1.5-0.5b": "attention", "mamba2-2.7b": "ssm"}


def decode_step_hlo(arch: str) -> str:
    """The compiled text of a fresh engine's masked decode step."""
    cfg = dataclasses.replace(
        get_smoke_config(arch).with_policy(NATIVE_F32), n_layers=2)
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.key(0)),
                      config=ServeConfig(batch_slots=2, max_len=16))
    tokens = jnp.zeros((2, 1), jnp.int32)
    active = jnp.ones((2,), bool)
    return eng._step.lower(eng.params, tokens, eng.state,
                           active).compile().as_text()


def without_metadata(text: str) -> str:
    """The compiled text without op metadata and source locations, its
    instructions and computations renamed in order of first appearance
    (passes number the instructions they create, and metadata can change
    how many they create and drop along the way)."""
    out, skip = [], False
    for line in text.splitlines():
        if line in DEBUG_SECTIONS:
            skip = True
        elif not line:
            skip = False
        if not skip:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    names: dict[str, str] = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%v{len(names)}")

    return re.sub(r"%[A-Za-z_][\w.\-]*", rename, "\n".join(out))


@pytest.mark.parametrize("arch", sorted(FAMILY_SCOPE))
def test_decode_step_ops_carry_the_scopes(arch):
    names = set(re.findall(r'op_name="([^"]*)"', decode_step_hlo(arch)))
    parts = {p for n in names for p in n.split("/")}
    assert {"gemm", "select", FAMILY_SCOPE[arch]} <= parts
    other = set(FAMILY_SCOPE.values()) - {FAMILY_SCOPE[arch]}
    assert not other & parts
    gemm_ops = {n.split("gemm/", 1)[1].split("/", 1)[0] for n in names if "gemm/" in n}
    assert "logits" in gemm_ops or "lm_head" in gemm_ops
    if arch == "qwen1.5-0.5b":
        assert {"qkv", "out", "attn_qk", "attn_av"} <= gemm_ops
        # the score and value contractions run inside the attention scope
        assert all("/attention/" in n for n in names
                   if "gemm/attn_qk" in n or "gemm/attn_av" in n)
    else:
        assert {"ssm_in", "ssm_out"} <= gemm_ops


@pytest.mark.parametrize("arch", sorted(FAMILY_SCOPE))
def test_scopes_change_only_metadata(arch, monkeypatch):
    scoped = decode_step_hlo(arch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        bare = decode_step_hlo(arch)
    finally:
        jax.clear_caches()
    assert "op_name=" in scoped and "gemm/" not in bare
    assert " dot(" in without_metadata(scoped)
    assert without_metadata(scoped) == without_metadata(bare)
