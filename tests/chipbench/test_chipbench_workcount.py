"""Work counts against hand-worked values, layers made of components, and
the benchmark configurations' counts as the per-family count gave them."""
from __future__ import annotations

import dataclasses
import sys
import types

import jax
import pytest

import chipbench_smoke
from chipbench.peaks import PEAKS, peaks_for
from chipbench.spec import arch_fields, load_json
from chipbench.weights import gemm_shapes
from chipbench.workcount import (Dims, component, gemm_flops, layer_list, least_seconds,
                                 weight_bytes_per_elem)

TINY = Dims(n_layers=2, d_model=8, vocab=10, layers=layer_list({"family": "dense"}, 2),
            n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, kv_cache_dtype="bfloat16")


def test_one_gemm():
    assert gemm_flops(3, 5, 7) == 210


def test_storage_per_mode():
    assert [weight_bytes_per_elem(m) for m in ("M8", "M16", "M24")] == [2, 4, 4]
    with pytest.raises(ValueError):
        weight_bytes_per_elem("M32")


def test_decode_step_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate+up 2 x 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 256 + 128
    params = 2 * per_layer + 8 * 10
    assert TINY.matmul_params() == params == 1232
    # two rows at contexts 3 and 5: 2*rows*params + 4*L*H*hd*ctx
    attn = 4 * 2 * 2 * 4 * (3 + 5)
    assert TINY.decode_flops([3, 5]) == 2 * 2 * params + attn == 5440
    # bytes at M24 (4 B/weight) + cached K and V, bf16, 2 layers, 1 kv head
    kv_per_tok = 2 * 2 * 1 * 4 * 2
    assert TINY.decode_bytes([3, 5], lambda op: "M24") == 4 * params + 8 * kv_per_tok
    assert TINY.decode_bytes([3, 5], lambda op: "M8") == 2 * params + 8 * kv_per_tok


def test_prefill_by_hand():
    # 3 tokens: causal pairs 1+2+3 = 6; the head (8 x 10) for the last only
    assert TINY.prefill_flops(3) == 2 * 3 * (1232 - 80) + 2 * 80 + 4 * 2 * 2 * 4 * 6


def test_ssm_state_and_flops():
    d = Dims(n_layers=1, d_model=4, vocab=6, layers=layer_list({"family": "ssm"}, 1),
             ssm_state=2, ssm_head_dim=2, ssm_expand=2, ssm_conv=4)
    # d_inner 8, 4 heads: in_proj 4 x (16 + 4 + 4), out_proj 8 x 4, head 4 x 6
    assert d.matmul_params() == 96 + 32 + 24
    assert d.ssm_state_bytes() == 4 * (4 * 2 * 2 + 3 * (8 + 4))
    assert d.decode_flops([7]) == 2 * 152 + 4 * 8 * 2


def test_roofline_bound_and_peaks():
    p = peaks_for("TPU v5 lite")
    t, which = least_seconds(197e12, 1.0, p)
    assert which == "compute" and t == pytest.approx(1.0)
    t, which = least_seconds(1.0, 819e9, p)
    assert which == "memory" and t == pytest.approx(1.0)
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(ValueError):
        peaks_for("TPU v9 imaginary")


# -- layers made of components ---------------------------------------------------

#: four (ssm, mlp) layers, then one (attention, mlp)
MIXED_CONFIG = {"layers": [["ssm", "mlp"]] * 4 + [["attention", "mlp"]]}
MIXED = Dims(n_layers=5, d_model=8, vocab=10, layers=layer_list(MIXED_CONFIG, 5),
             n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, ssm_state=2, ssm_head_dim=4,
             ssm_expand=2, ssm_conv=4)
# d_inner 16, 4 SSM heads: in_proj 8 x (32 + 4 + 4), out_proj 16 x 8
SSM_LAYER = 8 * 40 + 16 * 8
MLP_LAYER = 2 * 8 * 16 + 16 * 8
ATTN_LAYER = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8
MIXED_PARAMS = 4 * (SSM_LAYER + MLP_LAYER) + ATTN_LAYER + MLP_LAYER + 8 * 10
SSD_PER_TOKEN = 2 * 2 * 16 * 2  # readout and update: 4 * d_inner * d_state
STATE_PER_LAYER = 4 * (4 * 4 * 2 + 3 * (16 + 2 * 2))  # f32 SSM state + conv window


def test_mixed_layers_params_by_hand():
    assert MIXED.matmul_params() == MIXED_PARAMS == 3984
    assert set(MIXED.gemms()) == {"ssm_in", "ssm_out", "mlp_up", "mlp_down", "qkv", "out",
                                  "logits"}
    assert len(MIXED.gemms()["qkv"]) == 3 and len(MIXED.gemms()["mlp_up"]) == 10


def test_mixed_layers_decode_by_hand():
    # attention flops from the one attention layer only, SSD from the four others
    attn = 2 * 2 * 2 * 4 * (3 + 5)
    assert MIXED.decode_flops([3, 5]) == 2 * 2 * MIXED_PARAMS + attn + 4 * 2 * SSD_PER_TOKEN
    assert MIXED.decode_flops([3, 5]) == 17216
    # K/V bytes of the attention layer only (bf16, 1 kv head), state of the SSM layers
    kv_per_tok = 2 * 1 * 4 * 2
    assert MIXED.kv_bytes_per_token() == kv_per_tok
    assert MIXED.ssm_state_bytes() == 4 * STATE_PER_LAYER
    state = 2 * 2 * 4 * STATE_PER_LAYER  # two rows, read and written, four layers
    assert MIXED.decode_bytes([3, 5], lambda op: "M24") == (
        4 * MIXED_PARAMS + 8 * kv_per_tok + state) == 21952


def test_mixed_layers_prefill_by_hand():
    # 3 tokens: causal pairs 6 in the attention layer; 3 SSD tokens in each SSM layer
    assert MIXED.prefill_flops(3) == (2 * 3 * (MIXED_PARAMS - 80) + 2 * 80
                                      + 2 * 2 * 2 * 4 * 6 + 4 * 3 * SSD_PER_TOKEN)
    assert MIXED.prefill_flops(3) == 25312


def test_layer_list_is_the_files_list_of_layers():
    assert MIXED.layers == (("ssm", "mlp"),) * 4 + (("attention", "mlp"),)
    # the order of the layers moves no count
    first = dataclasses.replace(MIXED, layers=MIXED.layers[::-1])
    assert first.matmul_params() == MIXED.matmul_params()
    assert first.decode_flops([3, 5]) == MIXED.decode_flops([3, 5])
    assert first.prefill_flops(3) == MIXED.prefill_flops(3)


def test_family_gives_the_layers_without_a_list():
    assert TINY.layers == (("attention", "mlp"),) * 2
    assert layer_list({"family": "ssm"}, 3) == (("ssm",),) * 3
    with pytest.raises(ValueError, match="family 'hybrid'"):
        layer_list({"family": "hybrid"}, 3)
    with pytest.raises(ValueError, match="4 layers listed for a model of 5"):
        dataclasses.replace(MIXED, layers=MIXED.layers[:4])


def test_unknown_component_is_an_error_that_names_it():
    with pytest.raises(ValueError, match="component 'moe'"):
        dataclasses.replace(TINY, layers=(("moe",),) * 2)
    with pytest.raises(ValueError, match="component '../mlp'"):
        component("../mlp")


def test_a_new_component_module_is_counted_with_no_other_edit(monkeypatch):
    """A module under ``chipbench.work`` is all a new mechanism needs."""
    mod = types.ModuleType("chipbench.work.toy_expert")
    mod.gemms = lambda d: {"expert_up": [(d.d_model, 3)] * 2}
    mod.decode_flops = lambda d, context: 14
    mod.state_bytes = lambda d: 11
    monkeypatch.setitem(sys.modules, "chipbench.work.toy_expert", mod)
    d = dataclasses.replace(TINY, layers=layer_list({"layers": [["toy_expert", "mlp"]] * 2}, 2))
    params = 2 * (2 * 8 * 3 + 2 * 8 * 16 + 16 * 8) + 8 * 10
    assert d.matmul_params() == params
    assert d.decode_flops([4]) == 2 * params + 2 * 14
    assert d.ssm_state_bytes() == 22 and d.kv_bytes_per_token() == 0
    assert d.decode_bytes([4], lambda op: "M8") == 2 * params  # no decode_bytes: 0
    assert d.prefill_flops(3) == 2 * 3 * (params - 80) + 2 * 80
    assert set(d.gemms()) == {"expert_up", "mlp_up", "mlp_down", "logits"}


def test_the_layers_are_held_against_the_served_model():
    """``Dims.of`` refuses a list whose layers do not follow the program's
    kinds, or whose GEMMs are not the served weights' shapes."""
    cfg = types.SimpleNamespace(**{f.name: getattr(MIXED, f.name)
                                   for f in dataclasses.fields(Dims)})
    kinds = ["ssm_mlp"] * 4 + ["attn"]
    shapes = {(8, 40), (16, 8), (8, 16), (8, 8), (8, 4)}
    assert Dims.of(cfg, MIXED_CONFIG, kinds, shapes) == MIXED
    moved = {"layers": MIXED_CONFIG["layers"][::-1]}  # attention where an SSM layer is
    with pytest.raises(ValueError, match="program's kinds"):
        Dims.of(cfg, moved, kinds, shapes)
    two_ways = {"layers": [["ssm", "mlp"]] * 3 + [["ssm"], ["attention", "mlp"]]}
    with pytest.raises(ValueError, match="program's kinds"):
        Dims.of(cfg, two_ways, kinds, shapes)
    with pytest.raises(ValueError, match="program's kinds"):
        Dims.of(cfg, MIXED_CONFIG, kinds[:4], shapes)
    with pytest.raises(ValueError, match="served model's weights"):
        Dims.of(cfg, MIXED_CONFIG, kinds, shapes - {(8, 16)})  # no MLP served
    with pytest.raises(ValueError, match="served model's weights"):
        Dims.of(cfg, MIXED_CONFIG, kinds, shapes - {(8, 40)} | {(8, 44)})  # B and C in two groups


# -- the two benchmark configurations: the counts of the family-wide count ------

#: computed by the per-family count that the per-layer count replaced, at
#: full width: matmul params, decode flops and bytes at contexts ONE, FULL and
#: SPREAD, prefill flops at every snapped prompt length of both mixes
ONE, FULL, SPREAD = [1], [2048] * 24, [1, 17, 384, 769, 1024, 1537, 2048, 5]
PINNED = {
    "qwen1.5-0.5b": {
        "ops": ["qkv", "out", "mlp_up", "mlp_down", "logits"],
        "matmul_params": 463863808,
        "decode_flops": [927825920, 27097300992, 7990509568],
        "decode_bytes": {"M8": [927825920, 5759565824, 1496416256],
                         "M16": [1855553536, 6687293440, 2424143872],
                         "M24": [1855553536, 6687293440, 2424143872]},
        "prefill_flops": {256: 161385021440, 384: 244337868800, 512: 328901328896,
                          768: 502860087296, 1536: 1063391068160},
    },
    "mamba2-2.7b": {
        "ops": ["ssm_in", "ssm_out", "logits"],
        "matmul_params": 1414553600,
        "decode_flops": [2912993280, 69911838720, 23303946240],
        "decode_bytes": {"M8": [3001008128, 6954729472, 4204314624],
                         "M16": [5830115328, 9783836672, 7033421824],
                         "M24": [5830115328, 9783836672, 7033421824]},
        "prefill_flops": {256: 680070266880, 384: 1019976663040, 512: 1359883059200,
                          768: 2039695851520, 1536: 4079134228480},
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_benchmark_configurations_count_as_before(name):
    from repro.configs import get_config
    from repro.models import build_model

    conf = load_json(chipbench_smoke.ROOT / "chipbench" / "configs" / f"{name}.json")
    cfg = dataclasses.replace(get_config(conf["arch"]), **arch_fields(conf))
    model = build_model(cfg)
    shapes = gemm_shapes(jax.eval_shape(model.init, jax.random.key(0)))
    d, want = Dims.of(cfg, conf, model.kinds, shapes), PINNED[name]
    assert list(d.gemms()) == want["ops"]
    assert d.matmul_params() == want["matmul_params"]
    assert [d.decode_flops(c) for c in (ONE, FULL, SPREAD)] == want["decode_flops"]
    for mode, nbytes in want["decode_bytes"].items():
        assert [d.decode_bytes(c, lambda op: mode) for c in (ONE, FULL, SPREAD)] == nbytes
    assert {p: d.prefill_flops(p) for p in want["prefill_flops"]} == want["prefill_flops"]


# -- the readers on a count of mixed layers ------------------------------------------


def test_readers_run_on_mixed_layers(tmp_path):
    """``Context.build``, ``decode_roofline`` and ``mfu`` on a recorded
    trace, with the work of a model that mixes (ssm, mlp) and
    (attention, mlp) layers."""
    from repro.core.policy import PAPER_BASELINE

    from chipbench import readers
    from chipbench.client import StepRecord
    from chipbench.metrics import decode_roofline, mfu

    src = chipbench_smoke.ROOT / "tests" / "chipbench" / "data" / "smoke-dense-spans.xplane.pb"
    (tmp_path / "t").mkdir()
    (tmp_path / "t" / "host.xplane.pb").symlink_to(src)
    engine = types.SimpleNamespace(
        tracer=types.SimpleNamespace(events=[]), slots=3, phase_plans={},
        model_decode=types.SimpleNamespace(cfg=types.SimpleNamespace(policy=PAPER_BASELINE)))
    trace_s = readers.TraceData.from_dir(tmp_path).window_s
    drive = types.SimpleNamespace(steps=[StepRecord(0.0, 0.999 * trace_s, [6], [3, 5])])
    ctx = readers.Context.build(drive, engine, MIXED, types.SimpleNamespace(dir=tmp_path, t0=0.0),
                                peaks_for("TPU v5 lite"))
    assert set(ctx.decode_mode) == set(ctx.decode_impl) == set(MIXED.gemms())
    assert set(ctx.decode_mode.values()) == {"M24"}
    roof = decode_roofline.read(ctx, "itl")
    spans = readers.spans_within(ctx.trace.device_spans("modules", readers.DECODE_PROGRAM),
                                 *ctx.traced_steps()[0][1:])
    least, _ = least_seconds(MIXED.decode_flops([3, 5]),
                             MIXED.decode_bytes([3, 5], lambda op: "M24"), ctx.peaks)
    assert roof == pytest.approx(100 * least / (sum(s.dur for s in spans) * 1e-9), rel=1e-12)
    want = (MIXED.decode_flops([3, 5]) + MIXED.prefill_flops(6)) / ctx.trace.window_s
    assert mfu.read(ctx, "itl") == pytest.approx(100 * want / ctx.peaks.bf16_flops, rel=1e-12)
