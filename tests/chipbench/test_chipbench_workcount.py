"""Work counts against hand-worked values."""
from __future__ import annotations

import pytest

import chipbench_smoke  # noqa: F401
from chipbench.peaks import PEAKS, peaks_for
from chipbench.workcount import Dims, gemm_flops, least_seconds, weight_bytes_per_elem

TINY = Dims(family="dense", n_layers=2, d_model=8, vocab=10, n_heads=2,
            n_kv_heads=1, head_dim=4, d_ff=16, kv_cache_dtype="bfloat16")


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
    d = Dims(family="ssm", n_layers=1, d_model=4, vocab=6, ssm_state=2,
             ssm_head_dim=2, ssm_expand=2, ssm_conv=4)
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
