"""Smoke-size cells for the CPU tests of the chip benchmark: the same
harness, families and mixes, at widths a test run can hold."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.spec import Cell  # noqa: E402

DENSE = {
    "arch": "qwen1.5-0.5b", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "serving": {"slots": 3, "max_len": 48, "kv_cache_dtype": "bfloat16"},
}
SSM = {
    "arch": "mamba2-2.7b", "family": "ssm",
    "d_model": 64, "n_layer": 2, "vocab_size": 250, "pad_vocab_size_multiple": 16,
    "tie_embeddings": True,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                "headdim": 16, "ngroups": 1, "chunk_size": 8},
    "serving": {"slots": 3, "max_len": 48},
}
MIX = {
    "arrivals": {"process": "poisson", "rate": 12.0},
    "prompt": {"median": 10, "sigma": 0.5, "snap": [8, 16]},
    "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "warm_in_s": 0.3, "grace_s": 20,
}
# prefill_err: sound runs read under 1e-6 at this size, and the cells'
# controls (``high``, ``fp8``) 3e-5 and more
LIMITS = {"served_gap": 1e-3, "prefill_err": 5e-6, "sample_tokens": 40,
          "sample_requests": 4}


def cell(family: str = "dense", precision=None, arrivals=None) -> Cell:
    mix = dict(MIX, precision=precision or {"policy": "native_f32"})
    if arrivals:
        mix["arrivals"] = arrivals
    return Cell(name=f"smoke-{family}", chips=1,
                config=DENSE if family == "dense" else SSM, mix=mix,
                limits=LIMITS, end_to_end=(), per_layer=())
