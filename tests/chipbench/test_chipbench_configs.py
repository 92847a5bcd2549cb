"""The configuration files: each holds its source's keys as published, and
the harness reads from them the sizes the program runs."""
from __future__ import annotations

import dataclasses
import json

import pytest

import chipbench_smoke as smoke
from chipbench.spec import arch_fields, load_json

BENCH = json.loads((smoke.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_names_its_source_and_cuts(conf):
    f = load_json(smoke.ROOT / conf["file"])
    assert f["source"] == conf["source"]
    assert f["reduced"] == conf["reduced"]
    for key in conf["reduced"]:
        assert key in f["assumed"], key


def test_mamba2_keeps_the_source_ssm_cfg_and_runs_the_layer_defaults():
    conf = {c["name"]: c for c in BENCH["configs"]}["mamba2-2.7b"]
    f = load_json(smoke.ROOT / conf["file"])
    assert f["ssm_cfg"] == {"layer": "Mamba2"}
    assert "ssm_cfg" not in conf["reduced"]
    fields = arch_fields(f)
    assert (fields["ssm_state"], fields["ssm_head_dim"], fields["ssm_expand"],
            fields["ssm_conv"], fields["ssm_chunk"]) == (128, 64, 2, 4, 256)
    assert fields["d_model"] == 2560 and fields["vocab"] == 50288


#: ``arch_fields`` of each configuration file as the harness read it before
#: it read flat Mamba-2 keys
ARCH_FIELDS = {
    "qwen1.5-0.5b": {"vocab": 151936, "d_model": 1024, "n_layers": 24, "n_heads": 16,
                     "n_kv_heads": 16, "d_ff": 2816, "rope_theta": 1000000.0,
                     "norm_eps": 1e-06, "tie_embeddings": False,
                     "kv_cache_dtype": "bfloat16"},
    "mamba2-2.7b": {"vocab": 50288, "d_model": 2560, "n_layers": 32, "tie_embeddings": True,
                    "ssm_state": 128, "ssm_head_dim": 64, "ssm_expand": 2, "ssm_conv": 4,
                    "ssm_chunk": 256},
}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_arch_fields_of_the_configuration_files_stay(conf):
    assert arch_fields(load_json(smoke.ROOT / conf["file"])) == ARCH_FIELDS[conf["name"]]


def test_flat_mamba2_keys_give_the_ssm_fields():
    """A hybrid's Mamba-2 sizes, published flat (granite-4.0-h-micro's keys)."""
    from repro.models.config import ArchConfig

    flat = {"hidden_size": 2048, "num_hidden_layers": 20, "vocab_size": 100352,
            "mamba_d_state": 128, "mamba_d_head": 64, "mamba_expand": 2, "mamba_d_conv": 4,
            "mamba_chunk_size": 256, "mamba_n_groups": 1, "mamba_n_heads": 64}
    fields = arch_fields(flat)
    assert fields == {"vocab": 100352, "d_model": 2048, "n_layers": 20, "ssm_state": 128,
                      "ssm_head_dim": 64, "ssm_expand": 2, "ssm_conv": 4, "ssm_chunk": 256}
    assert set(fields) <= {f.name for f in dataclasses.fields(ArchConfig)}
