"""The configuration files: each holds its source's keys as published, and
the harness reads from them the sizes the program runs."""
from __future__ import annotations

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
