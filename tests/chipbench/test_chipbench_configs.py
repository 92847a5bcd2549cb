"""The configuration files: each holds its source's keys as published, and
the harness reads from them the sizes the program runs."""
from __future__ import annotations

import dataclasses
import json

import pytest

import chipbench_smoke as smoke
from chipbench.spec import arch_fields, load_json
from chipbench.workcount import layer_list

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


DATA = smoke.ROOT / "tests" / "chipbench" / "data"


def pinned_arch_fields(name: str) -> dict:
    """``arch_fields`` of configuration ``name`` as the harness read it
    before it read flat Mamba-2 keys: ``data/arch-fields-<name>.json``,
    which a new configuration brings."""
    path = DATA / f"arch-fields-{name}.json"
    if not path.is_file():
        pytest.fail(f"no pinned arch_fields for configuration {name!r}: "
                    f"add {path.relative_to(smoke.ROOT)}")
    return load_json(path)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_arch_fields_of_the_configuration_files_stay(conf):
    assert arch_fields(load_json(smoke.ROOT / conf["file"])) == pinned_arch_fields(conf["name"])


def test_a_missing_pin_names_the_file_to_add():
    with pytest.raises(pytest.fail.Exception,
                       match="tests/chipbench/data/arch-fields-no-such-config.json"):
        pinned_arch_fields("no-such-config")


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


# -- what a new entry of BENCHMARK.json brings ------------------------------------------


def configuration_needs(entry: dict, conf: dict | None) -> list[str]:
    """The files, relative to the repository's root, that a configuration
    entry needs beside it, given its file's content ``conf`` (None where it
    has none yet)."""
    out = [entry["file"], f"tests/chipbench/data/arch-fields-{entry['name']}.json"]
    if conf is None:
        return out
    family = conf.get("family")
    out += [f"chipbench/reference/{family}.py", f"tests/chipbench/smoke/{family}.json"]
    try:
        layers = layer_list(conf, 1)
    except ValueError as e:
        return out + [f"a 'layers' key in {entry['file']} ({e})"]
    return out + [f"chipbench/work/{c}.py" for c in sorted({c for lay in layers for c in lay})]


def cell_needs(entry: dict) -> list[str]:
    return [f"chipbench/mixes/{entry['traffic']}.json",
            f"chipbench/limits/{entry['name']}.json",
            f"tests/chipbench/data/limits-{entry['name']}.jsonl"]


def missing(needed: list[str]) -> list[str]:
    return [f for f in needed if not (smoke.ROOT / f).is_file()]


def needs(kind: str, entry: dict) -> list[str]:
    if kind == "cell":
        return cell_needs(entry)
    path = smoke.ROOT / entry["file"]
    return configuration_needs(entry, load_json(path) if path.is_file() else None)


ENTRIES = ([pytest.param("configuration", c, id=f"configuration-{c['name']}")
            for c in BENCH["configs"]]
           + [pytest.param("cell", w, id=f"cell-{w['name']}") for w in BENCH["workloads"]])


@pytest.mark.parametrize("kind,entry", ENTRIES)
def test_an_entry_brings_every_file_it_needs(kind, entry):
    """A configuration brings its file, a reference of its family, a smoke
    configuration of that family, its pinned ``arch_fields`` and a work
    count of every component its layers name; a cell its mix, its limits
    and the readings they were set from.  Each is a new file, found by
    name: no file of the harness is edited for a new entry."""
    lacking = missing(needs(kind, entry))
    assert not lacking, f"the {kind} {entry['name']!r} needs: {', '.join(lacking)}"


def test_a_new_family_is_told_each_file_it_lacks():
    entry = {"name": "x", "file": "chipbench/configs/x.json"}
    conf = {"family": "hybrid", "layers": [["ssm", "mlp"], ["attention", "mlp"], ["moe"]]}
    assert missing(configuration_needs(entry, conf)) == [
        "chipbench/configs/x.json", "tests/chipbench/data/arch-fields-x.json",
        "chipbench/reference/hybrid.py", "tests/chipbench/smoke/hybrid.json",
        "chipbench/work/moe.py"]
    lacking = missing(configuration_needs(entry, {"family": "hybrid"}))
    assert lacking[-1].startswith("a 'layers' key in chipbench/configs/x.json")
    assert missing(cell_needs({"name": "y", "traffic": "chat-f32"})) == [
        "chipbench/limits/y.json", "tests/chipbench/data/limits-y.jsonl"]
