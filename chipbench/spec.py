"""Finding a cell's files by name: BENCHMARK.json, the configuration file
under ``configs/``, the traffic mix under ``mixes/`` and the correctness
limits under ``limits/``.  Nothing here is specific to one cell."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    mix: dict  # the traffic mix file
    limits: dict  # limits/<cell>.json
    end_to_end: tuple  # BENCHMARK.json end_to_end entries this cell reports
    per_layer: tuple  # BENCHMARK.json per_layer entries this cell reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path | None = None) -> Cell:
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(ROOT / conf["file"]),
        mix=load_json(HERE / "mixes" / f"{w['traffic']}.json"),
        limits=load_json(HERE / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


#: configuration-file keys (the source's own names) -> the program's
#: ArchConfig fields
ARCH_KEYS = {
    "hidden_size": "d_model", "d_model": "d_model",
    "num_hidden_layers": "n_layers", "n_layer": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "tie_embeddings": "tie_embeddings",
    # Mamba-2 sizes as hybrid models publish them, flat
    "mamba_d_state": "ssm_state", "mamba_d_head": "ssm_head_dim",
    "mamba_expand": "ssm_expand", "mamba_d_conv": "ssm_conv",
    "mamba_chunk_size": "ssm_chunk",
}
SSM_KEYS = {"d_state": "ssm_state", "headdim": "ssm_head_dim",
            "expand": "ssm_expand", "d_conv": "ssm_conv",
            "chunk_size": "ssm_chunk"}


def padded_vocab(config: dict) -> int:
    v = int(config["vocab_size"])
    m = int(config.get("pad_vocab_size_multiple", 1))
    return -(-v // m) * m


def arch_fields(config: dict) -> dict:
    """The ArchConfig fields the configuration file fixes."""
    out = {"vocab": padded_vocab(config)}
    for k, field in ARCH_KEYS.items():
        if k in config:
            out[field] = config[k]
    # the source's ``ssm_cfg`` over the defaults of its layer class, which
    # the file writes out as ``ssm_defaults``
    ssm = {**config.get("ssm_defaults", {}), **config.get("ssm_cfg", {})}
    for k, field in SSM_KEYS.items():
        if k in ssm:
            out[field] = ssm[k]
    if "kv_cache_dtype" in config.get("serving", {}):
        out["kv_cache_dtype"] = config["serving"]["kv_cache_dtype"]
    return out
