import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any other import: jax locks the device
#   count on first init, and the production meshes below need 512 placeholder
#   host devices.  (Only the dry-run sets this — tests/benches see 1 device.)

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell, prove the sharding is coherent, and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 40-cell sweep
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>[__<policy>].json with
memory_analysis, scan-corrected HLO cost, collective breakdown and roofline
terms.  Failures (sharding mismatch, OOM at compile) are bugs — fix, re-run.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.core.policy import PRESETS  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shapes import (  # noqa: E402
    SHAPES,
    build_cell,
    cell_applicable,
    count_params,
    model_flops,
)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool, policy: str = "paper_baseline",
             out_dir: str = OUT_DIR, grad_compression: bool = False,
             kv_int8: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}__{policy}"
    if grad_compression:
        cell_id += "__gradcomp"
    if kv_int8:
        cell_id += "__kvint8"
    shape = SHAPES[shape_name]
    cfg = get_config(arch).with_policy(PRESETS[policy])
    if kv_int8:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    ok, reason = cell_applicable(cfg, shape)
    record: dict = {
        "cell": cell_id, "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "policy": policy, "chips": 512 if multi_pod else 256,
    }
    if not ok:
        record["status"] = "n/a"
        record["reason"] = reason
        _write(out_dir, cell_id, record)
        return record
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.perf_counter()
        cell = build_cell(cfg, shape, mesh, grad_compression=grad_compression)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                cell["fn"],
                in_shardings=cell["in_shardings"],
                out_shardings=cell.get("out_shardings"),
                donate_argnums=cell["donate"],
            ).lower(*cell["args"])
            t_lower = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        print(compiled.memory_analysis())  # proves it fits
        ca = compiled.cost_analysis()
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        cost = hlo_cost.parse_hlo_cost(compiled.as_text())
        terms = hlo_cost.roofline_terms(cost)
        params_shape = jax.eval_shape(cell["model"].init, jax.random.key(0))
        counts = count_params(params_shape, cell["cfg"])
        mf = model_flops(cell["cfg"], shape, params_shape)
        chips = record["chips"]
        useful_ratio = mf / (cost.flops * chips) if cost.flops else 0.0
        dom_t = max(terms["t_compute_s"], terms["t_memory_s"], terms["t_collective_s"])
        record.update(
            status="ok",
            t_lower_s=round(t_lower, 1),
            t_compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
            ),
            cost_analysis_raw=dict(
                flops=ca.get("flops"), bytes_accessed=ca.get("bytes accessed"),
                note="XLA counts while bodies once; see hlo terms for scan-corrected",
            ),
            roofline=terms,
            params=counts,
            model_flops_global=mf,
            useful_flops_ratio=useful_ratio,
            roofline_fraction_estimate=(
                (mf / chips / hlo_cost.device_peaks().flops) / dom_t if dom_t else 0.0
            ),
        )
    except Exception as e:  # a failure here is a sharding/memory bug
        record["status"] = "fail"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-3000:]
    _write(out_dir, cell_id, record)
    return record


def _write(out_dir: str, cell_id: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(record, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--policy", default="paper_baseline", choices=tuple(PRESETS))
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            t0 = time.perf_counter()
            rec = run_cell(arch, shape, mp, args.policy, args.out,
                           args.grad_compression, args.kv_int8)
            status = rec["status"]
            n_fail += status == "fail"
            dom = rec.get("roofline", {}).get("dominant", "-")
            print(
                f"[{status:4s}] {rec['cell']:70s} {time.perf_counter()-t0:6.1f}s dominant={dom}",
                flush=True,
            )
            if status == "fail":
                print(rec["error"])
    print(f"done; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
