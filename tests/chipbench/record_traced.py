"""Record a smoke-size traced run of the harness on a TPU, for the tests of
the trace reduction: ``data/<name>.xplane.pb`` (the profiler trace of the
window's last ``--trace-seconds``) and ``data/<name>.json`` (what the run
printed beside what the reduction read from the trace).

    python3 tests/chipbench/record_traced.py --family dense --name smoke-dense-spans

The smoke cell of ``chipbench_smoke`` runs with the per-layer metrics of the
benchmark's cell of the same family.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys

import chipbench_smoke as smoke
from chipbench import run, scopes
from chipbench.spec import load_cell

DATA = smoke.ROOT / "tests" / "chipbench" / "data"
CELL_OF = {"dense": "qwen05-chat-f32", "ssm": "mamba2-burst-a5"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--family", choices=sorted(CELL_OF), required=True)
    ap.add_argument("--name", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    ap.add_argument("--seconds", type=float, default=1.5)
    ap.add_argument("--trace-seconds", type=float, default=0.25)
    args = ap.parse_args(argv)
    import jax

    run.configure_jax(jax)
    run.require_chips(jax, 1)
    cell = dataclasses.replace(smoke.cell(args.family),
                               per_layer=load_cell(CELL_OF[args.family]).per_layer)
    run.TRACE_SECONDS = args.trace_seconds
    out = DATA / f"{args.name}.xplane.pb"
    read = {}
    per_layer = run.per_layer

    def keeping(cell, ctx):
        files = glob.glob(str(run.CACHE / "trace" / cell.name / "**" / "*.xplane.pb"),
                          recursive=True)
        shutil.copy(max(files, key=os.path.getmtime), out)
        pt = scopes.of(ctx)
        read.update(window=list(ctx.trace.window),
                    decode_scope_ms={str(k): v for k, v in pt.decode_scope_ms(ctx.trace).items()},
                    serve_idle_gaps=pt.idle_gaps(ctx.trace))
        return per_layer(cell, ctx)

    run.per_layer = keeping
    result = run.run_cell(cell, args.seed, args.seconds, True)
    doc = {"correct": result["correct"], "metrics": result["metrics"],
           "busy_s": result["device"]["busy_s"], "window_s": result["device"]["window_s"],
           "breakdown": result["breakdown"], **read}
    (DATA / f"{args.name}.json").write_text(json.dumps(doc, indent=1) + "\n")
    print(json.dumps(doc))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
