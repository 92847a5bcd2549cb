"""Calibration of a cell on the chip, in one process (not part of a run).

    python3 chipbench/calibrate.py knee   --workload <cell> --seconds 20 --rates 2,4,6
    python3 chipbench/calibrate.py limits --workload <cell> --seconds 20 --seeds 1,2,3

``knee`` serves the cell's mix with Poisson arrivals at each rate (req/s)
and prints, per rate, the TTFT and ITL tails, tokens per second, the median
step time by the prompt length it admitted, and how many requests due in the
window were still waiting at its close: the knee is the highest rate at
which that backlog does not grow.  ``limits`` runs the cell on each seed and
prints the compared numbers of the program beside those of the control, the
reference a step below the configuration's precision (``control`` in
``limits/<cell>.json``), and the verdict of the cell's committed limits on
each: ``correct`` for the program, ``control_correct`` for the control in
its place.  The limits are set from these readings.  Output lines are JSON,
one per run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, run  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402


def at_rate(mix: dict, rate: float) -> dict:
    return dict(mix, arrivals={"process": "poisson", "rate": rate})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="1")
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax

    run.configure_jax(jax)
    run.require_chips(jax, cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.what == "knee":
        for rate in (float(x) for x in args.rates.split(",")):
            c = dataclasses.replace(cell, mix=at_rate(cell.mix, rate),
                                    end_to_end=({"name": "ttft_p95_ms"}, {"name": "itl_p95_ms"},
                                                {"name": "tok_s"}))
            t = time.perf_counter()
            r = run.run_cell(c, seeds[0], args.seconds, False, t_process=t)
            print(json.dumps({"rate": rate,
                              "metrics": r["metrics"], "attempted": r["attempted"],
                              "failed": r["failed"], "waiting_at_close": r["waiting_at_close"],
                              "step_ms": r["step_ms"], "correct": r["correct"]}), flush=True)
    else:
        for seed in seeds:
            t = time.perf_counter()
            r = run.run_cell(cell, seed, args.seconds, False, t_process=t,
                             control=cell.limits["control"])
            control_ok, _ = check.control_verdict(r["readings"], cell.limits)
            print(json.dumps({"seed": seed, "readings": r["readings"],
                              "correct": r["correct"], "control_correct": control_ok}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
