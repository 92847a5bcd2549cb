"""Smoke-size cells for the CPU tests of the chip benchmark: the same
harness, families and mixes, at widths a test run can hold.

Each family's smoke configuration is a file ``smoke/<family>.json``, in the
keys of the benchmark's configuration files; a new family brings its own,
and the tests that run every family find it by name."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.spec import Cell, load_json  # noqa: E402

SMOKE = Path(__file__).resolve().parent / "smoke"
#: the families that have a smoke configuration
FAMILIES = sorted(p.stem for p in SMOKE.glob("*.json"))


def config(family: str) -> dict:
    """The smoke configuration of ``family``, ``smoke/<family>.json``."""
    path = SMOKE / f"{family}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no smoke configuration for family {family!r}: "
                                f"add {path.relative_to(ROOT)}")
    return load_json(path)


DENSE = config("dense")
SSM = config("ssm")
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
    return Cell(name=f"smoke-{family}", chips=1, config=config(family), mix=mix,
                limits=LIMITS, end_to_end=(), per_layer=())
