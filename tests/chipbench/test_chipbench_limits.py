"""Each cell's committed limits against the readings they were set from,
recorded on the chip at the cell's own size (``calibrate.py limits``, one
JSON line per seed in ``data/limits-<cell>.jsonl``): the program's readings
are correct under them, and the control's, judged by the harness's own
comparison, are not."""
from __future__ import annotations

import json

import pytest

import chipbench_smoke as smoke
from chipbench import check
from chipbench.spec import load_cell

CELLS = [w["name"] for w in json.loads((smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]
DATA = smoke.ROOT / "tests" / "chipbench" / "data"


def recorded(name):
    lines = (DATA / f"limits-{name}.jsonl").read_text().splitlines()
    return [json.loads(x) for x in lines if x.strip()]


@pytest.mark.parametrize("name", CELLS)
def test_committed_limits_pass_the_program_and_fail_the_control(name):
    limits = load_cell(name).limits
    runs = recorded(name)
    assert len({r["seed"] for r in runs}) >= 3
    for r in runs:
        ok, compared = check.verdict(r["readings"], limits)
        assert ok, (r["seed"], compared)
        control_ok, compared = check.control_verdict(r["readings"], limits)
        assert not control_ok, (r["seed"], compared)
