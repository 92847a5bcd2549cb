"""The reduction from profiler trace to metrics, on small traces recorded on
a TPU v5e by a smoke-size traced run of the harness (``data/``): busy time
against a brute-force union, idle gaps named by host spans, the engine's
programs found by name, and the numbers the chip run itself printed."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import chipbench_smoke  # noqa: F401
from chipbench import readers, trace

DATA = Path(__file__).resolve().parent / "data"
FIXTURES = ["smoke-dense", "smoke-ssm"]


@pytest.fixture(scope="module", params=FIXTURES)
def recorded(request):
    import jax

    pd = jax.profiler.ProfileData.from_file(str(DATA / f"{request.param}.xplane.pb"))
    chip = json.loads((DATA / f"{request.param}.json").read_text())
    return request.param, trace.TraceData.from_profile(pd), chip


def test_merge_and_cover_by_hand():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (7, 9), (12, 13)])
    assert m == [(0, 3), (5, 9), (12, 13)]
    assert trace.covered(m, 2, 6) == 2
    assert trace.covered(m, 0, 20) == 8
    assert trace.covered(m, 9, 12) == 0


def test_busy_matches_a_sweep_over_every_edge(recorded):
    _, td, _ = recorded
    t0, t1 = td.window
    dev = next(iter(td.ops))
    # count open operations across every start and end: busy where > 0
    edges = []
    for s in td.ops[dev]:
        a, b = max(s.start, t0), min(s.end, t1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert td.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < td.busy_s <= td.window_s
    gaps = td.idle_gaps()
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(td.window_s - td.busy_s, rel=1e-6)


def test_reduction_reads_what_the_chip_run_printed(recorded):
    _, td, chip = recorded
    assert td.window_s == pytest.approx(chip["window_s"], rel=1e-9)
    assert td.busy_s == pytest.approx(chip["busy_s"], rel=1e-9)
    assert td.breakdown() == json.loads(json.dumps(chip["breakdown"]))


def test_engine_programs_and_host_spans_are_found(recorded):
    _, td, chip = recorded
    assert td.device_spans("modules", readers.DECODE_PROGRAM)
    assert td.device_spans("modules", readers.PREFILL_PROGRAM)
    assert td.host_spans("chipbench.step")
    labels = {label for label, _ in td.breakdown()["idle_gaps"]}
    assert labels <= {"chipbench.step", "chipbench.wait", "chipbench.submit", "none"}
    # the per-layer readers found something in each traced run
    assert {"decode_step_ms.itl", "prefill_ms.ttft", "idle_share.itl",
            "host_ms_per_step.itl"} <= set(chip["metrics"])


def test_breakdown_fits_the_contract(recorded):
    _, td, _ = recorded
    b = td.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert len(b[key]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in b[key])
