"""The harness end to end on the CPU at smoke size: a sound run is correct,
every fault the serving cells can have makes ``correct`` false, and the
command refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chipbench_smoke as smoke
from chipbench import check, run
from chipbench.spec import load_cell

SEED = 2**31 + 77
SECONDS = 1.5
CELLS = [w["name"] for w in json.loads((smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def result(family="dense", breaker=None, precision=None):
    return run.run_cell(smoke.cell(family, precision), SEED, SECONDS, False,
                        breaker=breaker)


def altered_token(engine):
    """A token altered where it is produced: every decode token shifted."""
    step, vocab = engine._step, engine.model_decode.cfg.vocab

    def broken(params, tokens, state, active):
        tok, new = step(params, tokens, state, active)
        return (tok + 1) % vocab, new
    engine._step = broken


def state_unchanged(engine):
    """A step that returns its state unchanged: nothing reaches the cache."""
    step = engine._step

    def broken(params, tokens, state, active):
        tok, _ = step(params, tokens, state, active)
        return tok, state
    engine._step = broken


def half_batch(engine):
    """Half of the batch left out: the upper half of the slots never
    commits its step, and gets tokens decoded from stale state."""
    step, half = engine._step, engine.slots // 2

    def broken(params, tokens, state, active):
        keep = active & (jnp.arange(active.shape[0]) < half)
        return step(params, tokens, state, keep)
    engine._step = broken


@pytest.mark.parametrize("family", smoke.FAMILIES)
def test_sound_run_is_correct(family):
    r = result(family)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["readings"]["sampled_requests"] > 0
    assert r["compiles_in_window"] == {}
    assert set(r["setup_compiles"]) <= {e.rsplit("/", 1)[-1] for e in run.CompileCounter.EVENTS}
    assert list(r["compared"]) == ["served_gap", "prefill_err"]


@pytest.mark.parametrize("name", CELLS)
def test_cells_control_in_the_programs_place_is_not_correct(name):
    """Each cell's control (the reference a step below in precision), run
    through the harness at smoke size in the cell's family, comes out as not
    correct, while the program on the same run is correct."""
    cell = load_cell(name)
    r = run.run_cell(smoke.cell(cell.config["family"]), SEED, SECONDS, False,
                     control=cell.limits["control"])
    assert r["correct"], r["compared"]
    ok, compared = check.control_verdict(r["readings"], smoke.LIMITS)
    assert not ok, compared


@pytest.mark.parametrize("fault", [altered_token, state_unchanged, half_batch])
@pytest.mark.parametrize("family", smoke.FAMILIES)
def test_fault_in_timed_path_is_not_correct(family, fault):
    r = result(family, breaker=fault)
    assert not r["correct"], r["compared"]


def test_a_family_with_no_smoke_configuration_is_refused():
    """No fallback to another family's smoke cell: a new family's tests run
    on its own smoke configuration, or fail and name the file to add."""
    with pytest.raises(FileNotFoundError, match="tests/chipbench/smoke/no-such-family.json"):
        smoke.cell("no-such-family")


def test_end_to_end_metrics_of_a_window():
    r = result("dense")
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert r["device"]["platform"] == "cpu"


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(smoke.ROOT / "chipbench" / "run.py"), "--workload",
         "qwen05-chat-f32", "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=smoke.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cpu_has_no_peaks():
    with pytest.raises(ValueError):
        run.require_chips(type("J", (), {"devices": staticmethod(
            lambda: [type("D", (), {"platform": "tpu", "device_kind": "cpu"})()])}), 1)
    assert jax.devices()[0].platform == "cpu"


def test_ttft_is_read_alike_end_to_end_and_per_layer():
    """The client's TTFT tail: every request due in the window, one with no
    first token counted at the time it waited; the per-layer reader and the
    end-to-end reduction read the same number."""
    import types

    from chipbench.client import Drive, Record
    from chipbench.metrics import ttft_p95_ms
    from chipbench.traffic import TAIL, WINDOW, Arrival

    recs = {}
    for i in range(19):
        r = Record(Arrival(i, float(i), 384, 4, WINDOW), due_t=10.0 + i)
        r.token_t = [10.0 + i + 0.1 * (i + 1), 12.0 + i]
        recs[i] = r
    recs[19] = Record(Arrival(19, 19.0, 384, 4, WINDOW), due_t=29.0)
    recs[20] = Record(Arrival(20, 60.0, 384, 4, TAIL), due_t=70.0)
    recs[20].token_t = [90.0]
    d = Drive(recs, [], t_open=10.0, t_close=40.0, t_end=33.0)
    waits, failed = d.ttft()
    assert failed == 1 and len(waits) == 20
    assert max(waits) == pytest.approx(4.0)
    out, attempted, failed = run.end_to_end(d, 1.0, 30.0)
    per_layer = ttft_p95_ms.read(types.SimpleNamespace(drive=d), "itl")
    assert attempted == 20 and failed == 1
    assert per_layer == out["ttft_p95_ms"][0]
    assert per_layer == pytest.approx(1e3 * (1.9 + 0.05 * (4.0 - 1.9)))
