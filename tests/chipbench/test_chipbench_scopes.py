"""The program's side of a profiler trace (``chipbench.scopes``): device ops
charged to the program's named scopes, the engine's ``serve.*`` host spans,
and the readers built on them.  Recorded on a TPU v5e by smoke-size traced
runs (``record_traced.py``) of a program with spans and scopes
(``*-spans``), and of one with neither (the older ``smoke-*`` fixtures)."""
from __future__ import annotations

import importlib
import json
import types
from pathlib import Path

import pytest

import chipbench_smoke  # noqa: F401
from chipbench import readers, scopes, trace, xspace

DATA = Path(__file__).resolve().parent / "data"
SPANS = {"smoke-dense-spans": "attention", "smoke-ssm-spans": "ssm"}
BARE = ["smoke-dense", "smoke-ssm"]
READERS = {"decode_gemm_ms": "gemm", "decode_attn_ms": "attention",
           "decode_ssm_ms": "ssm", "decode_select_ms": "select"}
SPAN_READERS = ["dispatch_ms_per_step", "emit_ms_per_step"]


def load(name):
    import jax

    path = DATA / f"{name}.xplane.pb"
    td = trace.TraceData.from_profile(jax.profiler.ProfileData.from_file(str(path)))
    return td, scopes.ProgramTrace.from_file(path)


@pytest.fixture(scope="module", params=sorted(SPANS))
def recorded(request):
    td, pt = load(request.param)
    chip = json.loads((DATA / f"{request.param}.json").read_text())
    return request.param, td, pt, chip


@pytest.fixture(autouse=True)
def trace_root(tmp_path, monkeypatch):
    """Every fixture trace where a run leaves its own, as the profiler
    lays it out."""
    for path in DATA.glob("*.xplane.pb"):
        dest = tmp_path / path.stem / "plugins" / "profile" / "t" / "host.xplane.pb"
        dest.parent.mkdir(parents=True)
        dest.symlink_to(path)
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)


def read(td, reader):
    """What a metric's reader gives on a run whose reduced trace is ``td``."""
    return importlib.import_module(f"chipbench.metrics.{reader}").read(
        types.SimpleNamespace(trace=td), "itl")


def decode_step_ms(td):
    spans = td.device_spans("modules", readers.DECODE_PROGRAM)
    return sum(s.dur for s in spans) / len(spans) * 1e-6


def test_charge_takes_the_outermost_scope():
    assert scopes.charge("jit(_masked_step)/while/body/closed_call/attention/"
                         "gemm/attn_qk/dot_general") == "attention"
    assert scopes.charge("jit(_masked_step)/while/body/gemm/mlp_up/dot_general") == "gemm"
    assert scopes.charge("jit(_masked_step)/select/jit(_where)/select_n") == "select"
    assert scopes.charge("jit(_masked_step)/while/body/ssm/gemm/ssd/mul") == "ssm"
    assert scopes.charge("jit(_masked_step)/while/body/select_n") is None
    assert scopes.charge(None) is None
    assert scopes.program_id("jit__masked_step(5539715528726895929)") == 5539715528726895929
    assert scopes.program_id("jit_add") is None


def test_metadata_names_every_operation_the_profile_holds(recorded):
    """The hand-read event metadata holds each XLA op event's instruction,
    with its program id and op_name."""
    import jax

    name, td, pt, _ = recorded
    meta = xspace.event_metadata(DATA / f"{name}.xplane.pb", "/device:TPU:")
    names = {n for table in meta.values() for n, _ in table.values()}
    pd = jax.profiler.ProfileData.from_file(str(DATA / f"{name}.xplane.pb"))
    for plane in pd.planes:
        if plane.name in meta:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    assert {e.name for e in line.events} <= names


def test_scope_map_covers_the_decode_program(recorded):
    name, td, pt, _ = recorded
    ops = [(scopes.program_id(m.name), s.name) for m, inside in pt.decode_ops(td)
           for s in inside]
    assert ops
    assert all(key in pt.tf_op for key in ops)
    charged = {scopes.charge(pt.tf_op[key]) for key in ops}
    assert {"gemm", "select", SPANS[name]} <= charged
    assert not ({"attention", "ssm"} - {SPANS[name]}) & charged


def test_scoped_metrics_sum_to_no_more_than_the_step(recorded):
    _, td, pt, _ = recorded
    parts = [read(td, r) for r in READERS]
    assert sum(p for p in parts if p is not None) <= decode_step_ms(td)
    split = pt.decode_scope_ms(td)
    assert sum(split.values()) <= decode_step_ms(td)
    assert all(v > 0 for v in split.values())


def test_idle_gaps_inside_a_step_carry_engine_spans(recorded):
    _, td, pt, chip = recorded
    steps = td.host_spans("chipbench.step")
    gaps = pt.idle_gaps(td)
    inside = [label for (label, _), (a, b) in zip(
        gaps, sorted(td.idle_gaps(), key=lambda g: g[0] - g[1]))
        if any(s.start <= (a + b) // 2 < s.end for s in steps)]
    assert inside
    assert all(label.startswith(scopes.SERVE_PREFIX) for label in inside)
    assert gaps == json.loads(json.dumps(chip["serve_idle_gaps"]))


def test_engine_spans_nest_in_the_benchmark_step(recorded):
    _, td, pt, _ = recorded
    steps = td.host_spans("chipbench.step")
    engine = pt.spans("step", td)
    assert engine
    for s in engine:
        assert any(c.start <= s.start and s.end <= c.end for c in steps)
    decodes = pt.spans("decode", td)
    for kind in ("decode.inputs", "decode.dispatch", "decode.sync", "emit"):
        kids = pt.spans(kind, td)
        assert kids
        assert all(any(d.start <= k.start and k.end <= d.end for d in decodes) for k in kids)


def test_readers_read_what_the_chip_run_printed(recorded):
    name, td, pt, chip = recorded
    assert td.window == tuple(chip["window"])
    printed = {k.partition(".")[0]: v["value"] for k, v in chip["metrics"].items()}
    for reader in list(READERS) + SPAN_READERS:
        value = read(td, reader)
        if reader in printed:
            assert value == pytest.approx(printed[reader], rel=1e-9), reader
        else:
            assert reader in ("decode_attn_ms", "decode_ssm_ms"), reader
    own = {"attention": "decode_attn_ms", "ssm": "decode_ssm_ms"}[SPANS[name]]
    assert set(SPAN_READERS + ["decode_gemm_ms", "decode_select_ms", own]) <= set(printed)
    # the existing metrics of the cell read as the chip run printed them too
    assert td.breakdown() == json.loads(json.dumps(chip["breakdown"]))


@pytest.mark.parametrize("name", BARE)
def test_a_program_without_spans_or_scopes_reads_nothing(name):
    td, pt = load(name)
    assert pt.serve == [] and pt.markers == td.window
    assert set(pt.decode_scope_ms(td)) == {None}
    for reader in list(READERS) + SPAN_READERS:
        assert read(td, reader) is None, reader
