"""repro.obs: tracer ring, exporters, timeline, profiler, and the
engine-integration contracts — zero jit-visible cost when off, schema-valid
Chrome traces, and event streams that replay through the scheduler
invariant harness (tests/scheduler_model.py consumer mode)."""
import dataclasses
import json

import numpy as np
import pytest
import jax

from repro.configs import get_smoke_config
from repro.core.policy import NATIVE_F32
from repro.models import build_model
from repro.obs import (
    NULL_TRACER,
    Event,
    PhaseProfiler,
    TraceConfig,
    Tracer,
    precision_timeline,
    span_violations,
    to_chrome,
    to_prometheus,
    validate_chrome,
)
from repro.serve import (
    CacheConfig,
    Request,
    RequestClass,
    SchedulingConfig,
    ServeConfig,
    ServeEngine,
    Tenant,
)

from scheduler_model import FINISH, SUBMIT, check_replay, log_from_trace


def _tiny(arch="qwen1.5-0.5b", **over):
    cfg = get_smoke_config(arch).with_policy(NATIVE_F32)
    cfg = dataclasses.replace(cfg, **{"n_layers": 2, **over})
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _reqs(cfg, n, *, prompt_len=6, max_new=5, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, prompt_len)
                    .astype(np.int32), max_new=max_new, rid=i, **kw)
            for i in range(n)]


class TestTracer:
    def test_ring_capacity_and_dropped(self):
        tr = Tracer(TraceConfig(capacity=4), clock=lambda: 0.0)
        for i in range(10):
            tr.emit("token", rid=i)
        assert tr.emitted == 10
        assert len(tr.events) == 4
        assert tr.dropped == 6
        assert [e.rid for e in tr.events] == [6, 7, 8, 9]  # oldest dropped

    def test_counters_gauges_and_step_stamp(self):
        tr = Tracer(clock=lambda: 1.5)
        tr.inc("x")
        tr.inc("x", 2)
        tr.set_gauge("g", 7)
        tr.step = 3
        tr.emit("decode_step")
        tr.emit("submit", step=9)  # explicit step overrides
        assert tr.counters["x"] == 3
        assert tr.gauges["g"] == 7.0
        assert [e.step for e in tr.events] == [3, 9]
        assert tr.events[0].ts == 1.5

    def test_invalid_capacity(self):
        with pytest.raises(ValueError, match="capacity"):
            TraceConfig(capacity=0)

    def test_null_tracer_is_inert_and_refuses_export(self):
        NULL_TRACER.emit("token", rid=1)
        NULL_TRACER.inc("x")
        NULL_TRACER.set_gauge("g", 1)
        assert not NULL_TRACER.enabled
        assert NULL_TRACER.events == () and NULL_TRACER.dropped == 0
        assert NULL_TRACER.describe() == "tracing off"
        for call in (NULL_TRACER.chrome, NULL_TRACER.prometheus,
                     NULL_TRACER.precision_timeline):
            with pytest.raises(RuntimeError, match="tracing is off"):
                call()


class TestExport:
    def _lifecycle(self):
        t = iter(float(i) for i in range(100))
        return [
            Event(next(t), 0, "submit", rid=0),
            Event(next(t), 1, "admit", rid=0, slot=0),
            Event(next(t), 1, "token", rid=0, slot=0),
            Event(next(t), 2, "preempt", rid=0, slot=0, cause="priority"),
            Event(next(t), 3, "resume", rid=0, slot=1),
            Event(next(t), 3, "token", rid=0, slot=1),
            Event(next(t), 3, "done", rid=0, slot=1, cause="budget"),
        ]

    def test_chrome_valid_and_spans_cover_lifecycle(self):
        doc = to_chrome(self._lifecycle(), {"tokens_out": 2}, {"g": 1.0})
        assert validate_chrome(doc) == []
        spans = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["pid"] == 2]
        # queued -> running -> preempted -> running: four lifecycle spans
        assert [s["name"] for s in spans] == [
            "queued", "running", "preempted", "running"]
        counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
        assert {"tokens_out", "g"} <= counters
        json.dumps(doc)  # must be serializable as-is

    def test_chrome_inflight_spans_closed_at_ring_end(self):
        events = self._lifecycle()[:2]  # submit + admit, never done
        doc = to_chrome(events)
        assert validate_chrome(doc) == []
        spans = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["pid"] == 2]
        assert [s["name"] for s in spans] == ["queued", "running"]

    def test_validate_catches_malformed(self):
        assert validate_chrome({}) == ["traceEvents missing or not a list"]
        bad_dur = {"traceEvents": [
            {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0, "dur": -1}]}
        assert any("bad dur" in p for p in validate_chrome(bad_dur))
        overlap = {"traceEvents": [
            {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0, "dur": 10},
            {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 5, "dur": 10},
        ]}
        assert any("partially overlaps" in p for p in validate_chrome(overlap))
        missing = {"traceEvents": [{"ph": "i", "pid": 1}]}
        assert any("missing keys" in p for p in validate_chrome(missing))

    def test_span_violations(self):
        assert span_violations(self._lifecycle()) == []
        bad = [Event(0.0, 0, "admit", rid=1, slot=0)]  # admit before submit
        assert span_violations(bad)
        twice = self._lifecycle() + [Event(99.0, 4, "admit", rid=0, slot=1)]
        assert any("after done" in p for p in span_violations(twice))
        resume_running = [
            Event(0.0, 0, "submit", rid=2),
            Event(1.0, 1, "admit", rid=2, slot=0),
            Event(2.0, 2, "resume", rid=2, slot=0),
        ]
        assert span_violations(resume_running)

    def test_prometheus_text(self):
        text = to_prometheus({"tokens_out": 5, "a.b": 1}, {"occ": 0.5})
        assert "# TYPE repro_obs_tokens_out counter\n" in text
        assert "repro_obs_tokens_out 5\n" in text
        assert "repro_obs_a_b 1" in text  # sanitized name
        assert "# TYPE repro_obs_occ gauge\nrepro_obs_occ 0.5" in text
        assert to_prometheus({}, {}) == ""


class TestTimeline:
    def test_merges_three_precision_axes(self):
        rows = precision_timeline([
            Event(0.0, 1, "decode", data={"mode": "M16", "n_active": 2}),
            Event(1.0, 4, "mode_switch", data={"mode": "M24",
                                               "sites": {"mlp": "M24"}}),
            Event(2.0, 6, "draft_shift", data={"shift": 1}),
            Event(3.0, 8, "tier_tick", data={"keep": 5, "depth": 1}),
            Event(4.0, 8, "mode_switch", data={"mode": "M16"}),
        ])
        assert [r["step"] for r in rows] == [1, 4, 6, 8]
        assert rows[0]["mode"] == "M16" and rows[0]["draft_shift"] is None
        assert rows[1]["mode"] == "M24"
        assert rows[2]["draft_shift"] == 1 and rows[2]["mode"] == "M24"
        # step 8: tier tick and a second mode switch merge into one row
        assert rows[3]["tier_keep"] == 5 and rows[3]["mode"] == "M16"
        assert rows[3]["draft_shift"] == 1  # carried forward

    def test_empty(self):
        assert precision_timeline([]) == []


class TestProfiler:
    def test_phase_accounting_and_recompile_detection(self):
        tr = Tracer(clock=lambda: 0.0)
        p = PhaseProfiler(tr)
        p.record("decode", 0.5, tokens=10)
        p.record("decode", 0.5, tokens=10)
        p.observe_cache("decode_step", 1)
        p.observe_cache("decode_step", 1)  # stable: no recompile
        assert p.recompiles == 0
        p.observe_cache("decode_step", 3)  # grew by 2
        assert p.recompiles == 2
        snap = p.snapshot()
        assert snap["phases"]["decode"] == {
            "calls": 2, "wall_s": 1.0, "tokens": 20, "tok_s": 20.0}
        assert tr.counters["recompiles"] == 2
        assert [e.kind for e in tr.events] == ["recompile"]
        p.observe_cache("prefill", None)  # unavailable cache: no-op
        assert p.recompiles == 2


class TestEngineTracing:
    def test_zero_overhead_pin_tokens_and_compiles(self):
        # THE tentpole contract: tracing must be invisible to jit — same
        # tokens, same compile counts, traced vs untraced
        cfg, model, params = _tiny()
        reqs = _reqs(cfg, 4)
        base = ServeConfig(batch_slots=2, max_len=24)
        e_off = ServeEngine(model, params, config=base)
        e_on = ServeEngine(model, params, config=dataclasses.replace(
            base, trace=TraceConfig()))
        assert e_off.tracer is NULL_TRACER and e_on.tracer.enabled
        out_off = e_off.generate_batch(reqs)
        out_on = e_on.generate_batch(reqs)
        assert out_off == out_on
        assert e_off.decode_compile_count == e_on.decode_compile_count == 1

    def test_trace_true_means_default_config(self):
        cfg, model, params = _tiny()
        eng = ServeEngine(
            model, params,
            config=ServeConfig(batch_slots=1, max_len=16, trace=True))
        assert eng.tracer.enabled
        assert eng.tracer.config.capacity == TraceConfig().capacity

    def test_plain_run_replays_and_exports(self, tmp_path):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=24, trace=TraceConfig()))
        eng.generate_batch(_reqs(cfg, 4))
        log = check_replay(eng)
        assert sum(1 for _, k, _, _ in log if k == SUBMIT) == 4
        assert sum(1 for _, k, _, _ in log if k == FINISH) == 4
        path = tmp_path / "trace.json"
        doc = eng.tracer.export_chrome(str(path))
        assert validate_chrome(doc) == []
        assert validate_chrome(json.loads(path.read_text())) == []
        # counters reached the registry and the text exposition
        assert eng.tracer.counters["tokens_out"] == 20
        assert "repro_obs_tokens_out 20" in eng.tracer.prometheus()

    def test_zero_budget_request_traces_done_without_admit(self):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=1, max_len=16, trace=TraceConfig()))
        rng = np.random.default_rng(0)
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new=0, rid=0))
        eng.drain()
        kinds = [e.kind for e in eng.tracer.events if e.rid == 0]
        assert kinds == ["submit", "done"]
        done = [e for e in eng.tracer.events if e.kind == "done"][0]
        assert done.cause == "zero_budget" and done.slot == -1
        check_replay(eng)

    def test_multi_tenant_preemption_replay_and_causes(self):
        cfg, model, params = _tiny()
        sched = SchedulingConfig(
            tenants=[Tenant("hot", priority=0), Tenant("bulk", priority=2)],
            classes=[RequestClass("c", prompt_len=5, max_new=6)],
            min_quantum=1)
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=1, max_len=24, scheduling=sched,
            trace=TraceConfig()))
        rng = np.random.default_rng(0)
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new=6, rid=0,
                           tenant="bulk", rclass="c"))
        eng.step()
        eng.step()
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new=3, rid=1,
                           tenant="hot", rclass="c"))
        eng.drain()
        events = list(eng.tracer.events)
        pre = [e for e in events if e.kind == "preempt"]
        assert pre and all(e.cause == "priority" for e in pre)
        assert any(e.kind == "preempt_plan" for e in events)  # scheduler emits
        assert any(e.kind == "resume" and e.rid == 0 for e in events)
        check_replay(eng)

    def test_spec_round_events(self):
        from repro.spec import SpecConfig

        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=32,
            spec=SpecConfig(k=2, draft_shift=1), trace=TraceConfig()))
        eng.generate_batch(_reqs(cfg, 3, max_new=6))
        events = list(eng.tracer.events)
        rounds = [e for e in events if e.kind == "spec_round"]
        assert rounds
        for e in rounds:
            d = e.data
            assert d["drafted"] == eng.spec.k * d["n_active"]
            assert 0 <= d["agreed"] <= d["drafted"]
        assert eng.tracer.counters["spec_rounds"] == len(rounds)
        check_replay(eng)

    def test_paged_run_traces_cache_events_and_replays(self):
        # the hybrid local-window arch is where ring wrap writes back into
        # shared prompt pages mid-decode, so COW forks actually fire
        cfg, model, params = _tiny("recurrentgemma-9b", n_layers=3)
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=3, max_len=48,
            cache=CacheConfig(layout="paged", page_size=4),
            trace=TraceConfig()))
        prompt = np.asarray([7] * 8, np.int32)  # shared prefix -> shared pages
        for i in range(3):
            eng.submit(Request(prompt=np.append(prompt, i).astype(np.int32),
                               max_new=30, rid=i))
        eng.drain()
        kinds = {e.kind for e in eng.tracer.events}
        assert "prefix_share" in kinds
        assert "cow_fork" in kinds
        for e in eng.tracer.events:
            if e.kind == "cow_fork":
                assert e.cause == "shared_page_write"
        check_replay(eng)

    def test_adapt_run_emits_decisions_and_timeline(self):
        from repro.adapt import SLO
        from repro.serve import AdaptConfig

        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=32,
            adapt=AdaptConfig(slo=SLO(max_err=0.5), adapt_every=2),
            trace=TraceConfig()))
        eng.generate_batch(_reqs(cfg, 3, max_new=8))
        events = list(eng.tracer.events)
        decisions = [e for e in events if e.kind == "adapt_decision"]
        assert decisions
        assert all(e.cause in ("hold", "cooldown", "err_violation",
                               "latency_pressure", "clean_streak")
                   for e in decisions)
        switches = [e for e in events if e.kind == "mode_switch"]
        assert len(switches) == eng.metrics.mode_switches
        for e in switches:
            assert e.cause in ("err_violation", "latency_pressure",
                               "clean_streak")
            assert set(e.data["sites"]) == set(eng.mode_table.modes())
        rows = eng.tracer.precision_timeline()
        assert rows and rows[0]["mode"] is not None
        if switches:
            assert any(r["sites"] is not None for r in rows)
        check_replay(eng)

    def test_describe_consolidation(self):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=1, max_len=16, trace=TraceConfig()))
        eng.generate_batch(_reqs(cfg, 1))
        d = eng.describe()
        assert {"plans", "adaptation", "speculation", "tenancy",
                "cache", "trace", "profile"} <= set(d)
        # thin-wrapper contract: the legacy helpers read the same source
        assert eng.describe_plans() == d["plans"]
        assert eng.describe_cache() == d["cache"]
        assert eng.describe_adaptation() == d["adaptation"]
        assert eng.describe_speculation() == d["speculation"]
        assert eng.describe_tenancy() == d["tenancy"]
        block = eng.format_describe()
        for key in d:
            assert f"-- {key} --" in block
        # untraced engines don't grow the extra keys
        e2 = ServeEngine(model, params,
                         config=ServeConfig(batch_slots=1, max_len=16))
        assert set(e2.describe()) == {"plans", "adaptation", "speculation",
                                      "tenancy", "cache"}

    def test_log_from_trace_skip_causes(self):
        evs = [
            Event(0.0, 1, "preempt", rid=0, slot=0, cause="page_pressure"),
            Event(1.0, 1, "preempt", rid=1, slot=1, cause="priority"),
            Event(2.0, 1, "decode_step", data={"dur_ms": 1.0}),  # dropped
        ]
        full = log_from_trace(evs)
        assert len(full) == 2
        filtered = log_from_trace(evs, skip_causes=("page_pressure",))
        assert [rid for _, _, rid, _ in filtered] == [1]


class TestSpans:
    def test_spans_nest_carry_duration_parent_and_rid_in_order(self):
        t = iter(float(i) for i in range(100))
        tr = Tracer(clock=lambda: next(t))
        with tr.span("step") as step:
            with tr.span("admit", rid=7, slot=1, prompt_len=5) as admit:
                with tr.span("prefill", rid=7, slot=1):
                    pass
            tr.emit("token", rid=7)
            with tr.span("decode") as dec:
                dec.data["n_active"] = 3
        kinds = [e.kind for e in tr.events]
        assert kinds == ["step", "admit", "prefill", "token", "decode"]
        ev = {e.kind: e for e in tr.events}
        assert ev["step"] is step and ev["admit"] is admit
        assert [e.ts for e in tr.events] == sorted(e.ts for e in tr.events)
        assert ev["step"].data["parent"] is None
        assert ev["admit"].data["parent"] == "step"
        assert ev["prefill"].data["parent"] == "admit"
        assert ev["decode"].data["parent"] == "step"
        assert (ev["prefill"].rid, ev["prefill"].slot) == (7, 1)
        assert ev["admit"].data["prompt_len"] == 5
        assert ev["decode"].data["n_active"] == 3
        # clock reads: step 0, admit 1, prefill 2..3, admit ends 4, token 5,
        # decode 6..7, step ends 8
        assert ev["prefill"].data["dur_ms"] == 1e3
        assert ev["admit"].data["dur_ms"] == 3e3
        assert ev["step"].data["dur_ms"] == 8e3
        assert tr.emitted == len(tr.events) == 5 and tr._open == []

    def test_span_closes_on_exception(self):
        tr = Tracer(clock=lambda: 0.0)
        with pytest.raises(ValueError):
            with tr.span("step"):
                raise ValueError("boom")
        assert tr._open == [] and tr.events[0].data["dur_ms"] == 0.0

    def test_null_span_is_one_shared_no_op(self):
        a = NULL_TRACER.span("step")
        b = NULL_TRACER.span("admit", rid=1, slot=2, prompt_len=4)
        assert a is b
        with a as ev:
            assert ev is None
        assert NULL_TRACER.events == () and NULL_TRACER.emitted == 0

    def test_chrome_renders_every_span_nested(self):
        t = iter(float(i) for i in range(100))
        tr = Tracer(clock=lambda: next(t))
        with tr.span("step"):
            with tr.span("decode"):
                with tr.span("decode.sync"):
                    pass
            with tr.span("adapt"):
                pass
        doc = tr.chrome()
        assert validate_chrome(doc) == []
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert set(spans) == {"step", "decode", "decode.sync", "adapt"}
        assert spans["step"]["ts"] == 0.0 and spans["step"]["dur"] == 7e6
        assert spans["decode.sync"]["ts"] == 2e6
        assert spans["decode.sync"]["args"]["parent"] == "decode"

    @pytest.mark.parametrize("path", ["plain", "spec", "adapt"])
    def test_span_sites_keep_tokens_and_compiles(self, path):
        from repro.adapt import SLO
        from repro.serve import AdaptConfig
        from repro.spec import SpecConfig

        cfg, model, params = _tiny()
        reqs = _reqs(cfg, 4, max_new=6)
        extra = {"plain": {}, "spec": {"spec": SpecConfig(k=2, draft_shift=1)},
                 "adapt": {"adapt": AdaptConfig(slo=SLO(max_err=0.5),
                                                adapt_every=2)}}[path]
        base = ServeConfig(batch_slots=2, max_len=32, **extra)
        e_off = ServeEngine(model, params, config=base)
        e_on = ServeEngine(model, params, config=dataclasses.replace(
            base, trace=TraceConfig()))
        assert e_off.generate_batch(reqs) == e_on.generate_batch(reqs)
        assert e_off.decode_compile_count == e_on.decode_compile_count
        assert e_off.spec_compile_count == e_on.spec_compile_count
        events = list(e_on.tracer.events)
        kinds = {e.kind for e in events}
        round_kind = "spec_round" if path == "spec" else "decode"
        assert {"step", "schedule", "admit", "prefill", "scatter", round_kind,
                "decode.inputs", "decode.dispatch", "decode.sync",
                "emit"} <= kinds
        if path == "adapt":
            assert "adapt" in kinds
        parents = {e.kind: e.data["parent"] for e in events
                   if e.data and "dur_ms" in e.data}
        assert parents["step"] is None
        assert parents["schedule"] == parents["admit"] == "step"
        assert parents["prefill"] == "admit"
        assert parents["scatter"] == "prefill"
        assert parents[round_kind] == "step"
        for kid in ("decode.inputs", "decode.dispatch", "decode.sync", "emit"):
            assert parents[kid] == round_kind
        assert all(e.data["dur_ms"] >= 0 for e in events
                   if e.data and "dur_ms" in e.data)
        assert validate_chrome(e_on.tracer.chrome()) == []
        check_replay(e_on)

    def test_admit_events_are_the_fresh_admissions_in_order(self):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=24, trace=TraceConfig()))
        admitted = []
        admit = eng.scheduler.admit

        def recording(**kw):
            out = admit(**kw)
            admitted.extend((eng.scheduler.clock, t.rid, slot) for slot, t in out
                            if slot >= 0 and not t.tokens)
            return out
        eng.scheduler.admit = recording
        eng.generate_batch(_reqs(cfg, 5))
        events = list(eng.tracer.events)
        admits = [e for e in events if e.kind == "admit"]
        assert [(e.step, e.rid, e.slot) for e in admits] == admitted
        # the admit event opens the admission: its prefill starts after it
        prefill = {e.rid: e for e in events if e.kind == "prefill"}
        for e in admits:
            assert e.ts <= prefill[e.rid].ts
            assert e.data["prompt_len"] == 6 and e.data["parent"] == "step"
        log = check_replay(eng)
        assert sum(1 for _, k, _, _ in log if k == "admit") == 5

    def test_prefill_profile_takes_the_span_duration(self):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=24, trace=TraceConfig()))
        eng.generate_batch(_reqs(cfg, 3))
        spans = [e.data["dur_ms"] for e in eng.tracer.events
                 if e.kind == "prefill"]
        st = eng.profiler.phases["prefill"]
        assert st.calls == len(spans) == 3
        assert st.wall_s == pytest.approx(sum(spans) / 1e3, rel=1e-12)

    def test_spans_reach_the_profiler(self, tmp_path):
        cfg, model, params = _tiny()
        eng = ServeEngine(model, params, config=ServeConfig(
            batch_slots=2, max_len=24, trace=TraceConfig()))
        reqs = _reqs(cfg, 2, max_new=3)
        eng.generate_batch(reqs[:1])  # compile outside the profile
        before = len(eng.tracer.events)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.generate_batch(reqs[1:])
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        pd = jax.profiler.ProfileData.from_file(str(path))
        names = [e.name for plane in pd.planes if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name.startswith("serve.")]
        assert {"serve.step", "serve.schedule", "serve.admit", "serve.prefill",
                "serve.scatter", "serve.decode", "serve.decode.inputs",
                "serve.decode.dispatch", "serve.decode.sync",
                "serve.emit"} <= set(names)
        steps = [e for e in list(eng.tracer.events)[before:] if e.kind == "step"]
        assert names.count("serve.step") == len(steps) > 0
