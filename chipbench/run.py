"""Run one benchmark cell once on the attached accelerator.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under ``configs/`` and a traffic mix under
``mixes/``, named in ``BENCHMARK.json``) is served in this process through
``ServeEngine.submit``/``step``, behind the open-loop client of
``chipbench.client``.  Set-up draws the weights from the seed on the device,
warms every shape the mix sends and runs a short warm-in of its traffic;
then the window is measured.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, read by ``metrics/<name>.py``
from the host clock, the engine's tracer and a profiler trace of the
window's last seconds.  Both check what the window served against the plain
reference (``chipbench.check``).

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits nonzero and prints
no result.  JAX's compilation cache is kept at ``.chipbench_cache/jax``
inside the checkout, with no minimum compile time, so only a checkout's
first run compiles.  ``setup_compiles`` in the result counts JAX's compile
events during set-up: ``backend_compile_duration`` fires for every program
set up, ``cache_retrieval_time_sec`` for each one read from that cache, so a
cold run reads none and a warm one all.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".chipbench_cache"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, traffic  # noqa: E402
from chipbench.client import drive, no_annotation  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.spec import arch_fields, load_cell  # noqa: E402
from chipbench.weights import gemm_shapes  # noqa: E402
from chipbench.workcount import Dims  # noqa: E402

#: seconds of the window's end that a ``--trace 1`` run profiles
TRACE_SECONDS = 10.0
#: rids of warm-up requests, apart from the traffic's
WARMUP_RID = 1 << 40


class NoChip(RuntimeError):
    pass


def configure_jax(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[0]


def build(cell, seed: int, trace: bool):
    """The program under test: model, weights from the seed, engine."""
    from repro.configs import get_config
    from repro.core.policy import PRESETS
    from repro.models import build_model
    from repro.obs import TraceConfig
    from repro.serve import ServeConfig, ServeEngine

    from chipbench.weights import make_params

    conf, mix = cell.config, cell.mix
    cfg = dataclasses.replace(get_config(conf["arch"]), **arch_fields(conf))
    ask = mix["precision"]
    cfg = cfg.with_policy(PRESETS[ask.get("policy", "native_f32")])
    model = build_model(cfg)
    params = make_params(model.init, seed)
    srv = conf["serving"]
    engine = ServeEngine(model, params, config=ServeConfig(
        batch_slots=int(srv["slots"]), max_len=int(srv["max_len"]),
        accuracy=ask.get("accuracy"),
        prefill_tokens=max(traffic.lengths_used(mix)),
        tune_table=False,
        trace=TraceConfig(capacity=1 << 22) if trace else None))
    return cfg, params, engine


def warm_up(engine, mix, vocab: int) -> None:
    """Compile every shape the mix sends: one prefill per prompt length and
    the decode step, through the engine's own entry points."""
    from repro.serve import Request

    rng = traffic.seed_rng(0, 4)
    for i, n in enumerate(traffic.lengths_used(mix)):
        engine.submit(Request(prompt=rng.integers(0, vocab, n).astype("int32"),
                              max_new=3, rid=WARMUP_RID + i))
    engine.drain()


class Profiler:
    """Profiles the window's last ``TRACE_SECONDS`` into one trace file,
    with host markers at its ends (``chipbench.trace_open``/``_close``)."""

    def __init__(self, jax, directory: Path, t_close: float):
        self.jax, self.dir = jax, directory
        self.t_start = t_close - TRACE_SECONDS
        self.on = False
        self.window = None

    def annotate(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def tick(self, now: float) -> None:
        if not self.on and self.window is None and now >= self.t_start:
            shutil.rmtree(self.dir, ignore_errors=True)
            # host spans at TraceMe level, no Python function tracing
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.on = True
            with self.annotate("chipbench.trace_open"):
                self.t0 = time.perf_counter()

    def stop(self) -> None:
        if self.on:
            with self.annotate("chipbench.trace_close"):
                t1 = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.on = False
            self.window = (self.t0, t1)


class CompileCounter:
    """Programs traced, compiled or read from the cache, by host time, from
    JAX's monitoring events: any inside the window is a shape the warm-up
    missed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.times: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.times.append((time.perf_counter(), event.rsplit("/", 1)[-1]))

    def between(self, t0: float, t1: float) -> dict:
        out: dict[str, int] = {}
        for t, name in self.times:
            if t0 <= t <= t1:
                out[name] = out.get(name, 0) + 1
        return out


class GcPauses:
    """Pauses of Python's cyclic garbage collector, by host time."""

    def __init__(self):
        self.spans: list[tuple[float, float, int]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.spans.append((self._t0, time.perf_counter(), info["generation"]))

    def close(self):
        gc.callbacks.remove(self._on)

    def longest_between(self, t0: float, t1: float, n: int = 3) -> list:
        inside = [((b - a) * 1e3, g) for a, b, g in self.spans if t0 <= a < t1]
        return sorted(inside)[-n:]


def pct(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(d, setup_s: float, seconds: float) -> tuple[dict, int, int]:
    """The client-side metrics of a window, its attempted and failed
    counts; TTFT as ``Drive.ttft`` takes it."""
    ttft, failed = d.ttft()
    gaps, emitted = [], 0
    for r in d.records.values():
        ts = r.token_t
        emitted += sum(d.t_open <= t < d.t_close for t in ts)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if d.t_open <= b < d.t_close]
    out = {"setup_s": (setup_s, "s"), "tok_s": (emitted / seconds, "tokens/s")}
    if ttft:
        out["ttft_p95_ms"] = (pct(ttft, 95) * 1e3, "ms")
    if gaps:
        out["itl_p95_ms"] = (pct(gaps, 95) * 1e3, "ms")
    attempted = len(ttft) if ttft else sum(
        1 for r in d.records.values() if r.submit_t is not None and r.submit_t < d.t_close)
    return out, attempted, failed


def step_ms(steps) -> dict:
    """Median host milliseconds of the window's steps that admitted no
    prompt (``"0"``) and of those that admitted exactly one, by its length."""
    import numpy as np

    by: dict[str, list] = {}
    for s in steps:
        if len(s.prefills) <= 1:
            key = str(s.prefills[0]) if s.prefills else "0"
            by.setdefault(key, []).append((s.t1 - s.t0) * 1e3)
    return {k: [float(np.median(v)), len(v)] for k, v in sorted(by.items(), key=lambda kv: int(kv[0]))}


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        base, _, suffix = m["name"].partition(".")
        reader = importlib.import_module(f"chipbench.metrics.{base}")
        value = reader.read(ctx, suffix)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_process: float = T_PROCESS, breaker=None, control=None) -> dict:
    """One run of a cell; returns the result object.  ``breaker(engine)``
    lets a test break the timed path; ``control`` adds the control's
    readings at that precision."""
    import jax

    from repro.serve import Request

    counter = CompileCounter(jax)
    pauses = GcPauses()
    cfg, params, engine = build(cell, seed, trace)
    dims = Dims.of(cfg, cell.config, engine.model.kinds, gemm_shapes(params))
    if breaker is not None:
        breaker(engine)
    vocab = int(cell.config["vocab_size"])
    mix = cell.mix
    warm_up(engine, mix, vocab)
    arrivals = traffic.schedule(mix, seed, seconds)

    def make_request(a):
        return Request(prompt=traffic.prompt_tokens(seed, a, vocab),
                       max_new=a.max_new, rid=a.rid)

    t_open = time.perf_counter() + float(mix["warm_in_s"])
    prof = Profiler(jax, CACHE / "trace" / cell.name, t_open + seconds) if trace else None
    d = drive(engine, arrivals, make_request, seconds=seconds,
              grace_s=float(mix.get("grace_s", 0.0)), t_open=t_open,
              backlog_depth=int(mix["arrivals"].get("depth", 0)),
              annotate=prof.annotate if prof else no_annotation,
              on_tick=prof.tick if prof else None)
    if prof:
        prof.stop()
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    e2e, attempted, failed = end_to_end(d, t_open - t_process, seconds)
    result = {"attempted": attempted, "failed": failed,
              "waiting_at_close": sum(
                  1 for r in d.records.values()
                  if r.arrival.segment == traffic.WINDOW and r.due_t is not None
                  and not (r.token_t and r.token_t[0] <= d.t_close))}
    if trace:
        from chipbench import readers

        ctx = readers.Context.build(d, engine, dims, prof, peaks_for(device.device_kind))
        result["metrics"] = per_layer(cell, ctx)
        dev.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
        result["breakdown"] = ctx.trace.breakdown()
        shutil.rmtree(prof.dir, ignore_errors=True)
    else:
        names = {m["name"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in e2e.items()
                             if k in names}
        # the window's other client-side numbers, recorded and not judged
        result["unjudged"] = {k: v for k, (v, _) in e2e.items() if k not in names}
    result["device"] = dev
    result["setup_compiles"] = counter.between(t_process, t_open)
    result["compiles_in_window"] = counter.between(d.t_open, d.t_close)
    window_steps = [s for s in d.steps if d.t_open <= s.t0 < d.t_close]
    walls = sorted(((s.t1 - s.t0) * 1e3, len(s.prefills)) for s in window_steps)
    result["longest_steps_ms"] = walls[-3:]
    result["step_ms"] = step_ms(window_steps)
    result["longest_gc_ms"] = pauses.longest_between(d.t_open, d.t_close)
    pauses.close()

    # -- correctness: the window's own output against the reference --------
    lim = cell.limits
    sampled = check.sample(d.records, seed, int(lim["sample_tokens"]),
                           int(lim["sample_requests"]))
    prefill_logits = [
        jax.device_get(engine._prefill(engine.params, jax.numpy.asarray(r.prompt)[None],
                                       engine._solo0)[0][0, -1])
        for r in sampled]
    del engine
    gc.collect()
    ref = check.Reference(cell.config["family"], check.ref_dims(cfg, cell.config),
                          int(cell.config["serving"]["max_len"]))
    values = check.readings(ref, params, sampled, prefill_logits, control=control)
    ok, compared = check.verdict(values, lim)
    result["correct"] = bool(ok)
    result["readings"] = values
    result["compared"] = {k: {"value": v, "limit": lim_} for k, v, lim_ in compared}
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def ordered(result: dict) -> dict:
    keys = ["correct", "attempted", "failed", "metrics", "device", "breakdown",
            "unjudged", "setup_compiles", "compiles_in_window", "step_ms", "longest_steps_ms", "longest_gc_ms",
            "waiting_at_close", "readings", "compared"]
    return {k: result[k] for k in keys if k in result}


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    import jax

    configure_jax(jax)
    try:
        require_chips(jax, cell.chips)
    except (NoChip, ValueError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = ordered(run_cell(cell, args.seed, args.seconds, bool(args.trace)))
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
