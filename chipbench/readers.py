"""What a per-layer metric reader gets: one run's host records, the
engine's tracer events and plan, the reduced profiler trace, the work
counts and the chip's peaks.  Readers live in ``metrics/<name>.py``, each
with ``read(ctx, suffix) -> float | None``; None leaves the metric out."""
from __future__ import annotations

import dataclasses

from chipbench.trace import TraceData
from chipbench.workcount import Dims


@dataclasses.dataclass
class Context:
    drive: object  # chipbench.client.Drive
    admits: dict  # rid -> host time of the tracer's ``admit`` event
    dims: Dims
    peaks: object
    slots: int
    decode_mode: dict  # op class -> mode name of the decode program
    decode_impl: dict  # op class -> impl of the decode program
    trace: TraceData
    trace_offset_ns: float  # trace ns = host perf_counter s * 1e9 + offset

    @classmethod
    def build(cls, d, engine, dims: Dims, prof, peaks) -> "Context":
        admits = {e.rid: e.ts for e in engine.tracer.events if e.kind == "admit"}
        ops = dims.gemms()
        policy = engine.model_decode.cfg.policy
        plans = engine.phase_plans.get("decode", {})
        mode = {op: policy.mode_for(op).name for op in ops}
        impl = {op: (plans[op].impl if op in plans else policy.impl) for op in ops}
        trace = TraceData.from_dir(prof.dir)
        offset = trace.window[0] - prof.t0 * 1e9
        return cls(d, admits, dims, peaks, engine.slots, mode, impl, trace, offset)

    def to_trace(self, t: float) -> int:
        return int(t * 1e9 + self.trace_offset_ns)

    def traced_steps(self) -> list:
        """The engine steps that ran wholly inside the traced window, each
        with its span in trace time."""
        t0, t1 = self.trace.window
        out = []
        for s in self.drive.steps:
            a, b = self.to_trace(s.t0), self.to_trace(s.t1)
            if a >= t0 and b <= t1:
                out.append((s, a, b))
        return out


def module_match(fragment: str):
    """Matches a compiled program's trace name, ``jit_<fn>`` with any
    suffix the runtime appends."""
    return lambda name: name.split("(")[0].startswith(f"jit_{fragment}")


#: the engine's compiled programs, by the names of the functions jitted
PREFILL_PROGRAM = module_match("decode_step")
DECODE_PROGRAM = module_match("_masked_step")


def spans_within(spans, a: int, b: int) -> list:
    return [s for s in spans if s.start >= a and s.end <= b]
