"""What the program's own measurement adds to a profiler trace: the scope
each device operation ran under, and the engine's ``serve.*`` host spans.

Scopes.  The program names its compiled regions with ``jax.named_scope``:
``gemm/<op>`` on every policy-routed GEMM, ``attention`` on the attention
core, ``ssm`` on the Mamba2 mixer, ``select`` on the masked step's row
merge.  XLA keeps the name stack in each instruction's ``op_name``, which
the TPU profiler writes as the ``tf_op`` stat of the operation's event
metadata, beside its ``program_id`` (read by ``chipbench.xspace``).  An
operation is charged to the outermost of those scopes its ``tf_op`` names,
so the score and value contractions inside ``attention`` count as
attention, and ``gemm`` means the weight GEMMs and the head.  Operation
names repeat across programs: an ``XLA Ops`` event is matched to the
program whose ``XLA Modules`` span holds it (``jit_<fn>(<program_id>)``)
and looked up by (program id, operation name).

Spans.  The engine's ``repro.obs`` spans run under
``TraceAnnotation("serve.<kind>")`` and land on the host plane, on the
device trace's clock.

A program without either (an older program under this harness) reads
nothing here: the readers built on this module then return None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
from pathlib import Path

from chipbench import xspace
from chipbench.readers import DECODE_PROGRAM
from chipbench.trace import CONTAINERS, Span, TraceData, op_name

#: where ``chipbench/run.py``'s profiler writes a traced run's trace
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".chipbench_cache" / "trace"
SERVE_PREFIX = "serve."
#: the program's scopes; an operation is charged to the outermost it names
SCOPES = ("attention", "ssm", "select", "gemm")
MARKERS = ("chipbench.trace_open", "chipbench.trace_close")


def charge(tf_op: str | None) -> str | None:
    """The outermost scope an ``op_name`` path names, or None."""
    for part in (tf_op or "").split("/"):
        if part in SCOPES:
            return part
    return None


def program_id(module_name: str) -> int | None:
    """``jit__masked_step(5539715528726895929)`` -> 5539715528726895929."""
    digits = module_name.partition("(")[2].split(")", 1)[0]
    return int(digits) if digits.isdigit() else None


@dataclasses.dataclass
class ProgramTrace:
    tf_op: dict  # (program id, op name) -> its op_name path
    serve: list  # [Span] of the engine's serve.* host spans, by start
    markers: tuple  # (trace_open start, trace_close start), ns

    @classmethod
    def from_file(cls, path) -> "ProgramTrace":
        import jax

        pd = jax.profiler.ProfileData.from_file(str(path))
        serve, opened, closed = [], [], []
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SERVE_PREFIX):
                        serve.append(Span(e.name, int(e.start_ns), int(e.duration_ns)))
                    elif e.name == MARKERS[0]:
                        opened.append(int(e.start_ns))
                    elif e.name == MARKERS[1]:
                        closed.append(int(e.start_ns))
        serve.sort(key=lambda s: s.start)
        tf_op = {}
        for table in xspace.event_metadata(path, "/device:TPU:").values():
            for name, stats in table.values():
                pid = stats.get("program_id")
                if pid is not None:
                    tf_op[(int(pid), op_name(name))] = stats.get("tf_op")
        markers = (min(opened), max(closed)) if opened and closed else None
        return cls(tf_op, serve, markers)

    # -- scopes ------------------------------------------------------------------

    def decode_ops(self, td: TraceData):
        """(module span, [op spans]) of each decode program run wholly
        inside the window, leaving out loops and calls whose bodies are
        traced op by op."""
        t0, t1 = td.window
        for dev, modules in td.modules.items():
            ops = sorted(td.ops.get(dev, ()), key=lambda s: s.start)
            starts = [s.start for s in ops]
            for m in modules:
                if not (DECODE_PROGRAM(m.name) and m.start >= t0 and m.end <= t1):
                    continue
                i = bisect.bisect_left(starts, m.start)
                inside = []
                while i < len(ops) and ops[i].start < m.end:
                    if ops[i].end <= m.end and not ops[i].name.startswith(CONTAINERS):
                        inside.append(ops[i])
                    i += 1
                yield m, inside

    def decode_scope_ms(self, td: TraceData) -> dict:
        """Device ms per decode step charged to each scope (None: the
        operations under no scope), over the decode programs of the
        window; a scope no operation ran under is absent."""
        total: dict = {}
        steps = 0
        for m, ops in self.decode_ops(td):
            steps += 1
            pid = program_id(m.name)
            for s in ops:
                scope = charge(self.tf_op.get((pid, s.name)))
                total[scope] = total.get(scope, 0) + s.dur
        return {k: v / steps * 1e-6 for k, v in total.items()} if steps else {}

    # -- spans ---------------------------------------------------------------------

    def spans(self, kind: str, td: TraceData) -> list[Span]:
        """The ``serve.<kind>`` spans wholly inside the traced window."""
        t0, t1 = td.window
        name = SERVE_PREFIX + kind
        return [s for s in self.serve if s.name == name and s.start >= t0 and s.end <= t1]

    def ms_per_decode_step(self, kinds, td: TraceData) -> float | None:
        """Host ms of the spans of ``kinds`` per decode step (``decode`` or
        ``spec_round`` span) in the window; None without such spans."""
        steps = len(self.spans("decode", td)) + len(self.spans("spec_round", td))
        parts = [s for k in kinds for s in self.spans(k, td)]
        if not steps or not parts:
            return None
        return sum(s.dur for s in parts) / steps * 1e-6

    def label_at(self, td: TraceData, t: int) -> str:
        """The innermost benchmark or engine span open at ``t``."""
        inner = [s for s in td.host + self.serve
                 if s.start <= t < s.end and s.name not in MARKERS]
        return min(inner, key=lambda s: s.dur).name if inner else "none"

    def idle_gaps(self, td: TraceData, n: int = 10) -> list:
        """``TraceData.breakdown``'s longest idle gaps, each named by the
        innermost span of either kind open at its middle."""
        gaps = sorted(td.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.label_at(td, (a + b) // 2), (b - a) * 1e-9] for a, b in gaps]


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float) -> ProgramTrace:
    return ProgramTrace.from_file(path)


def of(ctx) -> ProgramTrace:
    """The program's side of the trace a run's readers reduce: the newest
    trace file under ``TRACE_ROOT`` whose window markers are ``ctx.trace``'s
    (read once, however many readers ask)."""
    files = sorted(glob.glob(str(TRACE_ROOT / "**" / "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in files:
        pt = _read(path, os.path.getmtime(path))
        if pt.markers == ctx.trace.window:
            return pt
    raise FileNotFoundError(f"no trace under {TRACE_ROOT} has the window {ctx.trace.window}")
