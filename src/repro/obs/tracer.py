"""Low-overhead structured tracing for the serving stack.

One :class:`Tracer` per engine records typed :class:`Event` records into a
bounded ring buffer (``collections.deque(maxlen=capacity)``) on a monotonic
clock, plus a flat counter/gauge registry.  Everything is host-side Python:
no event ever becomes a jit argument or a device value, so a traced engine
compiles and dispatches *exactly* what an untraced one does — the
zero-jit-visible-cost contract tests/test_obs.py pins (identical tokens and
identical compile counts with tracing on vs off).

When tracing is off the engine holds :data:`NULL_TRACER`, whose hooks are
no-ops and whose ``enabled`` flag lets hot paths skip even the argument
construction (``if tracer.enabled: tracer.emit(...)``).

Event taxonomy (DESIGN.md section Observability):

  lifecycle   submit, admit, resume, preempt, token, done — the per-request
              span skeleton; step stamps follow the scheduler clock and the
              stream replays through the tests/scheduler_model.py invariant
              harness (consumer mode);
  spans       step, schedule, admit > prefill > scatter, decode >
              (decode.inputs, decode.dispatch, decode.sync, emit),
              spec_round > (the same four), adapt — the engine's phases
              as nested spans (``Tracer.span``): one event at the span's
              start carrying ``dur_ms`` and its ``parent`` kind, and a
              ``serve.<kind>`` annotation on the profiler's clock;
  engine      recompile — jit cache growth (repro.obs.profile);
  decisions   adapt_decision, mode_switch, draft_shift, tier_tick,
              preempt_plan, admit_defer, admit_refuse, page_evict, cow_fork,
              prefix_share — every reconfiguration with its *cause*.

Exporters (Chrome trace, Prometheus text, the precision timeline) read the
ring after the run; see repro.obs.export / repro.obs.timeline.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Tracing knobs carried on ``ServeConfig.trace``.

    ``capacity``: ring-buffer size in events — old events drop first (the
    replay harness requires a lossless ring, so size it to the run).
    ``out``: Chrome-trace path ``launch/serve --trace-out`` writes at exit.
    """

    capacity: int = 1 << 16
    out: str | None = None

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")


@dataclasses.dataclass
class Event:
    """One typed trace record.  ``ts`` is the tracer's monotonic clock
    (seconds, ``time.perf_counter``); ``step`` is the scheduler clock the
    event belongs to; ``cause`` names *why* for decision events.  An event
    whose data carries ``dur_ms`` is a span: ``ts`` is its start."""

    ts: float
    step: int
    kind: str
    rid: int | None = None
    slot: int | None = None
    cause: str | None = None
    data: dict | None = None


class Tracer:
    """Ring-buffered event recorder + counter/gauge registry."""

    enabled = True

    def __init__(self, config: TraceConfig | None = None,
                 clock=time.perf_counter):
        self.config = config or TraceConfig()
        self.clock = clock
        self.events: collections.deque[Event] = collections.deque(
            maxlen=self.config.capacity)
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        #: current scheduler step — the engine advances this once per
        #: ``step()`` so emit sites need not thread the clock through
        self.step = 0
        self.emitted = 0
        #: events of the spans open now, innermost last
        self._open: list[Event] = []

    @property
    def dropped(self) -> int:
        """Events the ring has discarded (0 = lossless, replayable)."""
        return self.emitted - len(self.events)

    def emit(self, kind: str, *, rid: int | None = None,
             slot: int | None = None, cause: str | None = None,
             step: int | None = None, **data) -> None:
        self.emitted += 1
        self.events.append(Event(
            ts=self.clock(), step=self.step if step is None else int(step),
            kind=kind, rid=rid, slot=slot, cause=cause, data=data or None))

    def span(self, kind: str, *, rid: int | None = None,
             slot: int | None = None, **data) -> "_Span":
        """A context manager timing one engine phase.  On entry it appends
        one event of ``kind`` (``ts`` the start, ``data["parent"]`` the kind
        of the enclosing span or None) and yields it, so a site can add
        counts it learns inside; on exit it sets ``data["dur_ms"]``.  The
        block also runs under ``jax.profiler.TraceAnnotation("serve.<kind>")``,
        so a profiler session holds the span on the device trace's clock."""
        return _Span(self, kind, rid, slot, data)

    def inc(self, name: str, value: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def set_gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    # -- exporters (repro.obs.export / repro.obs.timeline) -------------------

    def chrome(self) -> dict:
        """The trace as a Chrome-trace/Perfetto ``traceEvents`` document."""
        from repro.obs.export import to_chrome

        return to_chrome(list(self.events), self.counters, self.gauges)

    def export_chrome(self, path: str) -> dict:
        """Write the Chrome-trace JSON to ``path``; returns the document."""
        from repro.obs.export import write_chrome

        return write_chrome(path, list(self.events), self.counters,
                            self.gauges)

    def prometheus(self) -> str:
        """Prometheus text exposition of the counter/gauge registry."""
        from repro.obs.export import to_prometheus

        return to_prometheus(self.counters, self.gauges)

    def precision_timeline(self) -> list[dict]:
        """Aligned per-step precision view (repro.obs.timeline)."""
        from repro.obs.timeline import precision_timeline

        return precision_timeline(list(self.events))

    def format_timeline(self) -> str:
        from repro.obs.timeline import format_timeline

        return format_timeline(self.precision_timeline())

    def describe(self) -> str:
        kinds: dict[str, int] = {}
        for e in self.events:
            kinds[e.kind] = kinds.get(e.kind, 0) + 1
        body = " ".join(f"{k}:{n}" for k, n in sorted(kinds.items()))
        return (f"{self.emitted} events ({self.dropped} dropped, "
                f"capacity {self.config.capacity}) | {body or '-'}")


class _Span:
    """One span of a :class:`Tracer` (see :meth:`Tracer.span`)."""

    __slots__ = ("tracer", "kind", "rid", "slot", "data", "event", "annotation")

    def __init__(self, tracer: Tracer, kind: str, rid, slot, data: dict):
        self.tracer, self.kind, self.rid, self.slot = tracer, kind, rid, slot
        self.data = data

    def __enter__(self) -> Event:
        tr = self.tracer
        args = {k: v for k, v in (("rid", self.rid), ("slot", self.slot))
                if v is not None}
        self.annotation = jax.profiler.TraceAnnotation(
            f"serve.{self.kind}", **args, **self.data)
        self.annotation.__enter__()
        self.data["parent"] = tr._open[-1].kind if tr._open else None
        ev = Event(ts=tr.clock(), step=tr.step, kind=self.kind, rid=self.rid,
                   slot=self.slot, data=self.data)
        tr.emitted += 1
        tr.events.append(ev)
        tr._open.append(ev)
        self.event = ev
        return ev

    def __exit__(self, *exc) -> None:
        tr, ev = self.tracer, self.event
        ev.data["dur_ms"] = (tr.clock() - ev.ts) * 1e3
        tr._open.pop()
        self.annotation.__exit__(*exc)


class _NullSpan:
    """The span of :class:`NullTracer`: enters and exits doing nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The tracing-off sentinel: every hook is a no-op and ``enabled`` is
    False, so guarded emit sites cost one attribute read.  Exporters refuse
    loudly rather than returning an empty trace that looks like a run."""

    enabled = False
    events: tuple = ()
    counters: dict[str, float] = {}
    gauges: dict[str, float] = {}
    step = 0
    emitted = 0
    dropped = 0

    def emit(self, kind: str, **kw) -> None:
        pass

    def span(self, kind: str, *, rid: int | None = None,
             slot: int | None = None, **data) -> _NullSpan:
        """The one shared no-op span: it yields None, so a site that adds
        counts to the span's event guards them with ``if tracer.enabled``."""
        return _NULL_SPAN

    def inc(self, name: str, value: float = 1.0) -> None:
        pass

    def set_gauge(self, name: str, value: float) -> None:
        pass

    def _off(self):
        raise RuntimeError(
            "tracing is off: construct the engine with "
            "ServeConfig(trace=TraceConfig(...)) to record events")

    def chrome(self) -> dict:
        self._off()

    def export_chrome(self, path: str) -> dict:
        self._off()

    def prometheus(self) -> str:
        self._off()

    def precision_timeline(self) -> list[dict]:
        self._off()

    def format_timeline(self) -> str:
        self._off()

    def describe(self) -> str:
        return "tracing off"


#: shared no-op tracer: the default for every instrumented component
NULL_TRACER = NullTracer()
