"""Reduction of one profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``: their ``XLA Ops`` line holds every operation the device
ran, their ``XLA Modules`` line every compiled program run.  Host planes
hold the benchmark's own spans (``chipbench.*`` ``TraceAnnotation``s), on
the same clock.  The traced window runs from the ``chipbench.trace_open``
marker to ``chipbench.trace_close``.

- busy: the union of the intervals in which an operation ran on the device,
  clipped to the window, averaged over the devices that ran any;
- idle gaps: the holes in that union, each named by the innermost host span
  open at its middle (``chipbench.step`` is host work inside an engine step,
  ``chipbench.wait`` the client waiting for a request).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "chipbench."
#: operations that only contain others (their bodies are traced too)
CONTAINERS = ("%while", "%conditional", "%call")


def op_name(text: str) -> str:
    """An XLA op's trace name is its whole HLO instruction: keep its name."""
    return text.split(" = ", 1)[0]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int  # ns
    dur: int  # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


def merge(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[int, int]], t0: int, t1: int) -> int:
    """ns of [t0, t1] that a merged interval list covers."""
    i = max(bisect.bisect_right(merged, (t0, t0)) - 1, 0)
    total = 0
    for s, e in merged[i:]:
        if s >= t1:
            break
        total += max(0, min(e, t1) - max(s, t0))
    return total


@dataclasses.dataclass
class TraceData:
    window: tuple[int, int]
    ops: dict  # device name -> [Span] of XLA ops
    modules: dict  # device name -> [Span] of compiled programs run
    host: list  # [Span] of the benchmark's host spans
    _busy: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def from_profile(cls, pd) -> "TraceData":
        ops, modules, host = {}, {}, []
        for plane in pd.planes:
            name = plane.name
            for line in plane.lines:
                if name.startswith("/device:TPU:") and line.name == OPS_LINE:
                    ops.setdefault(name, []).extend(
                        Span(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
                elif name.startswith("/device:TPU:") and line.name == MODULES_LINE:
                    modules.setdefault(name, []).extend(
                        Span(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events)
                elif name.startswith("/host:"):
                    host.extend(Span(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name.startswith(HOST_PREFIX))
        opened = [s.start for s in host if s.name == "chipbench.trace_open"]
        closed = [s.start for s in host if s.name == "chipbench.trace_close"]
        if not opened or not closed:
            raise ValueError("the trace holds no chipbench.trace_open/_close markers")
        ops = {k: v for k, v in ops.items() if v}
        if not ops:
            raise ValueError("the trace holds no device operations")
        host.sort(key=lambda s: s.start)
        return cls((min(opened), max(closed)), ops, modules, host)

    @classmethod
    def from_dir(cls, directory: Path) -> "TraceData":
        import jax

        files = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no xplane.pb under {directory}")
        return cls.from_profile(jax.profiler.ProfileData.from_file(files[-1]))

    # -- busy and idle ---------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: str) -> list[tuple[int, int]]:
        if device not in self._busy:
            t0, t1 = self.window
            self._busy[device] = merge((max(s.start, t0), min(s.end, t1))
                                       for s in self.ops[device] if s.end > t0 and s.start < t1)
        return self._busy[device]

    @property
    def busy_s(self) -> float:
        devs = list(self.ops)
        return sum(sum(e - s for s, e in self.busy(d)) for d in devs) * 1e-9 / len(devs)

    def busy_between(self, t0: int, t1: int) -> float:
        """Device-busy seconds inside [t0, t1], averaged over devices."""
        devs = list(self.ops)
        return sum(covered(self.busy(d), t0, t1) for d in devs) * 1e-9 / len(devs)

    def host_spans(self, name: str) -> list[Span]:
        t0, t1 = self.window
        return [s for s in self.host if s.name == name and s.start >= t0 and s.end <= t1]

    def label_at(self, t: int) -> str:
        inner = [s for s in self.host if s.start <= t < s.end
                 and s.name not in ("chipbench.trace_open", "chipbench.trace_close")]
        return min(inner, key=lambda s: s.dur).name if inner else "none"

    def idle_gaps(self) -> list[tuple[int, int]]:
        """The holes (start, end) in the first device's busy union."""
        dev = next(iter(self.ops))
        t0, t1 = self.window
        edges = [t0] + [x for iv in self.busy(dev) for x in iv] + [t1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    # -- programs and operations -------------------------------------------------

    def device_spans(self, kind: str, match) -> list[Span]:
        """Spans of ``kind`` ("ops" or "modules") inside the window whose
        name ``match(name)`` accepts, over every device."""
        t0, t1 = self.window
        src = self.ops if kind == "ops" else self.modules
        return [s for spans in src.values() for s in spans
                if match(s.name) and s.start >= t0 and s.end <= t1]

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        """The operations that took most device time, by HLO name, leaving
        out loops and calls whose bodies are counted op by op."""
        t0, t1 = self.window
        total: dict[str, float] = {}
        for spans in self.ops.values():
            for s in spans:
                name = op_name(s.name)
                if s.end > t0 and s.start < t1 and not name.startswith(CONTAINERS):
                    total[name] = total.get(name, 0.0) + s.dur * 1e-9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        """The ten operations that took most time, and the ten longest idle
        gaps, each named by what the host was doing."""
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[k, v] for k, v in self.top_ops(10)],
                "idle_gaps": [[self.label_at((a + b) // 2), (b - a) * 1e-9] for a, b in gaps]}
