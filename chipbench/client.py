"""The open-loop client: submits each request when it is due, steps the
engine in between, and stamps every event on the host clock.

Each request is timed from when it was due, not from when the loop got round
to submitting it, so a stall of the loop counts against the requests it
delays; how late each submission ran is kept as ``gen_lag``.  Tokens are
stamped when ``ServeEngine.step`` hands them to the client.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from chipbench.traffic import WINDOW, Arrival


@dataclasses.dataclass
class Record:
    arrival: Arrival
    due_t: float | None = None  # host clock when due (None: backlog)
    prompt: object = None  # token ids sent
    submit_t: float | None = None
    token_t: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.arrival.max_new


@dataclasses.dataclass
class StepRecord:
    """One ``ServeEngine.step`` call: its host span and, per row that got a
    decode token, the context that token attended over."""

    t0: float
    t1: float
    prefills: list  # prompt lengths admitted in this step
    contexts: list  # cache length of each decode row, its new token included


@dataclasses.dataclass
class Drive:
    records: dict  # rid -> Record
    steps: list  # StepRecord
    t_open: float  # host clock of window open
    t_close: float
    t_end: float  # loop stop

    def ttft(self) -> tuple[list, int]:
        """Seconds from due to first token of every request due in the
        window, and how many got none: such a request failed, and counts
        at the time it had waited when the loop stopped."""
        waits, failed = [], 0
        for r in self.records.values():
            if r.arrival.segment != WINDOW or r.due_t is None:
                continue
            if r.token_t:
                waits.append(r.token_t[0] - r.due_t)
            else:
                failed += 1
                waits.append(self.t_end - r.due_t)
        return waits, failed


def no_annotation(_name):
    return contextlib.nullcontext()


def drive(engine, arrivals: list[Arrival], make_request, *, seconds: float,
          grace_s: float, t_open: float, backlog_depth: int = 0,
          annotate=no_annotation, on_tick=None, clock=time.perf_counter) -> Drive:
    """Run the open loop until the window has closed and every request due
    in it has its first token (or ``grace_s`` has passed since the close).

    ``t_open`` is the host-clock time the window opens; arrivals carry due
    times relative to it (warm-in arrivals are negative).  A backlog
    (``backlog_depth`` > 0) keeps that many requests waiting beyond the
    running ones and stops at the close.  ``annotate(name)`` wraps each host
    phase (step, submit, wait) for the profiler; ``on_tick(now)`` runs once
    per loop turn (the traced run starts the profiler from it).
    """
    records: dict[int, Record] = {}
    steps: list[StepRecord] = []
    t_close = t_open + seconds
    pending = list(arrivals)
    nxt = 0
    waiting = 0  # submitted, no first token yet
    window_open_rids: set[int] = set()

    def submit(a: Arrival, due_t):
        nonlocal waiting
        rec = Record(a, due_t=due_t)
        req = make_request(a)
        rec.prompt = req.prompt
        with annotate("chipbench.submit"):
            rec.submit_t = clock()
            engine.submit(req)
        records[a.rid] = rec
        waiting += 1
        if a.segment == WINDOW and a.due is not None:
            window_open_rids.add(a.rid)

    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        if backlog_depth:
            while nxt < len(pending) and waiting < backlog_depth:
                submit(pending[nxt], None)
                nxt += 1
            if now >= t_close:
                break
        else:
            while nxt < len(pending) and t_open + pending[nxt].due <= now:
                submit(pending[nxt], t_open + pending[nxt].due)
                nxt += 1
            if now >= t_close and (not window_open_rids or now >= t_close + grace_s):
                break
        if engine.scheduler.has_work():
            with annotate("chipbench.step"):
                t0 = clock()
                events = engine.step()
                t1 = clock()
            prefills, contexts = [], []
            for rid, tok in events:
                rec = records[rid]
                if not rec.tokens:
                    waiting -= 1
                    window_open_rids.discard(rid)
                    prefills.append(rec.arrival.prompt_len)
                else:
                    contexts.append(rec.arrival.prompt_len + len(rec.tokens))
                rec.tokens.append(tok)
                rec.token_t.append(t1)
            steps.append(StepRecord(t0, t1, prefills, contexts))
        elif nxt < len(pending) and not backlog_depth:
            with annotate("chipbench.wait"):
                time.sleep(max(min(t_open + pending[nxt].due - clock(), 0.05), 0.0))
        elif backlog_depth:
            raise RuntimeError("backlog exhausted: the mix holds too few requests")
        else:
            with annotate("chipbench.wait"):
                time.sleep(0.001)
    return Drive(records, steps, t_open, t_close, clock())
