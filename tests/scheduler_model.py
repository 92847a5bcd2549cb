"""Reusable scheduler/engine invariant harness.

Three pieces, shared by the property-based suite (test_scheduler_model.py),
the deterministic tier-1 tests and the differential serving tests
(test_tenancy.py):

  * :class:`RefScheduler` — an independent pure-Python reference
    implementation of the scheduler contract (admission by (aged priority,
    deadline, seq), quantum-guarded preemption, budget clamp, zero-budget
    drain).  It shares no code with ``repro.serve.scheduler`` — only the
    contract — so bookkeeping bugs in either implementation surface as an
    event-stream divergence rather than agreeing with themselves.
  * :func:`drive` — a model-free simulation of ``ServeEngine.step()``'s
    scheduler interactions (tick -> preempt -> admit/resume -> one decode
    token per active slot), recording an event log and checking the
    per-step invariants as it goes.  Token *values* are irrelevant here
    (the model emits zeros); token *counting* is exact, which is what the
    conservation and quantum invariants need.
  * ``check_*`` invariant functions over a finished log + scheduler.

The contract pinned by the harness (DESIGN.md section Multi-tenant
scheduling):

  conservation      every submitted rid completes exactly once, with
                    exactly ``budget`` tokens, and no ticket is lost in a
                    queue or slot at drain
  slot accounting   at every step: occupied slots and the free list
                    partition ``range(slots)``; each occupied ticket knows
                    its slot
  intra-class FIFO  within one (tenant, class), *first* admissions happen
                    in submission order (same priority + same relative
                    deadline + monotone seq => the key preserves seq order)
  priority order    under the priority policy, nothing admits while a
                    strictly better-keyed waiter stays queued
  no starvation     with aging on, every trace drains within the driver's
                    step bound (effective priority falls without bound)
  ref equivalence   the real scheduler and :class:`RefScheduler` produce
                    identical (step, kind, rid, slot) event streams
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.serve.scheduler import (DECODE, DONE, PREEMPTED, PREFILL, Request,
                                   WAITING)

# event kinds recorded by drive()
SUBMIT, ADMIT, RESUME, PREEMPT, TOKEN, FINISH = (
    "submit", "admit", "resume", "preempt", "token", "finish")


@dataclasses.dataclass
class Spec:
    """One abstract request for trace generation: submit at engine step
    ``step`` (steps are relative to drive() start; same-step specs submit
    in list order, which defines seq order)."""

    step: int
    rid: int
    tenant: str = "default"
    rclass: str = "default"
    prompt_len: int = 4
    max_new: int = 4

    def request(self, vocab: int = 64) -> Request:
        rng = np.random.default_rng(self.rid)
        return Request(
            prompt=rng.integers(0, vocab, self.prompt_len).astype(np.int32),
            max_new=self.max_new, rid=self.rid,
            tenant=self.tenant, rclass=self.rclass)


def trace_from_specs(specs: list[Spec]) -> list[list[Spec]]:
    """Group specs into drive()'s per-step submission lists (index = step,
    padded with empty steps; within a step, list order = submission order)."""
    if not specs:
        return []
    horizon = max(s.step for s in specs) + 1
    steps: list[list[Spec]] = [[] for _ in range(horizon)]
    for s in specs:
        steps[s.step].append(s)
    return steps


# ---------------------------------------------------------------------------
# Reference scheduler: an independent implementation of the contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RefTicket:
    rid: int
    budget: int
    tenant: str
    rclass: str
    priority: int
    deadline: float
    seq: int
    submit_step: int
    queued_step: int
    tokens_at_admit: int = 0
    preemptions: int = 0
    state: str = WAITING
    slot: int = -1
    tokens: list[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.state == DONE

    @property
    def remaining(self) -> int:
        return max(self.budget - len(self.tokens), 0)


class RefScheduler:
    """Pure-Python reference scheduler: same contract as
    ``repro.serve.Scheduler``, implemented independently.  Free slots are
    recycled FIFO (freed order) — part of the contract, since the real
    scheduler hands the longest-free slot to the best-keyed waiter."""

    def __init__(self, slots: int, max_len: int, *, tenants=None,
                 classes=None, policy: str = "priority",
                 aging_steps: int = 8, preempt: bool = True,
                 min_quantum: int = 2):
        from repro.serve.tenancy import normalize_classes, normalize_tenants

        self.slots = slots
        self.max_len = max_len
        self.tenants = normalize_tenants(tenants)
        self.classes = normalize_classes(classes)
        self.policy = policy
        self.aging_steps = aging_steps
        self.preempt_enabled = bool(preempt) and policy == "priority"
        self.min_quantum = min_quantum
        self.clock = 0
        self.queue: list[RefTicket] = []
        self.free: list[int] = list(range(slots))
        self.tickets: dict[int, RefTicket] = {}
        self.by_slot: dict[int, RefTicket] = {}
        self.completed: list[int] = []
        self.preemptions = 0
        self._seq = 0

    def tick(self) -> None:
        self.clock += 1

    def submit(self, req: Request) -> int:
        tenant = self.tenants[req.tenant]
        rc = self.classes[req.rclass]
        n = len(req.prompt)
        t = RefTicket(
            rid=req.rid,
            budget=max(min(req.max_new, self.max_len - n + 1), 0),
            tenant=tenant.name, rclass=rc.name, priority=tenant.priority,
            deadline=(self.clock + rc.slo_steps
                      if rc.slo_steps is not None else math.inf),
            seq=self._seq, submit_step=self.clock, queued_step=self.clock)
        self._seq += 1
        self.tickets[req.rid] = t
        self.queue.append(t)
        return req.rid

    def eff_priority(self, t: RefTicket) -> int:
        if not self.aging_steps:
            return t.priority
        return t.priority - (self.clock - t.queued_step) // self.aging_steps

    def admission_key(self, t: RefTicket):
        if self.policy == "fifo":
            return (t.seq,)
        return (self.eff_priority(t), t.deadline, t.seq)

    def admit(self):
        out = []
        waiting = []
        for t in self.queue:
            if t.budget == 0:
                self.complete(t.rid)
                out.append((-1, t))
            else:
                waiting.append(t)
        self.queue = sorted(waiting, key=self.admission_key)
        while self.queue and self.free:
            t = self.queue.pop(0)
            slot = self.free.pop(0)
            t.slot = slot
            t.state = DECODE if t.tokens else PREFILL
            t.tokens_at_admit = len(t.tokens)
            self.by_slot[slot] = t
            out.append((slot, t))
        return out

    def plan_preemptions(self):
        if not (self.preempt_enabled and self.queue):
            return []
        victims, taken = [], set()
        spare = len(self.free)
        for w in sorted((t for t in self.queue if t.budget > 0),
                        key=self.admission_key):
            if spare > 0:
                spare -= 1
                continue
            cands = [
                t for t in self.by_slot.values()
                if t.state == DECODE and t.rid not in taken
                and t.priority > w.priority
                and len(t.tokens) - t.tokens_at_admit >= self.min_quantum
            ]
            if cands:
                v = max(cands, key=lambda t: (t.priority, t.deadline, t.seq))
                victims.append(v)
                taken.add(v.rid)
        return victims

    def preempt(self, rid: int) -> None:
        t = self.tickets[rid]
        del self.by_slot[t.slot]
        self.free.append(t.slot)
        t.slot = -1
        t.state = PREEMPTED
        t.queued_step = self.clock
        t.preemptions += 1
        self.preemptions += 1
        self.queue.append(t)

    def start_decode(self, rid: int) -> None:
        self.tickets[rid].state = DECODE

    def complete(self, rid: int) -> None:
        t = self.tickets[rid]
        if t.done:
            return
        t.state = DONE
        self.completed.append(rid)
        if t.slot >= 0:
            del self.by_slot[t.slot]
            self.free.append(t.slot)
            t.slot = -1

    def has_work(self) -> bool:
        return bool(self.queue or self.by_slot)


# ---------------------------------------------------------------------------
# Driver: the engine's scheduler interactions, without a model
# ---------------------------------------------------------------------------


def check_slot_accounting(sched) -> None:
    """Occupied + free must partition range(slots), with no slot counted
    twice and every occupied ticket knowing its slot."""
    occupied = set(sched.by_slot)
    free = list(sched.free)
    assert len(free) == len(set(free)), f"duplicate free slots: {free}"
    assert not (occupied & set(free)), "slot both free and occupied"
    assert occupied | set(free) == set(range(sched.slots)), (
        f"slots leaked: occupied={occupied} free={free}")
    for slot, t in sched.by_slot.items():
        assert t.slot == slot, f"ticket {t.rid} thinks slot {t.slot}, is {slot}"
        assert not t.done, f"done ticket {t.rid} still holds slot {slot}"


def check_priority_consistency(sched, admitted) -> None:
    """Under the priority policy, every ticket admitted this step must have
    a key <= every ticket still waiting (no queue-jumping past the sort)."""
    if sched.policy != "priority" or not admitted:
        return
    waiting_keys = [sched.admission_key(t) for t in sched.queue
                    if t.budget > 0]
    if not waiting_keys:
        return
    best_waiting = min(waiting_keys)
    for t in admitted:
        assert sched.admission_key(t) <= best_waiting, (
            f"admitted {t.rid} with key {sched.admission_key(t)} while a "
            f"better waiter (key {best_waiting}) stayed queued")


def drive(sched, trace: list[list[Spec]], vocab: int = 64,
          max_steps: int = 5000, per_step_checks: bool = True):
    """Run a submission trace to drain, mirroring ServeEngine.step()'s
    scheduler protocol exactly: per step, submit this step's requests, tick
    the clock, preempt planned victims, admit (fresh admissions emit their
    first token; resumed ones emit nothing), then emit one decode token for
    every active slot.  Returns the event log as a list of
    (step, kind, rid, slot) tuples.  Raises AssertionError if the trace
    fails to drain within ``max_steps`` — the no-starvation bound."""
    log: list[tuple[int, str, int, int]] = []
    pending = [list(step) for step in trace]
    steps = 0

    def emit(t) -> None:
        t.tokens.append(0)
        log.append((sched.clock, TOKEN, t.rid, t.slot))
        if len(t.tokens) >= t.budget:
            slot = t.slot
            sched.complete(t.rid)
            log.append((sched.clock, FINISH, t.rid, slot))
        else:
            sched.start_decode(t.rid)

    while pending or sched.has_work():
        steps += 1
        assert steps <= max_steps, (
            f"starvation: trace did not drain in {max_steps} steps "
            f"(waiting: {[t.rid for t in sched.queue]})")
        if pending:
            for spec in pending.pop(0):
                sched.submit(spec.request(vocab))
                log.append((sched.clock, SUBMIT, spec.rid, -1))
        sched.tick()
        for v in sched.plan_preemptions():
            slot = v.slot
            sched.preempt(v.rid)
            log.append((sched.clock, PREEMPT, v.rid, slot))
        admitted = []
        for slot, t in sched.admit():
            if slot < 0:
                log.append((sched.clock, FINISH, t.rid, -1))
                continue
            admitted.append(t)
            if t.tokens:
                log.append((sched.clock, RESUME, t.rid, slot))
            else:
                log.append((sched.clock, ADMIT, t.rid, slot))
                emit(t)
        if per_step_checks:
            check_priority_consistency(sched, admitted)
        for slot in sorted(sched.by_slot):
            emit(sched.by_slot[slot])
        if per_step_checks:
            check_slot_accounting(sched)
    return log


# ---------------------------------------------------------------------------
# Whole-log invariants
# ---------------------------------------------------------------------------


def check_conservation(sched, log) -> None:
    """Every submitted rid completes exactly once with exactly its budget
    of tokens; nothing is left queued or running."""
    submitted = [rid for _, kind, rid, _ in log if kind == SUBMIT]
    finished = [rid for _, kind, rid, _ in log if kind == FINISH]
    assert sorted(submitted) == sorted(finished), (
        f"lost/duplicated requests: submitted {sorted(submitted)} "
        f"finished {sorted(finished)}")
    assert len(set(finished)) == len(finished), "a rid finished twice"
    assert sorted(sched.completed) == sorted(submitted)
    assert not sched.queue and not sched.by_slot
    for rid in submitted:
        t = sched.tickets[rid]
        assert t.done and len(t.tokens) == t.budget, (
            f"rid {rid}: {len(t.tokens)} tokens vs budget {t.budget}")


def check_intra_class_fifo(sched, log) -> None:
    """Within one (tenant, class), first admissions happen in submission
    (seq) order — the deterministic-tie-break pin, generalized."""
    first_admit: dict[int, int] = {}
    for i, (_, kind, rid, _) in enumerate(log):
        if kind == ADMIT and rid not in first_admit:
            first_admit[rid] = i
    by_group: dict[tuple[str, str], list[int]] = {}
    for rid, pos in sorted(first_admit.items(), key=lambda kv: kv[1]):
        t = sched.tickets[rid]
        by_group.setdefault((t.tenant, t.rclass), []).append(t.seq)
    for group, seqs in by_group.items():
        assert seqs == sorted(seqs), (
            f"{group}: first admissions out of submission order: {seqs}")


def check_aging_bound(sched, log) -> None:
    """With aging on, no request waits unboundedly: every wait between
    joining the queue and (re-)admission is finite and, for the traces the
    generators produce, below an explicit bound derived from the aging
    rate (priority spread shrinks one rung per aging_steps ticks, and each
    admission frees a slot within max-budget tokens)."""
    if not sched.aging_steps or sched.policy != "priority":
        return
    spread = max(t.priority for t in sched.tickets.values()) - min(
        (t.priority for t in sched.tickets.values()), default=0)
    max_budget = max((t.budget for t in sched.tickets.values()), default=1)
    # crude but sufficient: once aged past the spread, a waiter out-ranks
    # every arrival; it then waits at most one full rotation of the slots
    bound = (spread + 2) * sched.aging_steps + (
        len(sched.tickets) + sched.slots) * max(max_budget, 1)
    queued_at: dict[int, int] = {}
    for step, kind, rid, _ in log:
        if kind == SUBMIT or kind == PREEMPT:
            queued_at[rid] = step
        elif kind in (ADMIT, RESUME) and rid in queued_at:
            wait = step - queued_at.pop(rid)
            assert wait <= bound, (
                f"rid {rid} waited {wait} steps (bound {bound})")


def check_quantum(sched, log) -> None:
    """Every preempted ticket emitted at least ``min_quantum`` tokens since
    its previous admission — preemption can never cancel progress."""
    tokens_since: dict[int, int] = {}
    for _, kind, rid, _ in log:
        if kind in (ADMIT, RESUME):
            tokens_since[rid] = 0
        elif kind == TOKEN:
            if rid in tokens_since:
                tokens_since[rid] += 1
        elif kind == PREEMPT:
            assert tokens_since.get(rid, 0) >= sched.min_quantum, (
                f"rid {rid} preempted after only {tokens_since.get(rid)} "
                f"tokens (min_quantum {sched.min_quantum})")


def check_equivalence(log_real, log_ref) -> None:
    """The real scheduler and the reference produce identical event
    streams (step, kind, rid, slot) — the differential core."""
    if log_real == log_ref:
        return
    for i, (a, b) in enumerate(zip(log_real, log_ref)):
        assert a == b, f"event {i} diverges: real {a} vs ref {b}"
    raise AssertionError(
        f"log lengths diverge: real {len(log_real)} vs ref {len(log_ref)}")


def check_all(sched, log) -> None:
    """The full single-scheduler invariant battery."""
    check_conservation(sched, log)
    check_intra_class_fifo(sched, log)
    check_aging_bound(sched, log)
    check_quantum(sched, log)


# ---------------------------------------------------------------------------
# Consumer mode: replay a real engine's trace through the same invariants
# ---------------------------------------------------------------------------

#: repro.obs event kinds -> drive() log kinds.  Everything else in the
#: trace (engine spans, mode_switch, cow_fork, ...) is engine detail the
#: scheduler contract does not speak about and is dropped by the mapping.
TRACE_KINDS = {
    "submit": SUBMIT,
    "admit": ADMIT,
    "resume": RESUME,
    "preempt": PREEMPT,
    "token": TOKEN,
    "done": FINISH,
}


def log_from_trace(events, *, skip_causes: tuple[str, ...] = ()) -> list:
    """Project a repro.obs event stream onto drive()'s
    ``(step, kind, rid, slot)`` log.  ``skip_causes`` drops lifecycle
    events whose cause is exempt from a specific invariant — e.g. the
    quantum check runs with ``skip_causes=("page_pressure",)`` because
    page-pressure eviction deliberately ignores ``min_quantum`` (memory
    pressure is a correctness condition, not a fairness policy)."""
    log = []
    for e in events:
        kind = TRACE_KINDS.get(e.kind)
        if kind is None or e.rid is None:
            continue
        if e.cause is not None and e.cause in skip_causes:
            continue
        log.append((e.step, kind, e.rid,
                    -1 if e.slot is None else int(e.slot)))
    return log


def check_replay(engine) -> list:
    """Replay a drained traced engine's event stream through the scheduler
    invariants — every trace becomes a checkable artifact.

    Checks always: lossless ring (no dropped events), request-span
    lifecycle order (repro.obs.span_violations), conservation, and the
    quantum bound over priority preemptions (page-pressure evictions are
    cause-exempt).  The FIFO and aging checks only run on traces without
    admission refusals/deferrals: under memory pressure the layout legally
    reorders admissions (slot order is a preference, not a barrier) and
    re-ranks waits, which those two checks would misread as violations.
    Returns the projected log."""
    from repro.obs import span_violations

    tracer = engine.tracer
    assert tracer.enabled, "check_replay needs a traced engine"
    assert tracer.dropped == 0, (
        f"{tracer.dropped} events dropped — raise TraceConfig.capacity to "
        f"make the trace replayable")
    events = list(tracer.events)
    bad = span_violations(events)
    assert not bad, f"lifecycle violations: {bad}"
    sched = engine.scheduler
    log = log_from_trace(events)
    check_conservation(sched, log)
    pressured = any(e.kind in ("admit_defer", "admit_refuse") for e in events)
    if not pressured:
        check_intra_class_fifo(sched, log)
        check_aging_bound(sched, log)
    check_quantum(sched, log_from_trace(events,
                                        skip_causes=("page_pressure",)))
    return log
