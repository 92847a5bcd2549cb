"""Masked step engine: true continuous batching over a fixed slot array.

``ServeEngine`` runs the streaming API

    rid = engine.submit(Request(prompt, max_new, rid))   # any time
    events = engine.step()                               # [(rid, token), ...]
    outputs = engine.drain()                             # run to completion

over one compiled decode step.  The slot array is fixed at ``batch_slots``;
per-slot state (KV positions, lengths, decode position) lives in the
per-slot ``DecodeState`` layout (``models.lm.init_decode_state(per_slot=
True)``), so slots at *different* sequence depths — and empty slots — share
the same ``jax.jit`` step: finished rows are masked out (their state is
frozen by a per-row select), new requests join mid-flight by scattering a
solo-prefilled row into their slot.  This is the ReservationStations fan-in/
fan-out shape from the ieee754fpu pipeline (SNIPPETS.md section 1) applied
to decode: the step function is the shared pipeline, the scheduler is the
fan-in.

Prefill runs per-request at the prompt's true length (batch=1) — no padded
positions ever enter the KV cache — then the resulting row is written into
the request's slot (one ``dynamic_update_slice`` per state leaf).

Precision phases: with ``accuracy=...`` the engine plans *two* policies via
``repro.plan.plan_model_policy`` — one for prefill GEMMs (prompt_tokens x d)
and one for decode GEMMs (slots x d) — and compiles each phase under its own
policy.  That is the paper's run-time mode switch exercised inside a single
workload: the mode bits flip between phases while the params and the KV
cache stream through unchanged (DESIGN.md section Serving).

With ``speculate=SpecConfig(...)`` the decode phase runs self-speculative
rounds (repro.spec): the cheap end of the mode ladder drafts ``k`` tokens
per slot, the exact baseline step verifies all ``k+1`` positions, and a
compiled rollback-select restores each slot to its accepted prefix —
outputs stay bit-identical to this engine's plain greedy decode while
expensive-mode steps per emitted token drop below 1 (DESIGN.md section
Speculative decoding).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rmpm import one_limb_stats
from repro.models.lm import LanguageModel
from repro.obs import NULL_TRACER, PhaseProfiler, TraceConfig, Tracer
from repro.serve.config import ServeConfig
from repro.serve.metrics import ServeMetrics
from repro.serve.paged import make_layout
from repro.serve.scheduler import Request, Scheduler, Ticket
from repro.serve.tenancy import RequestClass, Tenant

__all__ = ["Request", "RequestClass", "ServeConfig", "ServeEngine", "Tenant"]


def _plan_phase(model: LanguageModel, tokens: int, accuracy: float,
                backend: str | None, tune_table=None):
    """Plan one phase's policy (prefill or decode) for its GEMM M-dim."""
    from repro.core.precision import DF32_MODES
    from repro.plan import plan_model_policy

    base = model.cfg.policy
    policy, plans = plan_model_policy(
        model.cfg, tokens=tokens, accuracy=accuracy,
        backend=backend, rounding=base.rounding, tune_table=tune_table,
    )
    if (
        base.impl == "native"
        and policy.impl == "xla"
        and (backend or jax.default_backend()) != "tpu"
        and not any(p.mode in DF32_MODES for p in plans.values())
    ):
        # keep the fast CPU execution path when the base policy chose it and
        # the planner has no better limb impl to offer — but never on the
        # TPU, where 'native' (HIGHEST f32) costs more passes than the
        # planned modes, and never for DF32 modes, where 'xla' IS the limb
        # engine and 'native' (plain f32) would break the accuracy budget
        policy = policy.with_impl("native")
    return LanguageModel(model.cfg.with_policy(policy)), plans


def row_select(ax: int, new, old, active):
    """Per-row select along a state leaf's batch axis ``ax``: rows where
    ``active`` is False keep ``old`` exactly — the masking invariant shared
    by the masked steps and the speculative rollback (repro.spec).  Leaves
    with no batch axis (``repro.serve.paged.SHARED`` — the paged pools) keep
    ``new``: per-row isolation for them is the page table's job (inactive
    rows' cleared tables redirect their writes to the scratch page)."""
    if ax < 0:
        return new
    shape = [1] * new.ndim
    shape[ax] = active.shape[0]
    return jnp.where(active.reshape(shape), new, old)


class ServeEngine:
    #: decode-phase accuracy tightening: autoregressive decode feeds its
    #: rounding errors back (every generated token conditions the rest),
    #: while prefill errors are one-shot, so the decode phase plans at
    #: ``accuracy * DECODE_ACCURACY_SCALE`` — a budget near a mode boundary
    #: therefore flips the RMPM mode bits between the phases of one workload.
    DECODE_ACCURACY_SCALE = 2.0**-4

    def __init__(self, model: LanguageModel, params,
                 batch_slots: int | None = None, max_len: int | None = None,
                 greedy: bool = True, accuracy: float | None = None,
                 plan_backend: str | None = None,
                 prefill_tokens: int | None = None,
                 decode_accuracy_scale: float | None = None,
                 tune_table=None,
                 slo=None, adapt_every: int = 4, adapt: bool = True,
                 controller=None, speculate=None,
                 tenants=None, classes=None,
                 scheduler_policy: str = "priority",
                 preempt: bool = True, aging_steps: int = 8,
                 min_quantum: int = 2, cache=None,
                 config: ServeConfig | None = None):
        """``config=ServeConfig(...)`` is the documented construction path —
        one frozen value grouping the scheduling / adaptation / speculation /
        cache surfaces (repro.serve.config).  The flat kwargs remain as a
        deprecation shim: they are regrouped through
        ``ServeConfig.from_kwargs`` and must not be mixed with ``config=``.

        ``slo`` (repro.adapt.SLO) turns on closed-loop runtime precision
        adaptation of the decode phase: the planner's decode modes become a
        mutable ModeTable whose int32 scalars feed one compiled masked step
        (``lax.switch`` branch select — zero recompiles across mode changes);
        every ``adapt_every`` decode steps a probe runs the same executable
        at the max-mode reference and one mode down, and the hysteresis
        controller shifts the table against the SLO.  ``adapt=False`` keeps
        the probes and mode timeline (monitoring) but never shifts — the
        instrumented static baseline the adapt benchmark compares against.

        ``speculate`` (repro.spec.SpecConfig) turns on self-speculative
        decoding: each round drafts ``k`` tokens per slot under a cheap mode
        table, verifies all ``k+1`` positions with the exact baseline step,
        and rolls every slot back to its accepted prefix inside one compiled
        round — outputs stay bit-identical to the non-speculative greedy
        engine while expensive-mode steps per emitted token drop below 1
        (DESIGN.md section Speculative decoding).  Requires ``greedy=True``.

        ``tenants`` / ``classes`` (repro.serve.tenancy) declare the request
        streams multiplexed onto the slot array: the scheduler admits by
        (aged priority, deadline, seq) instead of FIFO, preempting running
        low-priority work by *parking its exact state row* (gather, requeue,
        scatter back at re-admission — never a re-prefill, so a preempted
        request's token stream stays bit-identical to an uncontended run).
        With ``slo=`` set, each tenant also gets its own ModeTable and
        hysteresis controller (its ``accuracy`` overrides ``slo.max_err``)
        so one tenant's hot workload cannot drag another tenant's modes;
        each step binds the per-site *most precise* mode across tenants
        with active slots.  ``scheduler_policy="fifo"`` restores the pure
        submission-order baseline (the tenant sweep's comparison point).
        """
        if config is not None:
            if batch_slots is not None or max_len is not None:
                raise ValueError(
                    "pass either config=ServeConfig(...) or the legacy flat "
                    "kwargs, not both")
            cfg = config
        else:
            if batch_slots is None or max_len is None:
                raise TypeError(
                    "ServeEngine requires batch_slots and max_len (or a "
                    "config=ServeConfig(...))")
            cfg = ServeConfig.from_kwargs(
                batch_slots, max_len, greedy=greedy, accuracy=accuracy,
                plan_backend=plan_backend, prefill_tokens=prefill_tokens,
                decode_accuracy_scale=decode_accuracy_scale,
                tune_table=tune_table, slo=slo, adapt_every=adapt_every,
                adapt=adapt, controller=controller, speculate=speculate,
                tenants=tenants, classes=classes,
                scheduler_policy=scheduler_policy, preempt=preempt,
                aging_steps=aging_steps, min_quantum=min_quantum,
                cache=cache)
        self.config = cfg
        batch_slots, max_len = cfg.batch_slots, cfg.max_len
        greedy, accuracy = cfg.greedy, cfg.accuracy
        plan_backend, prefill_tokens = cfg.plan_backend, cfg.prefill_tokens
        decode_accuracy_scale = cfg.decode_accuracy_scale
        tune_table = cfg.tune_table
        sch = cfg.scheduling
        tenants, classes = sch.tenants, sch.classes
        scheduler_policy, preempt = sch.policy, sch.preempt
        aging_steps, min_quantum = sch.aging_steps, sch.min_quantum
        slo, adapt_every = cfg.adapt.slo, cfg.adapt.adapt_every
        adapt, controller = cfg.adapt.adapt, cfg.adapt.controller
        speculate = cfg.spec
        if not greedy:
            # the masked step and the solo prefill take argmax; pretending
            # to honour a sampling flag would silently return greedy tokens
            # (and speculative verify is only exact against greedy decode)
            raise NotImplementedError(
                "ServeEngine only implements greedy decoding: temperature "
                "sampling is not wired into the masked step / prefill, and "
                "speculative verify requires greedy argmax. Pass greedy=True."
            )
        # metrics first: its plan-cache snapshot must predate phase planning
        # so plan_cache_delta() counts the plans this engine triggers
        self.metrics = ServeMetrics(batch_slots)
        # -- tracing (repro.obs): fixed at construction.  Off means the
        # shared no-op NULL_TRACER everywhere — every emit site is guarded
        # on ``tracer.enabled`` and never touches jit arguments, so traced
        # and untraced engines compile and dispatch identically (pinned by
        # tests/test_obs.py and the obs_sweep overhead gate)
        if cfg.trace:
            self.tracer = Tracer(cfg.trace if isinstance(cfg.trace, TraceConfig)
                                 else None)
        else:
            self.tracer = NULL_TRACER
        self.profiler = PhaseProfiler(self.tracer)
        if accuracy is not None:
            # Per-phase planning (DESIGN.md section Serving): decode GEMMs
            # see M = batch_slots at a tightened budget, prefill GEMMs see
            # M = prompt tokens at the caller's budget.  ``tune_table``
            # (TuneTable | path | None | False) routes both phases through
            # the measured-cost planner (DESIGN.md section Autotuner).
            scale = (self.DECODE_ACCURACY_SCALE if decode_accuracy_scale is None
                     else decode_accuracy_scale)
            self.model_decode, decode_plans = _plan_phase(
                model, batch_slots, accuracy * scale, plan_backend, tune_table)
            self.model_prefill, prefill_plans = _plan_phase(
                model, prefill_tokens or max_len, accuracy, plan_backend, tune_table)
            self.phase_plans = {"prefill": prefill_plans, "decode": decode_plans}
            # flat view kept for the PR-1 API (`engine.plans`)
            self.plans = {
                f"{phase}/{op}": p
                for phase, plans in self.phase_plans.items()
                for op, p in plans.items()
            }
        else:
            self.model_decode = self.model_prefill = model
            self.phase_plans = {}
            self.plans = {}
        self.model = self.model_decode
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.greedy = greedy
        self.scheduler = Scheduler(
            batch_slots, max_len, tenants=tenants, classes=classes,
            policy=scheduler_policy, preempt=preempt,
            aging_steps=aging_steps, min_quantum=min_quantum)
        self.scheduler.tracer = self.tracer
        self.metrics.set_tenant_shares(
            {name: t.share for name, t in self.scheduler.tenants.items()})
        #: rid -> (parked per-slot state row (device pytree), cache length)
        #: of preempted requests, scattered back verbatim at re-admission
        self._parked: dict[int, tuple[object, int]] = {}
        #: the KV layout owns the decode state's shape, the per-row
        #: gather/scatter, and (paged) the page-pool bookkeeping — the
        #: engine never touches cache internals directly (repro.serve.paged)
        self.layout = make_layout(cfg.cache, self.model_decode,
                                  batch_slots, max_len)
        self.layout.tracer = self.tracer
        self.state = self.layout.init()
        # solo-prefill template: one per-slot row, reused for every prefill.
        # Always the *dense* layout — the batch-1 dense row is the exchange
        # format every layout's scatter_row/gather_row speaks.
        self._solo0 = self.model_prefill.init_decode_state(
            1, max_len, per_slot=True)
        self._axes = self.layout.axes
        self._prefill = jax.jit(self.model_prefill.decode_step)
        self._step = jax.jit(self._masked_step)
        #: one-limb sites of the last decode program built (_decode_body)
        self.decode_one_limb = None
        # host-side slot mirrors
        self._active = np.zeros((batch_slots,), bool)
        self._last_tok = np.zeros((batch_slots,), np.int32)
        #: tokens currently in each slot's cache (virtual length) — drives
        #: paged allocation-on-append and the tier cold-page ages
        self._row_len = np.zeros((batch_slots,), np.int64)
        # -- runtime adaptation (repro.adapt) --------------------------------
        self.slo = slo
        self._adapt = bool(adapt)
        self._last_step_ms: float | None = None
        #: tokens each active slot emitted in the last measured step — the
        #: SLO's target_ms is a *per-decode-step* budget, so a speculative
        #: round (one dispatch emitting up to k+1 tokens per slot) must be
        #: normalized to its per-token step equivalent before the latency
        #: comparison, or every round would read as a latency violation and
        #: silently disable the controller's dead band (invariant iii)
        self._last_step_tokens = 1.0
        if self.phase_plans:
            self._static_decode_label = self.phase_plans["decode"]["mlp_up"].mode.name
        else:
            self._static_decode_label = model.cfg.policy.default.name
        #: per-tenant adaptation (tenants= with slo=): each tenant owns a
        #: private ModeTable + controller; the compiled step binds the
        #: per-site max across tenants with active slots (see
        #: ``_bound_scalars``) so a tenant needing precision always gets at
        #: least its own table's modes — isolation without a second compile
        self.tenant_tables: dict[str, object] = {}
        self.tenant_ctrl: dict[str, object] = {}
        self._combined_cache: dict[tuple, dict] = {}
        self._per_tenant_adapt = slo is not None and tenants is not None
        if slo is not None:
            from repro.adapt import SLO, HysteresisController, ModeTable

            def make_table():
                if self.phase_plans:
                    return ModeTable.from_plans(self.phase_plans["decode"])
                return ModeTable.from_policy(model.cfg.policy)

            if self._per_tenant_adapt:
                if controller is not None:
                    raise ValueError(
                        "controller= is a single shared instance; with "
                        "tenants= each tenant gets its own controller — "
                        "set per-tenant budgets via Tenant.accuracy instead")
                for name, ten in self.scheduler.tenants.items():
                    t_slo = SLO(
                        max_err=(ten.accuracy if ten.accuracy is not None
                                 else slo.max_err),
                        target_ms=slo.target_ms,
                        down_factor=slo.down_factor)
                    self.tenant_tables[name] = make_table()
                    ctrl = HysteresisController(t_slo)
                    ctrl.tracer, ctrl.name = self.tracer, f"adapt/{name}"
                    self.tenant_ctrl[name] = ctrl
                self.mode_table = None
                self.controller = None
            else:
                self.mode_table = make_table()
                self.controller = controller or HysteresisController(slo)
                self.controller.tracer = self.tracer
                self.controller.name = "adapt"
            self.adapt_every = max(int(adapt_every), 1)
            self._step_modal = jax.jit(self._masked_step_modal)
            self._probe = jax.jit(self._probe_fn)
        else:
            self.mode_table = None
            self.controller = None
        # -- self-speculative decoding (repro.spec) --------------------------
        self.spec = None
        if speculate is not None:
            self._init_spec(speculate)

    def _init_spec(self, spec) -> None:
        """Wire the speculative round: the verify table is the engine's live
        adaptive table when ``slo`` is set (so the PR-4 SLO controller keeps
        owning output quality) or the planner/policy decode modes otherwise;
        the draft table is that table shifted ``draft_shift`` rungs down,
        retuned at run time by the acceptance controller."""
        from repro.adapt import ModeTable
        from repro.spec import AcceptanceController, SpecConfig
        from repro.spec.rollout import build_spec_round

        if not isinstance(spec, SpecConfig):
            raise TypeError(
                f"speculate must be a repro.spec.SpecConfig, got {type(spec)}")
        if self.tenant_tables:
            # the speculative round binds ONE draft/verify table pair per
            # compiled round; per-tenant tables would need per-slot mode
            # binding inside the round — not built yet, so refuse loudly
            # rather than silently verifying tenant A under tenant B's modes
            raise NotImplementedError(
                "speculate= with per-tenant adaptation (tenants= and slo=) "
                "is not supported: the spec round verifies under one mode "
                "table. Drop slo= (static speculation works with tenants=) "
                "or drop speculate=.")
        self.spec = spec
        if self.mode_table is not None:
            self._spec_table = self.mode_table  # adaptive verify (slo path)
        elif self.phase_plans:
            self._spec_table = ModeTable.from_plans(self.phase_plans["decode"])
        else:
            self._spec_table = ModeTable.from_policy(self.model_decode.cfg.policy)
        ladder = int(self._spec_table.max_mode) - int(self._spec_table.min_mode)
        self._draft_shift = max(1, min(spec.draft_shift, max(ladder, 1)))
        self._accept_ctrl = (
            AcceptanceController(spec, ladder, shift=self._draft_shift)
            if spec.adapt and ladder > 0 else None)
        if self._accept_ctrl is not None:
            self._accept_ctrl.controller.tracer = self.tracer
            self._accept_ctrl.controller.name = "accept"
        self._spec_round = jax.jit(build_spec_round(
            self.model_decode, self._axes, spec.k,
            modal_verify=self.slo is not None))
        self._spec_window = [0, 0]  # (drafted, agreed) since last tick

    # -- compiled pieces -----------------------------------------------------

    def _decode_body(self, params, tokens, state):
        """The model's decode step, traced once per built program: the
        one-limb contraction sites it traced (``repro.core.rmpm``) are kept
        as ``decode_one_limb`` and set as ``one_limb_*`` tracer gauges."""
        snap = dataclasses.replace(one_limb_stats())
        out = self.model_decode.decode_step(params, tokens, state)
        self.decode_one_limb = one_limb_stats().since(snap)
        for name, value in dataclasses.asdict(self.decode_one_limb).items():
            self.tracer.set_gauge(f"one_limb_{name}", value)
        return out

    def _masked_step(self, params, tokens, state, active):
        """One decode token for every slot; rows where ``active`` is False
        keep their exact prior state (cache, positions, lengths) — finished
        and empty slots are inert, so a freed slot can be re-filled at any
        step without touching the others."""
        logits, new_state = self._decode_body(params, tokens, state)
        with jax.named_scope("select"):
            merged = jax.tree.map(
                lambda ax, new, old: row_select(ax, new, old, active),
                self._axes, new_state, state)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), merged

    def _masked_step_modal(self, params, tokens, state, active, modes):
        """The masked step with the mode table bound: ``modes`` is a dict of
        int32 scalars (jit arguments), so every table mutation between steps
        re-dispatches the ``lax.switch`` branches of one executable — the
        paper's run-time reconfiguration, no recompile."""
        from repro.adapt import bind_modes

        with bind_modes(modes):
            logits, new_state = self._decode_body(params, tokens, state)
        with jax.named_scope("select"):
            merged = jax.tree.map(
                lambda ax, new, old: row_select(ax, new, old, active),
                self._axes, new_state, state)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), merged

    def _probe_fn(self, params, tokens, state, active, cur, ref, down):
        """Shadow-forward error probe: the decode step replayed at the
        current, max-mode-reference and one-mode-down tables (same compiled
        executable, different mode scalars; state discarded).  Returns
        (err_current, err_one_down) as the normalized logit residual over
        active slots (repro.adapt.probe)."""
        from repro.adapt import bind_modes, logit_residual

        def fwd(modes):
            with bind_modes(modes):
                logits, _ = self.model_decode.decode_step(params, tokens, state)
            return logits[:, -1]

        l_cur, l_ref, l_down = fwd(cur), fwd(ref), fwd(down)
        return (logit_residual(l_cur, l_ref, active),
                logit_residual(l_down, l_ref, active))

    # -- streaming API -------------------------------------------------------

    def submit(self, req: Request) -> int:
        """Enqueue a request; it joins a slot on the next ``step()`` with
        free capacity.  Returns the rid."""
        rid = self.scheduler.submit(req)
        t = self.scheduler.tickets[rid]
        rc = self.scheduler.classes[t.rclass]
        self.metrics.on_submit(
            rid, tenant=t.tenant, rclass=t.rclass,
            slo_steps=rc.slo_steps, slo_ms=rc.slo_ms, step=t.submit_step)
        if self.tracer.enabled:
            self.tracer.emit(
                "submit", rid=rid, step=t.submit_step, tenant=t.tenant,
                rclass=t.rclass, prompt_len=len(t.prompt), budget=t.budget)
            self.tracer.inc("submitted")
        return rid

    def step(self) -> list[tuple[int, int]]:
        """One engine step: advance the scheduler clock, park any
        preemption victims (exact state-row gather + requeue), admit
        waiting requests into free slots (fresh: one solo prefill emitting
        the first token; preempted: scatter the parked row back — no new
        token, the request just continues), then run one masked batched
        decode step for every active slot.  Returns this step's
        (rid, token) events in emission order."""
        tr = self.tracer
        with tr.span("step") as ev:
            events = self._step_body()
            if tr.enabled:
                ev.step = self.scheduler.clock
                ev.data["n_active"] = int(self._active.sum())
        return events

    def _step_body(self) -> list[tuple[int, int]]:
        tr = self.tracer
        events: list[tuple[int, int]] = []
        with tr.span("schedule") as ev:
            self.scheduler.tick()
            tr.step = self.scheduler.clock
            if tr.enabled:
                ev.step = tr.step
            for victim in self.scheduler.plan_preemptions():
                self._park_slot(victim, cause="priority")
            self.layout.begin_admission()
            admitted = self.scheduler.admit(can_admit=self._can_admit)
        for slot, ticket in admitted:
            if slot < 0:
                # zero-budget admission (nothing fits the cache): the
                # scheduler completed it without a slot — route the
                # completion through metrics so summary()["completed"]
                # agrees with drain()/scheduler.completed
                self.metrics.on_done(ticket.rid, step=self.scheduler.clock)
                if tr.enabled:
                    tr.emit("done", rid=ticket.rid, slot=-1,
                            cause="zero_budget")
                continue
            if ticket.tokens:
                self._resume_slot(slot, ticket)
                continue
            with tr.span("admit", rid=ticket.rid, slot=slot) as ev:
                if tr.enabled:
                    ev.data.update(tenant=ticket.tenant, rclass=ticket.rclass,
                                   prompt_len=len(ticket.prompt))
                first = self._prefill_slot(slot, ticket)
                self.metrics.on_first_token(ticket.rid)
                events.append((ticket.rid, first))
                self._emit(ticket, slot, first)
        if self._active.any():
            self._prepare_pages()
        if self._active.any():
            if self.spec is not None:
                events.extend(self._spec_step())
            else:
                events.extend(self._decode_step())
            if (self.slo is not None
                    and self.metrics.decode_steps % self.adapt_every == 0
                    and self._active.any()):
                with tr.span("adapt"):
                    if self._per_tenant_adapt:
                        self._adapt_tick_tenants()
                    else:
                        self._adapt_tick()
            self._page_tick()
        return events

    def _can_admit(self, ticket: Ticket) -> bool:
        """Admission gate handed to the scheduler: the layout says whether
        it can map this ticket's cache content (dense: always — the free
        slot IS the capacity; paged: free pages after prefix-sharing
        hits)."""
        if ticket.rid in self._parked:
            return self.layout.can_admit(self._parked[ticket.rid][1])
        return self.layout.can_admit(len(ticket.prompt),
                                     prompt=ticket.prompt)

    def _prepare_pages(self) -> None:
        """Paged allocation-on-append, before the decode dispatch: every
        active row gets pages covering the tokens this step will write
        (1 plain decode, k+1 speculative).  When the pool cannot serve a
        row, the scheduler names a page-pressure victim — lowest effective
        priority among running requests — and its exact state parks
        (gather + requeue, the same bit-exact preemption path tenancy
        uses), freeing its pages; repeat until the survivors fit.  Dense
        layouts return no failures and this is a no-op."""
        ahead = (self.spec.k + 1) if self.spec is not None else 1
        while self._active.any():
            lengths = {int(s): int(self._row_len[s])
                       for s in np.nonzero(self._active)[0]}
            self.state, failed = self.layout.prepare_step(
                self.state, lengths, ahead)
            if not failed:
                return
            victim = self.scheduler.page_victim()
            if victim is None or victim.slot is None:
                victim = self.scheduler.by_slot[failed[0]]
            self._park_slot(victim, cause="page_pressure")
            self.metrics.on_page_evict()
            if self.tracer.enabled:
                self.tracer.emit("page_evict", rid=victim.rid,
                                 cause="page_pressure")
                self.tracer.inc("page_evictions")

    def _page_tick(self) -> None:
        """Post-step page accounting: occupancy/sharing stats every step,
        one tier demotion/measurement pass every ``tier_policy.every``
        decode steps (repro.adapt.pages)."""
        stats = self.layout.page_stats()
        if stats is None:
            return
        self.metrics.on_page_stats(stats)
        tp = self.config.cache.tier_policy
        if tp is None or self.metrics.decode_steps % tp.every != 0:
            return
        lengths = {int(s): int(self._row_len[s])
                   for s in np.nonzero(self._active)[0]}
        self.state, tstats = self.layout.tier_tick(
            self.state, lengths, self.metrics.decode_steps)
        if tstats is not None:
            self.metrics.on_page_tier(self.metrics.decode_steps, tstats)
            if self.tracer.enabled:
                self.tracer.emit(
                    "tier_tick",
                    cause="budget" if tp.budget else "open_loop",
                    keep=tstats.get("keep"), depth=tstats.get("depth"),
                    demoted=tstats.get("demoted"),
                    promoted=tstats.get("promoted"),
                    err=tstats.get("err"))
                self.tracer.inc("tier_demotions", tstats.get("demoted", 0))

    def _park_slot(self, victim: Ticket, cause: str = "priority") -> None:
        """Preempt a running request: gather its exact per-slot state row
        off the device (as a dense batch-1 row, whatever the layout), free
        the slot — and, paged, the row's pages — and requeue the ticket.
        Nothing is recomputed at resume — ``_resume_slot`` scatters this
        row back, so the token stream continues bit-identically.  ``cause``
        stamps the trace event ("priority" scheduler preemption vs
        "page_pressure" eviction — the latter legally ignores the quantum,
        which the replay harness accounts for by cause)."""
        slot = victim.slot
        self._parked[victim.rid] = (
            self.layout.gather_row(self.state, slot),
            int(self._row_len[slot]))
        self.state = self.layout.free_row(self.state, slot)
        self._active[slot] = False
        self.scheduler.preempt(victim.rid)
        self.metrics.on_preempt(victim.rid)
        if self.tracer.enabled:
            self.tracer.emit("preempt", rid=victim.rid, slot=slot,
                             cause=cause)
            self.tracer.inc("preemptions")

    def _resume_slot(self, slot: int, ticket: Ticket) -> None:
        """Re-admit a preempted request: scatter its parked state row into
        the (possibly different) slot and rearm the host mirrors.  No token
        is emitted and no prefill runs — the next masked step continues
        from ``ticket.tokens[-1]`` exactly as if the gap never happened."""
        row, length = self._parked.pop(ticket.rid)
        self.state = self.layout.scatter_row(
            self.state, row, slot, length=length)
        self._row_len[slot] = length
        self._active[slot] = True
        self._last_tok[slot] = ticket.tokens[-1]
        if self.tracer.enabled:
            self.tracer.emit("resume", rid=ticket.rid, slot=slot,
                             cache_len=length)

    def _tenant_active(self) -> dict[str, int]:
        """Active slots per tenant right now — metrics attribution for the
        fairness report (share of decode-slot work actually consumed)."""
        counts: dict[str, int] = {}
        for slot in np.nonzero(self._active)[0]:
            name = self.scheduler.by_slot[int(slot)].tenant
            counts[name] = counts.get(name, 0) + 1
        return counts

    def _bound_scalars(self, tenant_active: dict[str, int]):
        """(scalars, label) to bind this step under per-tenant adaptation:
        the per-site *maximum* (most precise) mode across the tables of
        tenants with active slots.  Each tenant therefore always runs at
        least as precisely as its own table demands — its controller can
        only see errors at or below what it asked for — while tables stay
        isolated (a hot tenant shifting up never mutates a cold tenant's
        table, and costs the cold tenant nothing once the hot tenant's
        slots drain)."""
        names = [n for n in tenant_active if n in self.tenant_tables]
        if not names:  # no active slots: probe-only callers, bind any table
            names = list(self.tenant_tables)
        combined: dict[str, object] = {}
        for n in names:
            for site, m in self.tenant_tables[n].modes().items():
                cur = combined.get(site)
                if cur is None or int(m) > int(cur):
                    combined[site] = m
        key = tuple(sorted((s, int(m)) for s, m in combined.items()))
        cached = self._combined_cache.get(key)
        if cached is None:
            cached = {s: jnp.asarray(int(m), jnp.int32)
                      for s, m in combined.items()}
            self._combined_cache[key] = cached
        label_names = sorted({m.name for m in combined.values()})
        label = (label_names[0] if len(label_names) == 1
                 else "/".join(label_names))
        return cached, label

    def _decode_step(self) -> list[tuple[int, int]]:
        """One masked batched decode step (the non-speculative path):
        host inputs, the dispatch, the sync on its tokens, then each row's
        token bookkeeping, each phase a span of its own."""
        tr = self.tracer
        events: list[tuple[int, int]] = []
        with tr.span("decode") as ev:
            with tr.span("decode.inputs"):
                tokens = jnp.asarray(self._last_tok[:, None])
                active = jnp.asarray(self._active)
                tenant_active = self._tenant_active()
                if self.slo is None:
                    label = self._static_decode_label
                elif self._per_tenant_adapt:
                    scalars, label = self._bound_scalars(tenant_active)
                else:
                    scalars, label = (self.mode_table.scalars(),
                                      self.mode_table.label())
            t0 = time.perf_counter()
            with tr.span("decode.dispatch"):
                if self.slo is not None:
                    next_tok, self.state = self._step_modal(
                        self.params, tokens, self.state, active, scalars)
                else:
                    next_tok, self.state = self._step(
                        self.params, tokens, self.state, active)
            with tr.span("decode.sync"):
                produced = np.asarray(next_tok)  # syncs the step
            self._last_step_ms = (time.perf_counter() - t0) * 1e3
            n_active = int(self._active.sum())
            self.metrics.on_decode_step(
                n_active, mode=label, tenant_active=tenant_active)
            if tr.enabled:
                ev.data.update(n_active=n_active, mode=label)
                tr.set_gauge("active_slots", n_active)
                self.profiler.record("decode", self._last_step_ms / 1e3,
                                     tokens=n_active)
                self.profiler.observe_cache("decode_step",
                                            self.decode_compile_count)
            with tr.span("emit"):
                for slot in np.nonzero(self._active)[0]:
                    ticket = self.scheduler.by_slot[int(slot)]
                    tok = int(produced[slot])
                    self._row_len[slot] += 1  # this step appended one KV entry
                    events.append((ticket.rid, tok))
                    self._emit(ticket, int(slot), tok)
        return events

    def _spec_step(self) -> list[tuple[int, int]]:
        """One speculative round: draft k cheap tokens per slot, verify all
        k+1 positions with the exact baseline step, emit each slot's
        accepted prefix plus the correction token (clamped to its remaining
        decode budget), and roll the state back inside the compiled round."""
        tr = self.tracer
        events: list[tuple[int, int]] = []
        with tr.span("spec_round") as ev:
            with tr.span("decode.inputs"):
                active_np = self._active.copy()
                tokens = jnp.asarray(self._last_tok[:, None])
                active = jnp.asarray(active_np)
                draft_modes = self._spec_table.scalars_shifted(-self.draft_shift)
                verify_modes = self._spec_table.scalars()
            t0 = time.perf_counter()
            with tr.span("decode.dispatch"):
                drafts, greedy, n_acc, self.state = self._spec_round(
                    self.params, tokens, self.state, active,
                    draft_modes, verify_modes)
            with tr.span("decode.sync"):
                drafts = np.asarray(drafts)  # (k, B)
                greedy = np.asarray(greedy)  # (k+1, B)
                n_acc = np.asarray(n_acc)  # (B,) — syncs the round
            self._last_step_ms = (time.perf_counter() - t0) * 1e3
            n_active = int(active_np.sum())
            self.metrics.on_decode_step(
                n_active,
                mode=(self.mode_table.label() if self.mode_table is not None
                      else self._static_decode_label),
                tenant_active=self._tenant_active(),
            )
            accepted = agreed = emitted = 0
            with tr.span("emit"):
                for slot in np.nonzero(active_np)[0]:
                    ticket = self.scheduler.by_slot[int(slot)]
                    j = int(n_acc[slot])
                    # the rolled-back cache holds the accepted prefix +
                    # correction (budget clamping truncates *emission*, not
                    # the cache)
                    self._row_len[slot] += j + 1
                    # two accounts: metrics credit only drafts that were
                    # *emitted* (a budget-truncated tail did no useful
                    # work), while the controller sees raw draft/verify
                    # *agreement* — truncation says nothing about draft
                    # quality and must not read as rejection
                    agreed += j
                    accepted += min(j, ticket.remaining)
                    burst = [int(drafts[i, slot]) for i in range(j)]
                    burst.append(int(greedy[j, slot]))  # correction / bonus
                    for tok in burst[:ticket.remaining]:
                        events.append((ticket.rid, tok))
                        self._emit(ticket, int(slot), tok)
                        emitted += 1
            self._last_step_tokens = emitted / n_active if n_active else 1.0
            self.metrics.on_spec_round(
                n_active, drafted=self.spec.k * n_active,
                accepted=accepted, emitted=emitted)
            if tr.enabled:
                from repro.spec.rollout import trace_round

                trace_round(tr, ev, k=self.spec.k, n_active=n_active,
                            agreed=agreed, emitted=emitted)
                tr.set_gauge("active_slots", n_active)
                self.profiler.record("spec", self._last_step_ms / 1e3,
                                     tokens=emitted)
                self.profiler.observe_cache("spec_round",
                                            self.spec_compile_count)
        self._spec_window[0] += self.spec.k * n_active
        self._spec_window[1] += agreed
        if (self._accept_ctrl is not None
                and self.metrics.spec_rounds % self.spec.every == 0):
            self._spec_adapt_tick()
        return events

    def _spec_adapt_tick(self) -> None:
        """Feed the windowed draft/verify disagreement rate to the
        acceptance controller; an applied decision moves ``draft_shift``
        one rung (repro.spec)."""
        drafted, agreed = self._spec_window
        if not drafted:
            return
        self._spec_window = [0, 0]
        before = self._accept_ctrl.shift
        self._accept_ctrl.observe(
            self.metrics.spec_rounds, 1.0 - agreed / drafted)
        if self._accept_ctrl.shift != before:
            self.metrics.on_draft_shift(
                self.metrics.spec_rounds, self._accept_ctrl.shift)
            if self.tracer.enabled:
                self.tracer.emit(
                    "draft_shift", shift=self._accept_ctrl.shift,
                    cause=self._accept_ctrl.controller.last_cause,
                    reject_rate=1.0 - agreed / drafted)
                self.tracer.inc("draft_shifts")

    @property
    def draft_shift(self) -> int:
        """Current rungs between the verify and draft tables (repro.spec)."""
        if self.spec is None:
            raise AttributeError("engine was built without speculate=")
        if self._accept_ctrl is not None:
            return self._accept_ctrl.shift
        return self._draft_shift

    def _adapt_tick(self) -> None:
        """One probe + controller observation; applies the shift when
        adaptation is enabled (monitor-only engines record but hold)."""
        table = self.mode_table
        ladder = int(table.max_mode) - int(table.min_mode)
        err_cur, err_down = self._probe(
            self.params,
            jnp.asarray(self._last_tok[:, None]),
            self.state,
            jnp.asarray(self._active),
            table.scalars(),
            table.scalars_shifted(ladder),  # clamps every site to max: ref
            table.scalars_shifted(-1),
        )
        err_cur, err_down = float(err_cur), float(err_down)
        self.metrics.on_probe(err_cur)
        step_ms = self._last_step_ms
        if step_ms is not None:
            step_ms /= max(self._last_step_tokens, 1.0)
        decision = self.controller.observe(
            self.metrics.decode_steps, err_cur, err_down,
            step_ms=step_ms,
            can_up=not table.at_max, can_down=not table.at_min)
        if self._adapt and decision:
            if table.shift_all(decision, tag=self.metrics.decode_steps):
                self.metrics.on_mode_switch()
                if self.tracer.enabled:
                    self.tracer.emit(
                        "mode_switch", cause=self.controller.last_cause,
                        direction=decision, mode=table.label(),
                        sites={s: m.name for s, m in table.modes().items()})
                    self.tracer.inc("mode_switches")

    def _adapt_tick_tenants(self) -> None:
        """One probe + controller observation *per tenant with active
        slots*, each against that tenant's own table and masked to that
        tenant's slots.  Isolation invariant (pinned by
        tests/test_tenancy.py): tenant A's residuals never reach tenant
        B's controller, so a hot workload shifting A's table up leaves B's
        table exactly where B's own traffic put it."""
        step_ms = self._last_step_ms
        if step_ms is not None:
            step_ms /= max(self._last_step_tokens, 1.0)
        tokens = jnp.asarray(self._last_tok[:, None])
        for name, table in self.tenant_tables.items():
            mask = np.zeros_like(self._active)
            for slot, t in self.scheduler.by_slot.items():
                if t.tenant == name and self._active[slot]:
                    mask[slot] = True
            if not mask.any():
                continue
            ladder = int(table.max_mode) - int(table.min_mode)
            err_cur, err_down = self._probe(
                self.params, tokens, self.state, jnp.asarray(mask),
                table.scalars(),
                table.scalars_shifted(ladder),
                table.scalars_shifted(-1),
            )
            err_cur, err_down = float(err_cur), float(err_down)
            self.metrics.on_probe(err_cur)
            decision = self.tenant_ctrl[name].observe(
                self.metrics.decode_steps, err_cur, err_down,
                step_ms=step_ms,
                can_up=not table.at_max, can_down=not table.at_min)
            if self._adapt and decision:
                if table.shift_all(decision, tag=self.metrics.decode_steps):
                    self.metrics.on_mode_switch()
                    if self.tracer.enabled:
                        self.tracer.emit(
                            "mode_switch",
                            cause=self.tenant_ctrl[name].last_cause,
                            direction=decision, tenant=name,
                            mode=table.label(),
                            sites={s: m.name
                                   for s, m in table.modes().items()})
                        self.tracer.inc("mode_switches")

    def drain(self) -> dict[int, list[int]]:
        """Step until queue and slots are empty; returns rid -> tokens for
        every request completed since construction."""
        while self.scheduler.has_work():
            self.step()
        return {rid: self.scheduler.tickets[rid].tokens
                for rid in self.scheduler.completed}

    # -- internals -----------------------------------------------------------

    def _prefill_slot(self, slot: int, ticket: Ticket) -> int:
        """Solo prefill at the prompt's true length, its row written into
        the slot, then the first-token sync (the ``prefill`` span).  The
        row's ``scatter`` is dispatched before the sync, so the sync also
        waits for it and the next step's dispatch finds the old state
        freed: the scatter is a span inside the prefill."""
        tr = self.tracer
        n = len(ticket.prompt)
        with tr.span("prefill", rid=ticket.rid, slot=slot) as ev:
            logits, solo = self._prefill(
                self.params, jnp.asarray(ticket.prompt)[None, :], self._solo0)
            with tr.span("scatter", rid=ticket.rid, slot=slot):
                self.state = self.layout.scatter_row(
                    self.state, solo, slot, prompt=ticket.prompt)
            first = int(jnp.argmax(logits[0, -1]))  # syncs the prefill
        self._row_len[slot] = n
        if tr.enabled:
            ev.data["prompt_len"] = n
            self.profiler.record("prefill", ev.data["dur_ms"] / 1e3, tokens=n)
            cache_size = getattr(self._prefill, "_cache_size", None)
            self.profiler.observe_cache(
                "prefill", cache_size() if callable(cache_size) else None)
        return first

    def _emit(self, ticket: Ticket, slot: int, tok: int) -> None:
        ticket.tokens.append(tok)
        self.metrics.on_token(ticket.rid)
        if self.tracer.enabled:
            self.tracer.emit("token", rid=ticket.rid, slot=slot)
            self.tracer.inc("tokens_out")
        if len(ticket.tokens) >= ticket.budget:
            self.scheduler.complete(ticket.rid)
            self.metrics.on_done(ticket.rid, step=self.scheduler.clock)
            if self.tracer.enabled:
                self.tracer.emit("done", rid=ticket.rid, slot=slot,
                                 cause="budget")
                self.tracer.inc("completed")
            self._active[slot] = False
            # completion frees the row's pages back to the pool (dense: no-op)
            self.state = self.layout.free_row(self.state, slot)
        else:
            self.scheduler.start_decode(ticket.rid)
            self._active[slot] = True
            self._last_tok[slot] = tok

    # -- reporting / compat --------------------------------------------------

    def describe(self) -> dict[str, str]:
        """The consolidated reporting surface: one dict with every
        subsystem's description (plans / adaptation / speculation / tenancy
        / cache — plus tracing/profiling when tracing is on).  The
        ``describe_*`` helpers below are thin per-key wrappers kept for the
        pre-obs API; ``launch/serve`` prints :meth:`format_describe`."""
        out = {
            "plans": self._describe_plans(),
            "adaptation": self._describe_adaptation(),
            "speculation": self._describe_speculation(),
            "tenancy": self._describe_tenancy(),
            "cache": self._describe_cache(),
        }
        if self.tracer.enabled:
            out["trace"] = self.tracer.describe()
            out["profile"] = self.profiler.describe()
        return out

    def format_describe(self) -> str:
        """One coherent engine report block (headers + sections)."""
        return "\n".join(f"-- {key} --\n{body}"
                         for key, body in self.describe().items())

    def describe_plans(self) -> str:
        return self.describe()["plans"]

    def describe_speculation(self) -> str:
        return self.describe()["speculation"]

    def describe_tenancy(self) -> str:
        return self.describe()["tenancy"]

    def describe_adaptation(self) -> str:
        return self.describe()["adaptation"]

    def describe_cache(self) -> str:
        return self.describe()["cache"]

    def _describe_plans(self) -> str:
        if not self.plans:
            return "unplanned (explicit policy)"
        return "\n".join(f"{op}: {p.describe()}" for op, p in self.plans.items())

    @property
    def decode_compile_count(self) -> int | None:
        """Number of compiled decode-step variants (None when jax does not
        expose the cache).  Stays 1 across arbitrary mode-table changes —
        the zero-recompile property tests/test_adapt.py pins."""
        fn = self._step_modal if self.slo is not None else self._step
        cache_size = getattr(fn, "_cache_size", None)
        return cache_size() if callable(cache_size) else None

    @property
    def spec_compile_count(self) -> int | None:
        """Compiled speculative-round variants (None when jax does not
        expose the cache).  Stays 1 across draft-shift and mode-table
        changes — the shift rides in as mode scalars, never a retrace."""
        if self.spec is None:
            return None
        cache_size = getattr(self._spec_round, "_cache_size", None)
        return cache_size() if callable(cache_size) else None

    def _describe_speculation(self) -> str:
        if self.spec is None:
            return "speculation off (no speculate=)"
        s = self.metrics.summary()
        acc = s["acceptance_rate"]
        vspt = s["verify_steps_per_token"]
        ctrl = ""
        if self._accept_ctrl is not None:
            ctrl = (f" | {self._accept_ctrl.shallower_moves} shallower / "
                    f"{self._accept_ctrl.deeper_moves} deeper moves")
        return (
            f"k={self.spec.k} draft_shift={self.draft_shift} "
            f"(verify {self._spec_table.describe()}) | "
            f"{s['spec_rounds']} rounds | acceptance "
            + (f"{acc:.2f}" if acc is not None else "-")
            + " | verify-steps/token "
            + (f"{vspt:.2f}" if vspt is not None else "-")
            + ctrl
        )

    def _describe_tenancy(self) -> str:
        """Scheduler configuration + per-tenant fairness report."""
        sch = self.scheduler
        head = (
            f"policy={sch.policy} aging_steps={sch.aging_steps} "
            f"preempt={'on' if sch.preempt_enabled else 'off'} "
            f"(min_quantum={sch.min_quantum}) | "
            f"{len(sch.tenants)} tenants x {len(sch.classes)} classes | "
            f"{sch.preemptions} preemptions, max wait {sch.max_wait_steps} "
            f"steps"
        )
        body = self.metrics.format_tenants()
        return head + ("\n" + body if body else "")

    def _describe_adaptation(self) -> str:
        if self.tenant_tables:
            lines = []
            for name in sorted(self.tenant_tables):
                table = self.tenant_tables[name]
                ctrl = self.tenant_ctrl[name]
                lines.append(
                    f"tenant {name}: table {table.describe()} | "
                    f"{table.switches} switches ({ctrl.up_shifts} up / "
                    f"{ctrl.down_shifts} down)")
            return "per-tenant adaptation\n" + "\n".join(lines)
        if self.mode_table is None:
            return "adaptation off (no slo)"
        s = self.metrics.summary()
        occ = " ".join(f"{m}:{f:.2f}" for m, f in s["mode_occupancy"].items())
        timeline = " -> ".join(
            f"@{step}:{label}" for step, label in self.metrics.mode_timeline)
        return (
            f"slo max_err={self.slo.max_err:g}"
            + (f" target_ms={self.slo.target_ms:g}" if self.slo.target_ms else "")
            + f" | table {self.mode_table.describe()} | "
            f"{s['mode_switches']} switches ({self.controller.up_shifts} up / "
            f"{self.controller.down_shifts} down) | occupancy {occ} | "
            f"timeline {timeline}"
        )

    def _describe_cache(self) -> str:
        """One-line KV layout report (layout name, pools, tiers, sharing)."""
        return self.layout.describe()

    def generate_batch(self, requests: list[Request]) -> dict[int, list[int]]:
        """Offline batch API on top of the streaming engine: submit
        everything, drain, return each request's tokens.  Unlike the
        pre-refactor lockstep loop, no request decodes past its own budget
        and ragged prompts never pollute the KV cache (each prefill runs at
        true length)."""
        rids = [self.submit(r) for r in requests]
        done = self.drain()
        return {rid: done[rid] for rid in rids}
