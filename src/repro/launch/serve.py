"""Serving launcher: open-loop continuous batching on any assigned arch.

Requests arrive by a Poisson process (``--arrival-rate`` req/s of wall
clock; 0 = everything at t=0), with ragged prompt lengths and per-request
decode budgets, and stream through ``repro.serve.ServeEngine``.  Pass
``--accuracy`` to let the matmul planner pick the RMPM precision mode per
phase — prefill and decode GEMMs are planned separately, so a budget near a
mode boundary flips the mode bits *between phases of the same workload*
(the paper's run-time reconfiguration, end to end).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 8 --slots 4 --arrival-rate 2 --accuracy 1e-3 [--kv-int8]

Pass ``--adapt`` (with ``--slo-err``, optionally ``--slo-ms``) to close the
loop at run time: the decode phase's planned modes become a mutable mode
table that repro.adapt's probe + hysteresis controller retunes against the
SLO between steps — one compiled step, the mode scalars select the live
``lax.switch`` branches (zero recompiles).

Pass ``--speculate`` (with ``--draft-k``, ``--draft-shift``) for
self-speculative decoding (repro.spec): the cheap mode of the same step
drafts, the exact baseline step verifies — outputs stay token-identical
while expensive-mode steps per token drop below 1.

Pass ``--multi-tenant`` for a canned two-tenant mix (an ``interactive``
tenant with priority 0 and deadline-carrying chat requests vs a ``bulk``
tenant flooding the slots with long batch decodes) under the priority+EDF
scheduler with preemption, and a per-tenant fairness/SLO report at the end
(``--scheduler-policy fifo`` shows the same traffic without priorities).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.policy import PRESETS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.plan import plan_cache_stats
from repro.serve import Request, ServeEngine, ragged_requests


def run_open_loop(eng: ServeEngine, reqs: list[Request], rate: float,
                  rng: np.random.Generator) -> dict[int, list[int]]:
    """Submit each request at its Poisson arrival time (wall clock), stepping
    the engine in between — requests join slots mid-flight as capacity
    frees."""
    if rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=len(reqs)))
    else:
        arrivals = np.zeros(len(reqs))
    t0 = time.perf_counter()
    pending = list(zip(arrivals, reqs))
    outputs: dict[int, list[int]] = {}
    while pending or eng.scheduler.has_work():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending.pop(0)[1])
        if eng.scheduler.has_work():
            eng.step()
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.05))
    for rid, toks in eng.drain().items():
        outputs[rid] = toks
    return outputs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="slot-array width (0 = one per request)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length; actual lengths are ragged")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = all at t=0)")
    ap.add_argument("--policy", default="native_f32", choices=tuple(PRESETS))
    ap.add_argument("--accuracy", type=float, default=None,
                    help="plan per-phase precision for this relative-error "
                         "budget instead of using the --policy preset modes")
    ap.add_argument("--tune-table", default="",
                    help="measured-cost tuning table (file or directory, "
                         "repro.tune) for the per-phase planner; empty = "
                         "TUNE_TABLE env var, then pure roofline")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--adapt", action="store_true",
                    help="closed-loop runtime precision adaptation of the "
                         "decode phase (repro.adapt)")
    ap.add_argument("--slo-err", type=float, default=0.05,
                    help="SLO: max observed relative error (probe logit "
                         "residual vs the max-mode reference)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="SLO: decode-step latency target in ms (0 = none); "
                         "overshooting applies downward mode pressure "
                         "within the error SLO")
    ap.add_argument("--adapt-every", type=int, default=4,
                    help="probe cadence in decode steps")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative decoding (repro.spec): draft "
                         "--draft-k tokens per slot under a cheap mode "
                         "table, verify with the exact baseline step — "
                         "bit-identical outputs, <1 expensive-mode step per "
                         "token")
    ap.add_argument("--draft-k", type=int, default=3,
                    help="draft depth per speculative round")
    ap.add_argument("--draft-shift", type=int, default=2,
                    help="initial rungs below the verify modes for the "
                         "draft table (the acceptance controller retunes "
                         "it at run time)")
    ap.add_argument("--multi-tenant", action="store_true",
                    help="canned interactive-vs-bulk tenant mix under the "
                         "priority scheduler, with a per-tenant SLO report")
    ap.add_argument("--scheduler-policy", default="priority",
                    choices=("priority", "fifo"),
                    help="scheduler for --multi-tenant (default: priority)")
    ap.add_argument("--paged", action="store_true",
                    help="page-table KV cache (repro.serve.paged): admission "
                         "gated on free pages, eviction under page pressure, "
                         "prefix sharing — bit-identical tokens at full "
                         "precision")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool budget for the largest cache group "
                         "(0 = memory-equivalent to the dense layout)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable read-only prompt-prefix page sharing")
    ap.add_argument("--tier-levels", default="",
                    help="comma-separated keep-bits ladder for precision-"
                         "tiered pages, e.g. '5,3' (empty = tiers off; "
                         "requires --paged and a bf16 KV cache)")
    ap.add_argument("--tier-cold-after", type=int, default=32,
                    help="tokens behind the decode head before a page is "
                         "demotion-eligible")
    ap.add_argument("--tier-every", type=int, default=8,
                    help="decode steps between tier ticks")
    ap.add_argument("--tier-budget", type=float, default=0.0,
                    help="closed-loop residual budget for the tier "
                         "controller (0 = open loop at full depth)")
    ap.add_argument("--trace", action="store_true",
                    help="structured tracing (repro.obs): per-request spans "
                         "+ engine/decision events, printed as a precision "
                         "timeline and profile at exit")
    ap.add_argument("--trace-out", default="",
                    help="write the trace as Chrome-trace/Perfetto JSON to "
                         "this path (implies --trace)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_policy(PRESETS[args.policy])
    if args.kv_int8:
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("use examples/ for multimodal drivers on CPU")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(args.seed)
    tenants = None
    classes = None
    if args.multi_tenant:
        from repro.serve import RequestClass, Tenant, class_requests

        tenants = [Tenant("interactive", priority=0, share=2.0),
                   Tenant("bulk", priority=2, share=1.0)]
        classes = [RequestClass("chat", slo_steps=10, prompt_len=6,
                                max_new=max(args.max_new // 2, 2)),
                   RequestClass("batch", prompt_len=args.prompt_len,
                                max_new=args.max_new)]
        n_bulk = max(args.requests // 2, 1)
        reqs = class_requests(classes[1], tenants[1], n_bulk, cfg.vocab, rng)
        reqs += class_requests(classes[0], tenants[0],
                               max(args.requests - n_bulk, 1), cfg.vocab,
                               rng, rid_base=100)
    else:
        reqs = ragged_requests(args.requests, cfg.vocab, args.prompt_len,
                               args.max_new, rng)
    # the grouped config path (ServeConfig.from_flags) — the documented way
    # to construct an engine; all launcher flags route through it
    from repro.serve import ServeConfig

    eng = ServeEngine(
        model, params,
        config=ServeConfig.from_flags(args, tenants=tenants, classes=classes))
    t0 = time.perf_counter()
    outs = run_open_loop(eng, reqs, args.arrival_rate, rng)
    dt = time.perf_counter() - t0
    for rid in sorted(outs):
        print(f"req {rid}: {outs[rid]}")
    # one coherent engine report: plans / adaptation / speculation / tenancy
    # / cache (+ trace and profile when tracing) from the consolidated
    # ServeEngine.describe() surface
    print(eng.format_describe())
    if args.adapt:
        print(f"compiled decode-step variants: {eng.decode_compile_count}")
    if args.speculate:
        print(f"compiled spec-round variants: {eng.spec_compile_count}")
    stats = plan_cache_stats()
    print(f"plan cache: {stats.entries} entries, "
          f"{stats.hits} hits / {stats.misses} misses (process-wide)")
    total = sum(len(v) for v in outs.values())
    print(f"{total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s incl compile; "
          f"kv={cfg.kv_cache_dtype}; slots={eng.config.batch_slots})")
    print(eng.metrics.format_summary())
    if eng.tracer.enabled:
        print(f"precision timeline:\n{eng.tracer.format_timeline()}")
        if args.trace_out:
            doc = eng.tracer.export_chrome(args.trace_out)
            print(f"trace: {len(doc['traceEvents'])} Chrome events "
                  f"-> {args.trace_out}")


if __name__ == "__main__":
    main()
