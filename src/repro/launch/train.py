"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
        --steps 50 --policy native_f32 --ckpt-dir /tmp/repro_ckpt

``--smoke`` shrinks the config to CPU scale (the full configs are for real
meshes; this container has one device).  On a real cluster the same driver
runs the full config: the mesh comes from ``--mesh data,model`` sizes and
jax.distributed initialization happens outside (standard JAX multi-host).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.policy import PRESETS
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed.sharding import param_shardings, replicated
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import adamw
from repro.train.loop import LoopConfig, resume_or_init, train_loop
from repro.train.step import TrainConfig, init_train_state, make_train_step


def run(argv: list[str] | None = None) -> tuple[dict, list[dict]]:
    """Parse ``argv`` (default: the command line), train, and return the
    final state and the per-step metrics history."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced CPU-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--policy", default="native_f32", choices=tuple(PRESETS))
    ap.add_argument(
        "--accuracy", type=float, default=0.0,
        help="relative-error budget for bulk GEMMs; when set, the matmul "
             "planner (repro.plan) derives the precision policy from the "
             "cost model instead of --policy",
    )
    ap.add_argument(
        "--tune-table", default="",
        help="measured-cost tuning table (file or directory, repro.tune) "
             "the planner resolves against; empty = TUNE_TABLE env var, "
             "then pure roofline",
    )
    ap.add_argument("--mesh", default="", help="e.g. '4,2' for (data=4, model=2)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument(
        "--adapt", action="store_true",
        help="grad-norm-drift precision schedule (repro.adapt): the train "
             "step compiles once with runtime mode scalars; the schedule "
             "relaxes precision down the RMPM ladder while the grad norm is "
             "stable and shifts it back up on drift spikes",
    )
    ap.add_argument("--slo-err", type=float, default=0.5,
                    help="adapt: max tolerated relative grad-norm drift")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="adapt: per-step latency target in ms (0 = none)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_policy(PRESETS[args.policy])
    if args.accuracy > 0:
        from repro.plan import plan_model_policy

        planned, plans = plan_model_policy(
            cfg, tokens=args.batch * args.seq, accuracy=args.accuracy,
            tune_table=args.tune_table or None,
        )
        cfg = cfg.with_policy(planned)
        print(f"planned policy ({args.accuracy:.1e} budget): {planned.describe()}")
        for op, p in plans.items():
            print(f"  {op}: {p.describe()}")
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("use examples/ for multimodal drivers on CPU")
    model = build_model(cfg)
    tcfg = TrainConfig(
        optimizer=adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                                    total_steps=args.steps),
        accum_steps=args.accum,
    )

    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[: len(shape)]
        mesh = jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))

    step_fn = make_train_step(model, tcfg, mesh)
    schedule = None
    if args.adapt:
        from repro.adapt import (
            SLO,
            ModeTable,
            TrainPrecisionSchedule,
            bind_modes,
        )

        table = ModeTable.from_policy(cfg.policy)
        schedule = TrainPrecisionSchedule(
            table, SLO(max_err=args.slo_err, target_ms=args.slo_ms or None))
        inner_step = step_fn

        def step_fn(state, batch, modes):  # noqa: F811 — modal wrapper
            with bind_modes(modes):
                return inner_step(state, batch)

        print(f"adaptive precision schedule: start {table.describe()}")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch, seed=0)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    if mesh is not None:
        params_shape = jax.eval_shape(model.init, jax.random.key(0))
        p_shard = param_shardings(params_shape, cfg, mesh)
        state_shard = {
            "params": p_shard,
            "opt": {"step": replicated(mesh), "m": p_shard, "v": p_shard},
        }
        shardings = ((state_shard, None, None) if schedule is not None
                     else (state_shard, None))
        # the state is created already sharded: initialising it eagerly
        # would place all of it on the first device before the step moves it
        init_sharded = jax.jit(
            lambda: init_train_state(model, jax.random.key(0), tcfg),
            out_shardings=state_shard)
        with jax.set_mesh(mesh):
            step = jax.jit(step_fn, in_shardings=shardings, donate_argnums=0)
            start, state = resume_or_init(mgr, init_sharded, state_shard)
    else:
        step = jax.jit(step_fn, donate_argnums=0)
        start, state = resume_or_init(
            mgr, lambda: init_train_state(model, jax.random.key(0), tcfg)
        )
    if start:
        data.skip_to(start)
        print(f"resumed at step {start}")

    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"{args.arch}: {n/1e6:.1f}M params | policy {cfg.policy.describe()} | mesh {args.mesh or 'single'}")

    pf = Prefetcher(data)
    try:
        ctx = jax.set_mesh(mesh) if mesh is not None else _null()
        with ctx:
            state, hist = train_loop(
                step, state, pf,
                LoopConfig(total_steps=args.steps, checkpoint_every=args.checkpoint_every),
                ckpt_manager=mgr, start_step=start, adapt=schedule,
                on_metrics=lambda r: print(
                    f"step {r['step']:5d} loss {r['loss']:.4f} gnorm {r['grad_norm']:.2f} "
                    f"dt {r['dt']*1e3:.0f}ms"
                    + (f" mode {r['mode']}" if "mode" in r else "")
                    + (" STRAGGLER" if r["straggler"] else "")
                ),
            )
    finally:
        pf.close()
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    print(f"loss {first:.4f} -> {last:.4f}")
    if schedule is not None:
        modes = [h.get("mode") for h in hist if "mode" in h]
        timeline = [modes[0]] if modes else []
        for m in modes[1:]:
            if m != timeline[-1]:
                timeline.append(m)
        print(f"precision schedule: {' -> '.join(timeline)} "
              f"({schedule.table.switches} switches)")
    return state, hist


def main() -> None:
    run()


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
