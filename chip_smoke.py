"""Chip smoke test: the serving main path at full qwen1.5-0.5b width on a TPU.

    python chip_smoke.py [--seed 0]          # one chip: kernels, then serving
    python chip_smoke.py --four-chips        # training on a 2x2 mesh vs one chip

One process drives the chip.  Phases, in order; any failure exits nonzero:

1. device   -- a TPU must be attached; the CPU is never a fallback.
2. kernels  -- ``limb_matmul`` (k = 1, 2, 3), ``tile_matmul`` at each f32
               mode and one mixed map, ``quantize_mantissa`` (GRTE, keep 15),
               and ``mp_matmul_runtime(impl="tile")`` across modes 1-3, at the
               model's GEMM shapes.  Every compiled program must hold a
               ``tpu_custom_call`` (the kernel was not interpreted), uniform
               tile maps are bit-identical to ``limb_matmul``, each mode of
               the kernel and of the XLA limb algebra stays inside
               ``MODE_REL_ERROR`` against a HIGHEST-precision f32 dot, and the
               runtime path keeps one executable across modes.
3. serve    -- ``ServeEngine`` serves 8 requests on 4 slots (max_len 1024,
               16 new tokens each, greedy) in two arms: (a) per-phase planning
               at accuracy 2**-5, (b) ``PAPER_BASELINE`` on the fused limb
               kernel.  Every request must get exactly its tokens, each arm
               compiles at most one decode step, (b)'s decode step holds the
               kernel, and (b)'s prefill logits match ``native_f32``.

The last line of standard output is one JSON object naming the device; it is
printed only when every phase passed.  Throughput and compile times printed on
earlier lines are smoke output, not benchmark metrics.

Weights are random, drawn from ``--seed``; nothing is read from outside the
checkout.  The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<repo>/.jax_cache`` (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# -- phase 3 workload ----------------------------------------------------------
ARCH = "qwen1.5-0.5b"
SLOTS = 4
MAX_LEN = 1024
N_REQUESTS = 8
PROMPT_LENS = (64, 128)  # a small fixed set keeps prefill compiles bounded
MAX_NEW = 16
ACCURACY = 2.0**-5

# -- phase 2 shapes: the model's GEMMs (K, N) at decode and prefill M ----------
GEMM_KN = ((1024, 3072), (1024, 2816), (2816, 1024), (1024, 151936))
GEMM_M = (SLOTS, max(PROMPT_LENS))

# -- --four-chips ----------------------------------------------------------------
TRAIN_ARGS = ["--arch", ARCH, "--steps", "3", "--batch", "8", "--seq", "256"]
MESH = "2,2"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def compiled(what: str, fn, *args):
    """Compile ``fn`` for ``args`` and assert the kernel is in it; returns the
    executable, so the result checked is the one this program computes."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{what}: no tpu_custom_call in the compiled HLO (interpreted?)")
    return exe


def rel_err(out, ref) -> float:
    import numpy as np

    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(out - ref).max() / np.abs(ref).max())


# ---------------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------------


def phase_device(expect_count: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX sees platform {dev.platform!r} "
            f"({dev.device_kind}); this smoke test runs on the chip only")
    check(len(devices) >= expect_count,
          f"need {expect_count} TPU devices, JAX sees {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    return dev


# ---------------------------------------------------------------------------
# phase 2
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.precision import F32_MODES, MODE_LIMBS, Mode, auto_mode
    from repro.core.rmpm import mp_matmul, mp_matmul_runtime
    from repro.kernels.limb_matmul.ops import limb_matmul
    from repro.kernels.quantize_mantissa.ops import quantize_mantissa_op
    from repro.kernels.quantize_mantissa.ref import quantize_mantissa_ref
    from repro.kernels.tile_matmul.ops import (
        DEFAULT_BLOCK,
        tile_grid,
        tile_matmul,
        tile_matmul_mode,
    )
    from repro.plan.cost import MODE_REL_ERROR

    # the reference is itself an f32 dot, so it carries up to M24's error:
    # a mode's bound against it is that mode's error plus the reference's
    REF_ERROR = MODE_REL_ERROR[Mode.M24]
    bm, bn, bk = DEFAULT_BLOCK
    key = jax.random.key(seed)
    rng = np.random.default_rng(seed)
    highest = jax.jit(lambda a, b: jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST))
    for m in GEMM_M:
        for kd, n in GEMM_KN:
            key, ka, kb = jax.random.split(key, 3)
            a = jax.random.normal(ka, (m, kd), jnp.float32)
            b = jax.random.normal(kb, (kd, n), jnp.float32)
            ref = np.asarray(highest(a, b))
            shape = f"M={m} K={kd} N={n}"
            uniform = {}
            errs = []
            for mode in F32_MODES:
                k = MODE_LIMBS[mode]
                bound = MODE_REL_ERROR[mode] + REF_ERROR
                out = np.asarray(compiled(
                    f"limb_matmul k={k} {shape}",
                    lambda a, b, k=k: limb_matmul(a, b, k), a, b)(a, b))
                err = rel_err(out, ref)
                check(err <= bound, f"limb_matmul {mode.name} {shape}: error "
                                    f"{err:.3e} > {bound:.3e}")
                tile = np.asarray(compiled(
                    f"tile_matmul_mode {mode.name} {shape}",
                    lambda a, b, mode=mode: tile_matmul_mode(a, b, mode),
                    a, b)(a, b))
                check(np.array_equal(tile, out),
                      f"uniform tile map {mode.name} {shape}: not bit-identical "
                      f"to limb_matmul")
                uniform[int(mode)] = out
                # the XLA limb algebra (impl="xla") must hold the same bound
                xla = np.asarray(jax.jit(lambda a, b, mode=mode: mp_matmul(
                    a, b, mode, impl="xla"))(a, b))
                err_xla = rel_err(xla, ref)
                check(err_xla <= bound, f"mp_matmul(xla) {mode.name} {shape}: "
                                        f"error {err_xla:.3e} > {bound:.3e}")
                errs.append(f"{mode.name} {err:.3e} (xla {err_xla:.3e})")
            # one mixed map: each tile must equal the uniform run at its mode
            (gm, gn, _), (bm_, bn_, _) = tile_grid(m, n, kd, bm=bm, bn=bn, bk=bk)
            mode_map = rng.integers(1, 4, size=(gm, gn)).astype(np.int32)
            mixed = np.asarray(compiled(
                f"tile_matmul mixed map {shape}",
                lambda a, b, mm: tile_matmul(a, b, mm),
                a, b, mode_map)(a, b, mode_map))
            per_elem = np.repeat(np.repeat(mode_map, bm_, 0), bn_, 1)[:m, :n]
            expect = np.choose(per_elem - 1, [uniform[1], uniform[2], uniform[3]])
            check(np.array_equal(mixed, expect),
                  f"mixed tile map {shape}: a tile differs from the uniform "
                  f"run at its mode ({gm}x{gn} map)")
            # run-time reconfiguration: one executable, modes as data
            rt = jax.jit(lambda a, b, mode: mp_matmul_runtime(
                a, b, mode, impl="tile", allow_auto=False))
            for mode in F32_MODES:
                out = np.asarray(rt(a, b, jnp.int32(int(mode))))
                check(np.array_equal(out, uniform[int(mode)]),
                      f"mp_matmul_runtime(tile) at {mode.name} {shape}: not "
                      f"bit-identical to limb_matmul")
            check(rt._cache_size() == 1,
                  f"mp_matmul_runtime(tile) {shape}: {rt._cache_size()} "
                  f"executables across modes 1-3, expected 1")
            compiled(f"mp_matmul_runtime(tile) {shape}", rt, a, b, jnp.int32(1))
            line = f"kernels {shape}: " + ", ".join(errs) + f"; mixed {gm}x{gn} map ok"
            if m == GEMM_M[0]:
                # full-mantissa operands need all three limbs: the operand
                # probe must see a nonzero residual after each of the first two
                picked = int(jax.jit(auto_mode)(a, b))
                check(picked == int(Mode.M24),
                      f"auto_mode {shape}: picked mode {picked} for full-mantissa "
                      f"operands, expected M24")
                q = np.asarray(compiled(
                    f"quantize_mantissa {kd}x{n}",
                    lambda x: quantize_mantissa_op(x, 15, "grte"),
                    b)(b))
                check(np.array_equal(q, quantize_mantissa_ref(np.asarray(b), 15, "grte")),
                      f"quantize_mantissa grte keep 15 ({kd}x{n}): differs from "
                      f"the bit-level reference")
                line += f"; quantize_mantissa {kd}x{n} exact"
            print(line)
            del a, b, ref, uniform, mixed, expect
    print("phase kernels: ok (every program held a tpu_custom_call)")


# ---------------------------------------------------------------------------
# phase 3
# ---------------------------------------------------------------------------

# Both prefill arms carry at most M24's per-GEMM error (2**-21 relative): the
# limb algebra by construction, native_f32 by its HIGHEST-precision dot.  The
# randomly initialised model does not compound it through its 24 layers: the
# gap between two such runs measured 2x the bound on the CPU, and the chip's
# native_f32 logits sat 9x the bound from an f32 CPU run of the same model.
# 32x allows that and still sits 300x below a GEMM that fell to M8.
LOGITS_TOL = 32 * 2.0**-21


def serve_arm(name: str, model, params, config, requests, meter):
    import jax

    from repro.serve import ServeEngine

    c0, t0 = meter.seconds, time.perf_counter()
    eng = ServeEngine(model, params, config=config)
    outs = eng.generate_batch(requests)
    jax.block_until_ready(eng.state)
    wall = time.perf_counter() - t0
    for req in requests:
        got = len(outs.get(req.rid, []))
        check(got == req.max_new,
              f"arm {name}: request {req.rid} got {got} tokens, expected {req.max_new}")
    n_dec = eng.decode_compile_count
    check(n_dec is not None and n_dec <= 1,
          f"arm {name}: {n_dec} compiled decode steps, expected at most 1")
    tokens = sum(len(v) for v in outs.values())
    print(f"arm {name}: {len(outs)} requests, {tokens} tokens, "
          f"decode steps compiled: {n_dec}")
    print(f"arm {name} (smoke output, not a benchmark): "
          f"{tokens / wall:.1f} tok/s including compile, wall {wall:.1f} s, "
          f"compile {meter.seconds - c0:.1f} s")
    return eng


def phase_serve(seed: int, meter: CompileMeter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.policy import NATIVE_F32, PAPER_BASELINE
    from repro.models import build_model
    from repro.serve import Request, ServeConfig

    cfg = get_config(ARCH)
    print(f"model {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    params = jax.jit(build_model(cfg).init)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    requests = [
        Request(prompt=rng.integers(0, cfg.vocab, PROMPT_LENS[i % len(PROMPT_LENS)])
                .astype(np.int32), max_new=MAX_NEW, rid=i)
        for i in range(N_REQUESTS)
    ]

    # (a) per-phase planning from the launcher's default policy, as
    # ``repro.launch.serve --accuracy`` does
    model_a = build_model(cfg.with_policy(NATIVE_F32))
    eng_a = serve_arm("a (accuracy 2**-5)", model_a, params, ServeConfig(
        batch_slots=SLOTS, max_len=MAX_LEN, accuracy=ACCURACY,
        prefill_tokens=max(PROMPT_LENS), tune_table=False), requests, meter)
    print(eng_a.format_describe())
    del eng_a

    # (b) every pmm site on the fused limb kernel
    model_b = build_model(cfg.with_policy(PAPER_BASELINE.with_impl("pallas")))
    eng_b = serve_arm("b (paper_baseline, pallas)", model_b, params, ServeConfig(
        batch_slots=SLOTS, max_len=MAX_LEN, tune_table=False), requests, meter)
    tokens = jnp.zeros((SLOTS, 1), jnp.int32)
    active = jnp.ones((SLOTS,), bool)
    decode_hlo = eng_b._step.lower(eng_b.params, tokens, eng_b.state,
                                   active).compile().as_text()
    check("tpu_custom_call" in decode_hlo,
          "arm b: the compiled decode step holds no tpu_custom_call")
    print(f"arm b: compiled decode step holds "
          f"{decode_hlo.count('tpu_custom_call')} tpu_custom_call references")
    del eng_b

    prompt = jnp.asarray(requests[0].prompt)[None]
    logits = {}
    for name, model in (("pallas M24", model_b),
                        ("native_f32", build_model(cfg.with_policy(NATIVE_F32)))):
        state = model.init_decode_state(1, MAX_LEN, per_slot=True)
        logits[name], _ = jax.jit(model.decode_step)(params, prompt, state)
    err = rel_err(logits["pallas M24"], logits["native_f32"])
    check(err <= LOGITS_TOL,
          f"prefill logits: pallas M24 vs native_f32 differ by {err:.3e} "
          f"(relative to max |logit|) > {LOGITS_TOL:.3e}")
    print(f"prefill logits ({len(requests[0].prompt)} tokens): pallas M24 vs "
          f"native_f32 max rel diff {err:.3e} <= {LOGITS_TOL:.3e}")
    print("phase serve: ok")


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------

# Three AdamW steps from one seed on one batch stream: the mesh reorders the
# f32 reductions (the model axis splits contractions into partial sums), so
# losses differ by rounding only.
LOSS_TOL = 1e-3


def phase_train_mesh() -> None:
    import jax
    import numpy as np

    from repro.launch import train

    print(f"train {ARCH} on a (data, model) = ({MESH}) mesh: {' '.join(TRAIN_ARGS)}")
    state, hist = train.run(TRAIN_ARGS + ["--mesh", MESH])
    mesh_losses = [h["loss"] for h in hist]
    devices = jax.devices()[:4]
    held = {d: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(state):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"  device {d.id}: state shards {held[d] / 1e9:.3f} GB, "
              f"bytes_in_use {stats.get('bytes_in_use', 0) / 1e9:.3f} GB")
    check(all(held[d] > 0 for d in devices),
          "mesh training: some device holds no part of the train state")
    check(max(held.values()) < 0.75 * total,
          f"mesh training: one device holds {max(held.values()) / 1e9:.2f} of "
          f"{total / 1e9:.2f} GB of state; it is not spread")
    del state

    print(f"train {ARCH} on one chip: {' '.join(TRAIN_ARGS)}")
    state, hist = train.run(TRAIN_ARGS)
    one_losses = [h["loss"] for h in hist]
    del state
    diffs = np.abs(np.asarray(mesh_losses) - np.asarray(one_losses))
    print(f"losses mesh {mesh_losses} vs one chip {one_losses}: "
          f"max |diff| {diffs.max():.3e}")
    check(diffs.max() <= LOSS_TOL,
          f"mesh and one-chip losses differ by {diffs.max():.3e} > {LOSS_TOL}")
    print("phase train mesh: ok")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only train on a 2x2 mesh and compare with one chip")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # no tuning table from outside the checkout reaches the planner
    os.environ.pop("TUNE_TABLE", None)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    try:
        dev = phase_device(4 if args.four_chips else 1)
        cache_dir = enable_compile_cache()
        meter = CompileMeter()
        t0 = time.perf_counter()
        if args.four_chips:
            phase_train_mesh()
        else:
            phase_kernels(args.seed)
            phase_serve(args.seed, meter)
        print(f"compile: {meter.seconds:.1f} s backend compile, "
              f"{meter.hits} persistent-cache hits (cache {cache_dir}); "
              f"total {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
