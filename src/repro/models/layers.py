"""Shared layers.  Every contraction routes through the RMPM engine (C1):
the paper's multi-precision multiplier is the only multiplier in the system.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.adapt.runtime_policy import runtime_mode_for
from repro.core.policy import PrecisionPolicy
from repro.core.precision import F32_MODES, DoubleF32
from repro.core.rmpm import mp_einsum, mp_einsum_runtime, mp_matmul_runtime
from repro.plan import execute, plan_matmul

Array = jax.Array
Params = dict[str, Any]

# ---------------------------------------------------------------------------
# Policy-routed contractions
# ---------------------------------------------------------------------------


def pmm(x: Array, w: Array, op: str, policy: PrecisionPolicy) -> Array:
    """Policy-routed matmul: the op-class name selects the precision mode
    (the paper's application-program-driven mode-select bits), the planner
    (repro.plan) selects Strassen depth and — when ``policy.impl='auto'`` —
    the execution impl.  Planning happens at trace time on static shapes and
    is cached, so a scanned layer stack plans each distinct GEMM once.

    When the call-site is bound to a runtime mode scalar (repro.adapt's
    ``bind_modes``, installed by the adaptive serve/train steps), the plan's
    static mode becomes merely the initial condition: execution routes
    through ``mp_matmul_runtime`` with the plan's impl/tuned block
    preserved, and the scalar — a jit argument — reconfigures precision
    with zero recompiles.  Tile-eligible plans (``Plan.tile_eligible``:
    pallas-class f32 plans) take the partitioned-SIMD kernel — the scalar
    becomes a uniform per-tile mode map inside ONE fused dispatch,
    bit-identical to the pallas branch the ``lax.switch`` would have
    picked; other impls keep the N-branch switch.  Only f32-ladder plans
    are switchable; DF32/Strassen plans keep their static path.

    The call runs under ``jax.named_scope("gemm/<op>")``, so its device ops
    carry the op class in their HLO ``op_name`` (DESIGN.md section
    Observability).
    """
    with jax.named_scope(f"gemm/{op}"):
        return _pmm(x, w, op, policy)


def _pmm(x: Array, w: Array, op: str, policy: PrecisionPolicy) -> Array:
    plan = plan_matmul(
        tuple(x.shape),
        tuple(w.shape),
        mode=policy.mode_for(op),
        impl=None if policy.impl == "auto" else policy.impl,
        rounding=policy.rounding,
        max_depth=policy.max_strassen_depth,
    )
    rt_mode = runtime_mode_for(op)
    if (
        rt_mode is not None
        and plan.mode in F32_MODES
        and plan.dtype == "float32"
        and not isinstance(x, DoubleF32)
    ):
        # runtime reconfiguration wins over the plan's Strassen depth: the
        # switch branches are classical (depth applies per static mode only).
        # Mode tables hold concrete modes, so the AUTO operand probe is
        # skipped (allow_auto=False — it would re-read both operands).
        # 'native' cannot express a mode switch; xla keeps the lax.switch
        impl = "tile" if plan.tile_eligible else "xla"
        return mp_matmul_runtime(
            x, w, rt_mode, rounding=plan.rounding,
            impl=impl, block=plan.block, allow_auto=False,
        )
    return execute(plan, x, w)


def pein(eq: str, a: Array, b: Array, op: str, policy: PrecisionPolicy) -> Array:
    """Policy-routed einsum (see :func:`pmm`), under ``gemm/<op>``."""
    with jax.named_scope(f"gemm/{op}"):
        return _pein(eq, a, b, op, policy)


def _pein(eq: str, a: Array, b: Array, op: str, policy: PrecisionPolicy) -> Array:
    mode = policy.mode_for(op)
    rt_mode = runtime_mode_for(op)
    if (
        rt_mode is not None
        and mode in F32_MODES
        and not isinstance(a, DoubleF32)
        and not isinstance(b, DoubleF32)
    ):
        # bound sites always run the limb engine: a 'native' policy impl
        # (plain f32, mode-blind) cannot express a mode switch, so the xla
        # limb algebra is the runtime path even for native policies —
        # adaptation trades the native fast path for reconfigurability
        impl = policy.impl if policy.impl in ("xla", "pallas", "tile") else "xla"
        return mp_einsum_runtime(
            eq, a, b, rt_mode, rounding=policy.rounding, impl=impl
        )
    return mp_einsum(
        eq, a, b, mode, rounding=policy.rounding, impl=policy.impl
    )


def plinear(x: Array, p: Params, op: str, policy: PrecisionPolicy) -> Array:
    """:func:`pmm` plus the bias, both under ``gemm/<op>``."""
    with jax.named_scope(f"gemm/{op}"):
        out = _pmm(x, p["w"], op, policy)
        if "b" in p:
            out = out + p["b"].astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, bias: bool = False, scale: float | None = None) -> Params:
    std = scale if scale is not None else (2.0 / (d_in + d_out)) ** 0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * std}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def stacked(keys, init_fn, *args, **kw):
    """Initialize per-layer params stacked along a leading layer axis
    (matches the lax.scan-over-layers execution)."""
    return jax.vmap(lambda k: init_fn(k, *args, **kw))(keys)


# ---------------------------------------------------------------------------
# Norms / activations / RoPE
# ---------------------------------------------------------------------------


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
        ang = ang[None, :, None, :]  # (1, S, 1, half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# KV cache (bf16 or block-scaled int8 — the paper's precision lever applied
# to decode memory, section Perf)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: Array  # (B, Smax, Hkv, hd)  bf16 or int8
    v: Array
    k_scale: Array | None  # (B, Smax, Hkv, 1) f32 when int8
    v_scale: Array | None
    pos: Array  # (Smax,) int32 — global position stored in each slot (-1 empty)
    # per-slot layout (continuous batching): pos is (B, Smax)
    length: Array  # scalar int32 — total tokens ever appended; (B,) per-slot


def kv_cache_init(batch: int, s_max: int, n_kv: int, hd: int, dtype: str,
                  per_slot: bool = False) -> KVCache:
    # distinct k/v buffers: donated arguments must not alias
    if per_slot:
        pos = jnp.full((batch, s_max), -1, jnp.int32)
        length = jnp.zeros((batch,), jnp.int32)
    else:
        pos = jnp.full((s_max,), -1, jnp.int32)
        length = jnp.int32(0)

    def z(dt):
        # distinct buffers per call: k/v must never alias (donation)
        return jnp.zeros((batch, s_max, n_kv, hd), dt)

    if dtype == "int8":
        def s():
            return jnp.zeros((batch, s_max, n_kv, 1), jnp.float32)

        return KVCache(z(jnp.int8), z(jnp.int8), s(), s(), pos, length)
    return KVCache(z(jnp.bfloat16), z(jnp.bfloat16), None, None, pos, length)


def stack_tree(n: int, tree):
    """Stack a cache/state pytree along a new leading layer axis."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


def _quant_rows(x: Array) -> tuple[Array, Array]:
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def kv_cache_append(cache: KVCache, k_new: Array, v_new: Array) -> KVCache:
    """Append (B, S_new, Hkv, hd) f32 at slot length % capacity (ring buffer
    for sliding-window caches; plain append while length < capacity).
    Multi-token appends must not straddle the wrap point (prefill sizes the
    cache to the prompt, so wrap only occurs in 1-token decode steps).
    """
    cap = cache.k.shape[1]
    s_new = k_new.shape[1]
    if s_new > cap:
        # prefill longer than the (sliding-window) cache: keep the tail only
        drop = s_new - cap
        k_new = k_new[:, drop:]
        v_new = v_new[:, drop:]
        new_pos = cache.length + drop + jnp.arange(cap, dtype=jnp.int32)
        length = cache.length + s_new
        cache = KVCache(cache.k, cache.v, cache.k_scale, cache.v_scale,
                        new_pos, cache.length)
        s_new = cap
        slot = jnp.int32(0)
        pos = new_pos
        total = length
    else:
        slot = jax.lax.rem(cache.length, cap)
        pos = jax.lax.dynamic_update_slice(
            cache.pos, cache.length + jnp.arange(s_new, dtype=jnp.int32), (slot,)
        )
        total = cache.length + s_new
    if cache.k_scale is not None:
        kq, ks = _quant_rows(k_new)
        vq, vs = _quant_rows(v_new)
        k = jax.lax.dynamic_update_slice(cache.k, kq, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, vq, (0, slot, 0, 0))
        kss = jax.lax.dynamic_update_slice(cache.k_scale, ks, (0, slot, 0, 0))
        vss = jax.lax.dynamic_update_slice(cache.v_scale, vs, (0, slot, 0, 0))
        return KVCache(k, v, kss, vss, pos, total)
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, slot, 0, 0))
    return KVCache(k, v, None, None, pos, total)


def _kv_append_row(c: KVCache, k_new: Array, v_new: Array) -> KVCache:
    """Single-row append: ``c`` leaves carry no batch dim (k: (Smax, Hkv, hd),
    pos: (Smax,), length: scalar).  Mirrors ``kv_cache_append`` including the
    ring wrap and the long-prefill tail-keep."""
    cap = c.k.shape[0]
    s_new = k_new.shape[0]
    if s_new > cap:
        drop = s_new - cap
        k_new, v_new = k_new[drop:], v_new[drop:]
        pos = c.length + drop + jnp.arange(cap, dtype=jnp.int32)
        total = c.length + s_new
        slot = jnp.int32(0)
        s_new = cap
    else:
        slot = jax.lax.rem(c.length, cap)
        pos = jax.lax.dynamic_update_slice(
            c.pos, c.length + jnp.arange(s_new, dtype=jnp.int32), (slot,)
        )
        total = c.length + s_new
    if c.k_scale is not None:
        kq, ks = _quant_rows(k_new)
        vq, vs = _quant_rows(v_new)
        return KVCache(
            jax.lax.dynamic_update_slice(c.k, kq, (slot, 0, 0)),
            jax.lax.dynamic_update_slice(c.v, vq, (slot, 0, 0)),
            jax.lax.dynamic_update_slice(c.k_scale, ks, (slot, 0, 0)),
            jax.lax.dynamic_update_slice(c.v_scale, vs, (slot, 0, 0)),
            pos, total,
        )
    return KVCache(
        jax.lax.dynamic_update_slice(c.k, k_new.astype(c.k.dtype), (slot, 0, 0)),
        jax.lax.dynamic_update_slice(c.v, v_new.astype(c.v.dtype), (slot, 0, 0)),
        None, None, pos, total,
    )


def kv_cache_append_slots(cache: KVCache, k_new: Array, v_new: Array) -> KVCache:
    """Per-slot append for continuous batching: ``cache.length`` is (B,) and
    ``cache.pos`` is (B, Smax), so every slot writes at its *own* ring offset
    — slots at different sequence lengths share one compiled step
    (DESIGN.md section Serving)."""
    return jax.vmap(_kv_append_row)(cache, k_new, v_new)


# ---------------------------------------------------------------------------
# Paged KV cache (fixed-size pages in a shared pool, per-row page tables —
# the serving-side layout behind repro.serve.paged.PagedLayout).  Virtual
# addressing preserves the dense ring semantics bit-for-bit: virtual slot v
# of row b lives at pool[page_tbl[b, v // page], v % page], so append/view
# reproduce exactly the (B, cap, Hkv, hd) arrays the dense cache would hold.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Page-table KV cache node (always per-slot / continuous batching).

    ``k_pool``/``v_pool`` are shared across rows: (P, page, Hkv, hd) with
    page index 0 reserved as a scratch page — unmapped table entries (-1)
    clamp to it, so appends from inactive rows (whose tables the engine has
    cleared) land in scratch instead of corrupting live pages.  ``page_tbl``
    is (B, n_pages) int32 (-1 = unmapped); ``pos``/``length`` keep the exact
    dense per-slot semantics (pos (B, cap) global positions, -1 empty).
    """

    k_pool: Array  # (P, page, Hkv, hd) bf16 or int8
    v_pool: Array
    k_scale: Array | None  # (P, page, Hkv, 1) f32 when int8
    v_scale: Array | None
    page_tbl: Array  # (B, n_pages) int32, -1 = unmapped (-> scratch page 0)
    pos: Array  # (B, cap) int32 global positions (-1 empty)
    length: Array  # (B,) int32 total tokens ever appended per row


def paged_cache_init(batch: int, cap: int, n_kv: int, hd: int, dtype: str,
                     n_pages: int, page_size: int) -> PagedKVCache:
    """Build an empty paged cache: ``n_pages`` usable pages (+1 scratch) of
    ``page_size`` tokens; ``cap`` is the per-row virtual capacity (the dense
    cache's Smax — ring wrap happens in virtual space)."""
    per_row = -(-cap // page_size)
    if n_pages < per_row:
        raise ValueError(
            f"pool of {n_pages} pages cannot hold one full row "
            f"(cap={cap}, page_size={page_size} -> {per_row} pages/row)")
    tbl = jnp.full((batch, per_row), -1, jnp.int32)
    pos = jnp.full((batch, cap), -1, jnp.int32)
    length = jnp.zeros((batch,), jnp.int32)

    def z(dt):
        return jnp.zeros((n_pages + 1, page_size, n_kv, hd), dt)

    if dtype == "int8":
        def sc():
            return jnp.zeros((n_pages + 1, page_size, n_kv, 1), jnp.float32)

        return PagedKVCache(z(jnp.int8), z(jnp.int8), sc(), sc(), tbl, pos, length)
    return PagedKVCache(z(jnp.bfloat16), z(jnp.bfloat16), None, None, tbl, pos,
                        length)


def _paged_addr(cache: PagedKVCache, vi: Array) -> tuple[Array, Array]:
    """Virtual indices (B, S) -> (pool page, in-page offset), clamping
    unmapped entries to the scratch page."""
    ps = cache.k_pool.shape[1]
    pages = jnp.take_along_axis(cache.page_tbl, vi // ps, axis=1)
    return jnp.maximum(pages, 0), vi % ps


def paged_append(cache: PagedKVCache, k_new: Array, v_new: Array) -> PagedKVCache:
    """Per-row ring append through the page table.  Mirrors
    ``kv_cache_append_slots`` exactly in virtual space (same cast, same int8
    row quantization, same pos/length updates); multi-token appends must not
    straddle the virtual wrap point, same as the dense contract."""
    cap = cache.pos.shape[1]
    b, s_new = k_new.shape[:2]
    slot = jax.lax.rem(cache.length, cap)  # (B,)
    vi = slot[:, None] + jnp.arange(s_new, dtype=jnp.int32)  # (B, S)
    rows = jnp.arange(b)[:, None]
    pos = cache.pos.at[rows, vi].set(
        cache.length[:, None] + jnp.arange(s_new, dtype=jnp.int32))
    pages, off = _paged_addr(cache, vi)
    total = cache.length + s_new
    if cache.k_scale is not None:
        kq, ks = _quant_rows(k_new)
        vq, vs = _quant_rows(v_new)
        return PagedKVCache(
            cache.k_pool.at[pages, off].set(kq),
            cache.v_pool.at[pages, off].set(vq),
            cache.k_scale.at[pages, off].set(ks),
            cache.v_scale.at[pages, off].set(vs),
            cache.page_tbl, pos, total,
        )
    return PagedKVCache(
        cache.k_pool.at[pages, off].set(k_new.astype(cache.k_pool.dtype)),
        cache.v_pool.at[pages, off].set(v_new.astype(cache.v_pool.dtype)),
        None, None, cache.page_tbl, pos, total,
    )


def paged_view(cache: PagedKVCache) -> tuple[Array, Array, Array | None, Array | None]:
    """Materialize the dense (B, cap, Hkv, hd) view the attention kernel
    reads: gather pages by table, flatten, trim to the virtual capacity.
    Unmapped entries read the scratch page — garbage there is masked by
    ``pos == -1`` in flash_attention, so the view is bit-identical to the
    dense cache wherever positions are valid."""
    cap = cache.pos.shape[1]
    npg, ps = cache.page_tbl.shape[1], cache.k_pool.shape[1]
    b = cache.page_tbl.shape[0]
    tbl = jnp.maximum(cache.page_tbl, 0)

    def view(pool):
        if pool is None:
            return None
        return pool[tbl].reshape(b, npg * ps, *pool.shape[2:])[:, :cap]

    return view(cache.k_pool), view(cache.v_pool), view(cache.k_scale), view(cache.v_scale)


def paged_scatter_rows(cache: PagedKVCache, k: Array, v: Array,
                       k_scale: Array | None, v_scale: Array | None,
                       pos: Array, length: Array) -> PagedKVCache:
    """Write every row's full (B, cap, ...) virtual content back through the
    page table — the inverse of ``paged_view``, used by the speculative
    rollback to restore rejected writes.  Rows whose table entries are
    unmapped write the scratch page (inactive rows are harmless); rows
    sharing a page write identical bits (shared prefix pages are fully
    settled before any speculative round), so duplicate scatters are
    order-independent."""
    cap = cache.pos.shape[1]
    b = cache.page_tbl.shape[0]
    vi = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    pages, off = _paged_addr(cache, vi)

    def put(pool, vals):
        if pool is None:
            return None
        return pool.at[pages, off].set(vals.astype(pool.dtype))

    return PagedKVCache(
        put(cache.k_pool, k), put(cache.v_pool, v),
        put(cache.k_scale, k_scale), put(cache.v_scale, v_scale),
        cache.page_tbl, pos, length,
    )


def kv_append(cache: KVCache | PagedKVCache, k_new: Array, v_new: Array):
    """Layout dispatch for cache appends (the KVLayout seam): paged nodes
    scatter through their page table, dense per-slot nodes ((B,) lengths)
    ring-append per row, shared-length nodes append at one scalar offset."""
    if isinstance(cache, PagedKVCache):
        return paged_append(cache, k_new, v_new)
    if cache.length.ndim == 1:
        return kv_cache_append_slots(cache, k_new, v_new)
    return kv_cache_append(cache, k_new, v_new)


def kv_view(cache: KVCache | PagedKVCache):
    """The (k, v, k_scale, v_scale) arrays attention reads for this node."""
    if isinstance(cache, PagedKVCache):
        return paged_view(cache)
    return cache.k, cache.v, cache.k_scale, cache.v_scale


def _dequant_chunk(x: Array, scale: Array | None) -> Array:
    """A cache chunk as the contraction reads it: an unscaled chunk keeps its
    storage dtype (a bf16 value is one exact limb, so the limb engine need
    not split it); an int8 chunk times its scale is an f32 value."""
    if scale is None:
        return x
    return x.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Flash attention (chunked-KV online softmax — never materializes S x S)
# ---------------------------------------------------------------------------


def flash_attention(
    q: Array,  # (B, Sq, Hq, hd) f32
    k: Array,  # (B, Skv, Hkv, hd) f32/bf16/int8
    v: Array,
    policy: PrecisionPolicy,
    *,
    causal: bool = True,
    window: int = 0,  # sliding window (0 = unbounded)
    q_offset: Array | int = 0,  # global position of q[0] (decode); (B,) per-slot
    kv_len: Array | int | None = None,  # valid cache length
    kv_positions: Array | None = None,  # (Skv,) or (B, Skv) global positions
    k_scale: Array | None = None,
    v_scale: Array | None = None,
    chunk: int = 1024,
) -> Array:
    """Online-softmax attention, KV scanned in chunks.

    GQA: Hq = Hkv * G.  Scores and attention-value products go through the
    RMPM engine ('attn_qk' / 'attn_av' op classes).  The chunked scan keeps
    the compiled working set at O(Sq * chunk) instead of O(Sq * Skv) — the
    memory-roofline term depends directly on ``chunk``.
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        def padded(x):
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))

        k, v = padded(k), padded(v)
        if k_scale is not None:
            k_scale, v_scale = padded(k_scale), padded(v_scale)
        if kv_positions is not None:
            kv_positions = jnp.pad(
                kv_positions,
                ((0, 0), (0, pad)) if kv_positions.ndim == 2 else (0, pad),
                constant_values=-1,
            )
    if kv_len is None:
        kv_len = skv
    if kv_positions is None:
        kv_positions = jnp.arange(n_chunks * chunk, dtype=jnp.int32)
        kv_positions = jnp.where(kv_positions < jnp.asarray(kv_len), kv_positions, -1)

    qg = q.reshape(b, sq, hkv, g, hd).astype(jnp.float32) * (hd**-0.5)
    q_offset = jnp.asarray(q_offset)
    # per-slot decode: q_offset (B,) -> q_pos (B, Sq); shared: (Sq,)
    q_pos = (q_offset[:, None] if q_offset.ndim else q_offset) + jnp.arange(sq)

    kc = k.reshape(b, n_chunks, chunk, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk, hkv, hd)
    ksc = k_scale.reshape(b, n_chunks, chunk, hkv, 1) if k_scale is not None else None
    vsc = v_scale.reshape(b, n_chunks, chunk, hkv, 1) if v_scale is not None else None

    def step(carry, ci):
        m, lse, acc = carry
        kt = _dequant_chunk(
            jax.lax.dynamic_index_in_dim(kc, ci, 1, keepdims=False),
            jax.lax.dynamic_index_in_dim(ksc, ci, 1, keepdims=False) if ksc is not None else None,
        )
        vt = _dequant_chunk(
            jax.lax.dynamic_index_in_dim(vc, ci, 1, keepdims=False),
            jax.lax.dynamic_index_in_dim(vsc, ci, 1, keepdims=False) if vsc is not None else None,
        )
        s = pein("bqhgd,bkhd->bhgqk", qg, kt, "attn_qk", policy)  # (B,Hkv,G,Sq,C)
        kv_pos = jax.lax.dynamic_slice_in_dim(
            kv_positions, ci * chunk, chunk, axis=kv_positions.ndim - 1
        )
        # broadcast to (B|1, Sq|1, C) so per-slot positions mask per batch row
        kv_b = kv_pos if kv_pos.ndim == 2 else kv_pos[None, :]  # (B|1, C)
        q_b = q_pos if q_pos.ndim == 2 else q_pos[None, :]  # (B|1, Sq)
        valid = kv_b[:, None, :] >= 0
        if causal:
            valid = valid & (kv_b[:, None, :] <= q_b[:, :, None])
        if window:
            valid = valid & (kv_b[:, None, :] > q_b[:, :, None] - window)
        s = jnp.where(valid[:, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = lse * alpha + p.sum(axis=-1)
        pv = pein("bhgqk,bkhd->bhgqd", p, vt, "attn_av", policy)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (m, lse, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(lse, 1e-30)[..., None]
    # (B,Hkv,G,Sq,hd) -> (B,Sq,Hq,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, hd)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + flash) — train and decode paths
# ---------------------------------------------------------------------------


def attention_init(key, cfg) -> Params:
    ks = jax.random.split(key, 4)
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": dense_init(ks[0], cfg.d_model, hq * hd, cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, hkv * hd, cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, hkv * hd, cfg.qkv_bias),
        "wo": dense_init(ks[3], hq * hd, cfg.d_model),
    }


def attention_apply(
    p: Params,
    x: Array,
    cfg,
    *,
    positions: Array | None = None,
    cache: KVCache | PagedKVCache | None = None,
    window: int = 0,
    causal: bool = True,
    kv_override: tuple[Array, Array] | None = None,  # cross-attention KV
) -> tuple[Array, KVCache | PagedKVCache | None]:
    policy = cfg.policy
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    sp = cfg.attn_shard == "sequence"
    q = plinear(x, p["wq"], "qkv", policy).reshape(b, s, hq, hd)
    if kv_override is None:
        k = plinear(x, p["wk"], "qkv", policy).reshape(b, s, hkv, hd)
        v = plinear(x, p["wv"], "qkv", policy).reshape(b, s, hkv, hd)
        if positions is None:
            positions = jnp.arange(s)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if sp:
            # sequence-parallel attention: Q stays S-sharded over 'model';
            # K/V are all-gathered (small for GQA) — without these explicit
            # constraints GSPMD replicates the whole attention computation
            # (measured 5-11x HLO-flop waste, EXPERIMENTS.md section Perf cell A)
            from repro.distributed.sharding import BATCH_AXES as _BA, constrain as _c

            q = _c(q, _BA, "model", None, None)
            k = _c(k, _BA, None, None, None)
            v = _c(v, _BA, None, None, None)
        else:
            from repro.distributed.sharding import BATCH_AXES as _BA, constrain as _c

            q = _c(q, _BA, None, "model", None)
            k = _c(k, _BA, None, "model", None)
            v = _c(v, _BA, None, "model", None)
    else:
        enc = kv_override[0]
        k = plinear(enc, p["wk"], "qkv", policy).reshape(b, enc.shape[1], hkv, hd)
        v = plinear(enc, p["wv"], "qkv", policy).reshape(b, enc.shape[1], hkv, hd)
        causal = False

    q_offset = None
    if cache is not None and kv_override is None:
        q_offset = cache.length
        cache = kv_append(cache, k, v)  # KVLayout dispatch (paged/per-slot/shared)
    # the attention core (cache read and flash attention; not the
    # projections) under one scope: its score and value contractions
    # count as attention, not as weight GEMMs
    with jax.named_scope("attention"):
        out = _attention_core(q, k, v, policy, cfg, cache, q_offset,
                              causal, window)
    out = pmm(out.reshape(b, s, hq * hd), p["wo"]["w"], "out", policy)
    return out, cache


def _attention_core(q, k, v, policy, cfg, cache, q_offset, causal, window):
    """Flash attention over the fresh K/V (prefill, no cache) or over the
    cache read after this step's append (decode: ``q_offset`` is the cache
    length before it)."""
    s = q.shape[1]
    if q_offset is not None:
        if s > 1:
            # prefill: attend over the fresh full-length K/V (the window
            # cache may be smaller than the prompt; it keeps only the tail)
            fresh_pos = jnp.arange(s, dtype=jnp.int32)
            fresh_pos = (q_offset[:, None] if q_offset.ndim else jnp.asarray(q_offset)) + fresh_pos
            out = flash_attention(
                q, k, v, policy, causal=causal, window=window,
                q_offset=q_offset,
                kv_positions=fresh_pos,
                chunk=cfg.attn_chunk,
            )
        else:
            k_read, v_read, ks_read, vs_read = kv_view(cache)
            out = flash_attention(
                q,
                k_read,
                v_read,
                policy,
                causal=causal,
                window=window,
                q_offset=q_offset,
                kv_positions=cache.pos,
                k_scale=ks_read,
                v_scale=vs_read,
                chunk=cfg.attn_chunk,
            )
    else:
        out = flash_attention(
            q, k, v, policy, causal=causal, window=window, chunk=cfg.attn_chunk
        )
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "gate": dense_init(ks[0], d_model, d_ff),
        "up": dense_init(ks[1], d_model, d_ff),
        "down": dense_init(ks[2], d_ff, d_model),
    }


def swiglu_apply(p: Params, x: Array, policy: PrecisionPolicy) -> Array:
    g = pmm(x, p["gate"]["w"], "mlp_up", policy)
    u = pmm(x, p["up"]["w"], "mlp_up", policy)
    return pmm(jax.nn.silu(g) * u, p["down"]["w"], "mlp_down", policy)


def gelu_mlp_init(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "up": dense_init(ks[0], d_model, d_ff, bias=True),
        "down": dense_init(ks[1], d_ff, d_model, bias=True),
    }


def gelu_mlp_apply(p: Params, x: Array, policy: PrecisionPolicy) -> Array:
    h = jax.nn.gelu(plinear(x, p["up"], "mlp_up", policy))
    return plinear(h, p["down"], "mlp_down", policy)


# ---------------------------------------------------------------------------
# Causal depthwise conv (SSM / RG-LRU front)
# ---------------------------------------------------------------------------


def causal_conv1d(x: Array, w: Array, state: Array | None = None):
    """x: (B, S, C); w: (K, C) depthwise.  Returns (y, new_state) where
    state carries the trailing K-1 inputs for decode."""
    ksz = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (ksz - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(ksz)
    )
    new_state = xp[:, -(ksz - 1) :, :] if ksz > 1 else None
    return y, new_state
