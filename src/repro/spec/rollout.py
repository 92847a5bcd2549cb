"""The compiled speculative round: draft k cheap, verify k+1 exact, roll back.

One round over the serve engine's slot array (B = slots):

  1. **draft** — ``k`` single-token substeps of the same compiled decode
     body under the *draft* mode table (``bind_modes``: the mode-select
     scalars are jit arguments, so changing the draft depth costs zero
     recompiles).  The draft runs on a scratch copy of the state: its
     low-precision KV writes and recurrent updates are never kept.
  2. **verify** — ``k + 1`` substeps of the *exact baseline step* (static
     plans, or the live adaptive table when the engine adapts) from the same
     pre-round state, over the inputs ``[t0, d1..dk]``.  Greedy argmax at
     position ``i`` is precisely the token the non-speculative engine would
     have emitted after the first ``i`` inputs — so the longest prefix where
     draft and verify agree, plus verify's correction token at the first
     disagreement, *is* the baseline token sequence (bit-identical outputs).
  3. **rollback-select** — one compiled select restores every slot to its
     accepted prefix, per leaf kind:

       * KV ``length`` / ``DecodeState.position``: arithmetic
         (``len0 + 1 + n_acc``);
       * KV rows (``k``/``v``/scales/``pos``): entries the verify chain
         wrote past the accepted point are restored from the pre-round
         cache by a ``pos > len0 + n_acc`` mask — this also repairs
         sliding-window ring buffers, whose rejected writes land on top of
         still-live old-window rows;
       * recurrent states (SSM / RG-LRU / conv): gathered per slot from the
         per-substep snapshot stack at index ``n_acc`` (these are small —
         the KV cache itself is never stacked);
       * inactive rows keep their exact pre-round state (the engine's
         masking invariant).

The whole round is one function, jitted once per engine: mode tables, draft
shift and acceptance all ride in as array arguments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.adapt import bind_modes
from repro.models.layers import (
    KVCache,
    PagedKVCache,
    paged_scatter_rows,
    paged_view,
)
from repro.serve.engine import row_select as _sel  # the masked-step freeze


def _is_kv(x) -> bool:
    """Cache nodes of either layout — skipped by snapshot, rolled back by
    the pos-mask select rather than the substep stack."""
    return isinstance(x, (KVCache, PagedKVCache))


def _gather_substep(stacked, n_acc, ax: int):
    """Pick snapshot ``n_acc[b]`` per slot from a (k+1, ...)-stacked leaf
    whose unstacked batch axis is ``ax``."""
    shape = [1] * stacked.ndim
    shape[ax + 1] = n_acc.shape[0]
    idx = n_acc.reshape(shape).astype(jnp.int32)
    return jnp.take_along_axis(stacked, idx, axis=0)[0]


def snapshot(state):
    """The rollback payload of one verify substep: every leaf except the KV
    caches (those roll back via length arithmetic + the pos-mask select, so
    stacking them across substeps would be k+1 copies of decode memory)."""
    return jax.tree.map(lambda n: None if _is_kv(n) else n, state,
                        is_leaf=_is_kv)


def _roll_kv(axn: KVCache, c0: KVCache, cf: KVCache, n_acc, active) -> KVCache:
    """Roll one KV cache node back to its accepted prefix.

    ``axn`` carries the per-leaf batch axes (layer-stacked caches put batch
    at axis 1, un-stacked hybrid remainders at axis 0); ``c0``/``cf`` are
    the pre-round and post-verify nodes.  Entries with a stored position
    past the last accepted token were written by rejected substeps: their
    rows (and ring slots — for sliding windows they overwrote live old
    rows) are restored from ``c0``.
    """
    shape = [1] * c0.length.ndim
    shape[axn.length] = n_acc.shape[0]
    keep_last = c0.length + n_acc.reshape(shape)  # position of last kept token
    mask = cf.pos > keep_last[..., None]  # (..., B, Smax): rejected writes

    def mix(fresh, old):
        m = mask.reshape(mask.shape + (1,) * (fresh.ndim - mask.ndim))
        return jnp.where(m, old, fresh)

    rolled = KVCache(
        k=mix(cf.k, c0.k),
        v=mix(cf.v, c0.v),
        k_scale=None if cf.k_scale is None else mix(cf.k_scale, c0.k_scale),
        v_scale=None if cf.v_scale is None else mix(cf.v_scale, c0.v_scale),
        pos=jnp.where(mask, c0.pos, cf.pos),
        length=keep_last + 1,
    )
    return jax.tree.map(lambda ax, new, old: _sel(ax, new, old, active),
                        axn, rolled, c0)


def _roll_paged_one(c0: PagedKVCache, cf: PagedKVCache, keep_last, mask):
    """Roll one un-stacked paged node: mix the pre-round and post-verify
    *virtual views* under the same pos mask the dense rollback uses, then
    scatter every row's mixed content back through the (unchanged) page
    table.  Shared prefix pages receive identical duplicate writes (their
    content is settled before the round and the mask never flips it), and
    unmapped rows write scratch — so the scatter is order-independent."""
    k0, v0, ks0, vs0 = paged_view(c0)
    kf, vf, ksf, vsf = paged_view(cf)

    def mix(fresh, old):
        if fresh is None:
            return None
        m = mask.reshape(mask.shape + (1,) * (fresh.ndim - mask.ndim))
        return jnp.where(m, old, fresh)

    return paged_scatter_rows(
        cf, mix(kf, k0), mix(vf, v0), mix(ksf, ks0), mix(vsf, vs0),
        pos=jnp.where(mask, c0.pos, cf.pos), length=keep_last + 1)


def _roll_paged(axn: PagedKVCache, c0: PagedKVCache, cf: PagedKVCache,
                n_acc, active) -> PagedKVCache:
    """Paged twin of :func:`_roll_kv`.  The verify chain appended through
    the page table (prepare_step pre-allocated and COW-forked pages for all
    k+1 writes), so rejected entries live in private pages: restoring them
    is a per-row virtual mix + scatter.  Per-row leaves (pos/length) then
    freeze inactive rows via the usual select; pool leaves are SHARED —
    inactive rows' cleared tables already routed their writes to scratch."""
    shape = [1] * c0.length.ndim
    shape[axn.length] = n_acc.shape[0]
    keep_last = c0.length + n_acc.reshape(shape)
    mask = cf.pos > keep_last[..., None]
    if c0.length.ndim == 2:  # layer-stacked group
        rolled = jax.vmap(_roll_paged_one)(c0, cf, keep_last, mask)
    else:
        rolled = _roll_paged_one(c0, cf, keep_last, mask)
    return jax.tree.map(lambda ax, new, old: _sel(ax, new, old, active),
                        axn, rolled, c0)


def rollback(axes, state0, state_fin, snaps, n_acc, active):
    """One compiled rollback-select over the whole DecodeState pytree."""

    def roll(axn, s0n, finn, snapn):
        if isinstance(axn, PagedKVCache):
            return _roll_paged(axn, s0n, finn, n_acc, active)
        if isinstance(axn, KVCache):
            return _roll_kv(axn, s0n, finn, n_acc, active)
        return _sel(axn, _gather_substep(snapn, n_acc, axn), s0n, active)

    return jax.tree.map(roll, axes, state0, state_fin, snaps, is_leaf=_is_kv)


def build_spec_round(model, axes, k: int, modal_verify: bool):
    """Build the pure round function for ``model`` (jit it once).

    ``axes``: the engine's per-leaf batch-axis pytree (``_batch_axes``).
    ``modal_verify``: bind the verify substeps to the engine's live mode
    table (the adaptive engines' baseline is the modal step); when False the
    verify substeps run the static-plan path — the exact executable the
    PR-2 baseline engine steps with.

    Returned signature::

        round_fn(params, tokens, state, active, draft_modes, verify_modes)
            -> (drafts (k, B), greedy (k+1, B), n_acc (B,), new_state)
    """
    if k < 1:
        raise ValueError(f"draft depth k must be >= 1, got {k}")

    def round_fn(params, tokens, state, active, draft_modes, verify_modes):
        # -- draft: k cheap-mode substeps on a scratch state ----------------
        def draft_body(carry, _):
            tok, st = carry
            with bind_modes(draft_modes):
                logits, st2 = model.decode_step(params, tok, st)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt[:, None], st2), nxt

        (_, _), drafts = jax.lax.scan(
            draft_body, (tokens, state), None, length=k)  # drafts: (k, B)

        # -- verify: k+1 exact baseline substeps from the pre-round state ---
        inputs = jnp.concatenate([tokens, drafts.T], axis=1)  # (B, k+1)

        def verify_body(st, tok_col):
            if modal_verify:
                with bind_modes(verify_modes):
                    logits, st2 = model.decode_step(params, tok_col[:, None], st)
            else:
                logits, st2 = model.decode_step(params, tok_col[:, None], st)
            g = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return st2, (g, snapshot(st2))

        state_fin, (greedy, snaps) = jax.lax.scan(
            verify_body, state, inputs.T)  # greedy: (k+1, B)

        # -- accept the longest agreeing prefix, roll back the rest ---------
        match = (drafts == greedy[:-1]).astype(jnp.int32)  # (k, B)
        n_acc = jnp.sum(jnp.cumprod(match, axis=0), axis=0)  # (B,) in [0, k]
        new_state = rollback(axes, state, state_fin, snaps, n_acc, active)
        return drafts, greedy, n_acc, new_state

    return round_fn


#: windowed draft/verify agreement below this rate is a rejection storm —
#: the draft table is too cheap for the current token distribution and the
#: acceptance controller should be pulling the shift shallower
REJECT_STORM_RATE = 0.25


def trace_round(tracer, event, *, k: int, n_active: int, agreed: int,
                emitted: int) -> None:
    """Record one speculative round's counts on its ``spec_round`` span
    ``event`` (repro.obs).

    ``agreed`` is raw draft/verify agreement (what the acceptance controller
    sees), ``emitted`` the tokens that actually left the engine after budget
    clamping.  A round whose acceptance rate drops below
    :data:`REJECT_STORM_RATE` is stamped ``cause="reject_storm"`` so draft
    collapses are findable in the trace without replaying the counters."""
    if not tracer.enabled:
        return
    drafted = k * n_active
    rate = agreed / drafted if drafted else None
    if rate is not None and rate < REJECT_STORM_RATE:
        event.cause = "reject_storm"
    event.data.update(n_active=n_active, drafted=drafted, agreed=agreed,
                      emitted=emitted, accept_rate=rate)
    tracer.inc("spec_rounds")
    tracer.inc("spec_drafted", drafted)
    tracer.inc("spec_agreed", agreed)
    if rate is not None and rate < REJECT_STORM_RATE:
        tracer.inc("spec_reject_storms")
