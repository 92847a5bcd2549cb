"""Sharding rules: DP / FSDP / TP / EP / SP over the production mesh.

Mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model') multi-pod.
  * batch            -> ('pod', 'data')   (pod is extra data parallelism)
  * TP ('heads')     -> heads / d_ff / experts / vocab on 'model'
  * SP ('sequence')  -> sequence on 'model' (archs whose head count does not
                        divide the model axis: qwen1.5-4b 20H, internvl2 14H)
  * FSDP             -> parameters additionally sharded over 'data'
                        (ZeRO-3 via GSPMD; scan-level all-gather)

``constrain`` is a mesh-aware with_sharding_constraint that becomes a no-op
outside a mesh context (CPU smoke tests) and drops axis names the current
mesh does not have (single-pod vs multi-pod reuse the same model code).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("pod", "data")


def _active_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", None) or getattr(mesh, "empty", False):
        return None
    return mesh


def _clean_spec(axes, mesh) -> P:
    names = set(mesh.axis_names)
    # axes that are Manual in the current (abstract) mesh — e.g. 'pod' inside
    # the gradient-compression shard_map — cannot appear in constraints
    try:
        manual = {
            n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if "Manual" in str(t)
        }
        names -= manual
    except AttributeError:
        pass
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        elif isinstance(a, (tuple, list)):
            kept = tuple(n for n in a if n in names)
            out.append(kept if kept else None)
        else:
            out.append(a if a in names else None)
    return P(*out)


def _axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, tuple(mesh.shape[a] for a in mesh.axis_names)))


def _fit_spec(axes, shape, mesh) -> P:
    """Drop mesh axes whose product does not divide the dim (replicate
    instead) — non-divisible cases (odd vocabs, batch=1 long-context,
    GQA kv-heads < model axis) are legal configs, not errors."""
    sizes = _axis_sizes(mesh)
    spec = _clean_spec(axes, mesh)
    fitted = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            fitted.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        kept: list[str] = []
        prod = 1
        for n in names:
            if dim % (prod * sizes[n]) == 0:
                kept.append(n)
                prod *= sizes[n]
        fitted.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*fitted)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """Sharding constraint that is a no-op without a mesh context."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, _fit_spec(axes, x.shape, mesh))


# ---------------------------------------------------------------------------
# Parameter shardings (by tree-path name patterns)
# ---------------------------------------------------------------------------


def _spec_for(path: str, ndim: int, cfg, stacked: bool) -> tuple:
    """Return partition axes for the trailing (non-layer-stack) dims."""
    tp = cfg.attn_shard == "heads"  # TP scheme; SP keeps weights unsharded on model
    fsdp = ("data",) if cfg.fsdp else None
    mdl = "model"

    def tail(*axes):
        return ((None,) if stacked else ()) + axes

    # --- embeddings / logits ---
    if "unembed" in path:  # (D, V)
        if path.endswith(".b"):
            return tail(mdl)
        return tail(fsdp, mdl)
    if "embed" in path:  # (V, D)
        return tail(mdl, fsdp)
    # --- MoE ---
    if "router" in path:
        return tail(fsdp, None) if ndim - stacked == 2 else tail(None)
    # Expert weights: EP (E over model) + ZeRO-3 (D or F over data).
    # Perf cell B iteration 1 tried EP-local (no data sharding): collective
    # bytes halved but resident experts hit 258 GB/device (61 layers x 24
    # experts) — refuted.  The per-microbatch regather is the honest ZeRO-3
    # cost at 1T scale; cross-pod gradient compression attacks the slower
    # link instead (EXPERIMENTS.md section Perf cell B).
    if any(s in path for s in ("moe.gate", "moe.up")):  # (E, D, F)
        return tail(mdl, fsdp, None)
    if "moe.down" in path:  # (E, F, D)
        return tail(mdl, None, fsdp)
    # --- ssm ---
    if "in_proj" in path:  # (D, d_proj) — output channels model-sharded
        return tail(fsdp, mdl if tp else None)
    if "out_proj" in path:  # (d_inner, D)
        return tail(mdl if tp else None, fsdp)
    if "conv_w" in path:  # (K, C)
        return tail(None, mdl if tp else None)
    if any(s in path for s in ("a_log", "dt_bias", "d_skip")):
        return tail(mdl if tp else None)
    # --- griffin rg-lru ---
    if any(s in path for s in ("in_x", "in_gate")):  # (D, W)
        return tail(fsdp, mdl if tp else None)
    if any(s in path for s in (".wa.", ".wx.")):  # (W, W)
        return tail(fsdp, mdl if tp else None)
    if path.endswith("lam"):
        return tail(mdl if tp else None)
    if ".out." in path or path.endswith("out.w"):  # (W, D)
        return tail(mdl if tp else None, fsdp)
    # --- attention ---
    if any(s in path for s in ("wq", "wk", "wv")):
        if path.endswith(".b"):  # bias (H*hd,)
            return tail(mdl if tp else None)
        return tail(fsdp, mdl if tp else None)
    if "wo" in path:  # (H*hd, D)
        return tail(mdl if tp else None, fsdp)
    # --- mlp ---
    if any(s in path for s in ("gate", "up")):
        if path.endswith(".b"):
            return tail(mdl if tp else None)
        return tail(fsdp, mdl if tp else None)
    if "down" in path:
        if path.endswith(".b"):
            return tail(None)
        return tail(mdl if tp else None, fsdp)
    # --- norms / scalars / everything else: replicated (fsdp on 1st if big)
    return tail(*([None] * (ndim - (1 if stacked else 0))))


def _tree_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _tree_paths(v, f"{prefix}.{k}" if prefix else str(k))
    else:
        yield prefix, tree


def param_shardings(params_shape: Any, cfg, mesh) -> Any:
    """PyTree of NamedSharding matching ``params_shape`` (ShapeDtypeStructs
    or arrays).  Layer-stacked leaves (leading dim == n_layers-ish) get a
    leading None axis."""

    def one(path, leaf):
        ndim = len(leaf.shape)
        stacked = _is_stacked(path, leaf, cfg)
        axes = _spec_for(path, ndim, cfg, stacked)
        axes = tuple(axes)[:ndim]
        axes = axes + (None,) * (ndim - len(axes))
        return jax.NamedSharding(mesh, _fit_spec(axes, leaf.shape, mesh))

    flat = dict(_tree_paths(params_shape))
    return _rebuild(params_shape, {p: one(p, leaf) for p, leaf in flat.items()})


def _is_stacked(path: str, leaf, cfg) -> bool:
    head = path.split(".", 1)[0]
    return head in ("layers", "enc_layers", "dec_layers", "super", "rem", "moe_layers")


def _rebuild(tree, flat, prefix=""):
    if isinstance(tree, dict):
        return {
            k: _rebuild(v, flat, f"{prefix}.{k}" if prefix else str(k))
            for k, v in tree.items()
        }
    return flat[prefix]


def input_shardings(batch_shape: Any, mesh) -> Any:
    """Batch inputs: leading dim over ('pod','data'), rest replicated."""

    def one(leaf):
        axes = (BATCH_AXES,) + (None,) * (len(leaf.shape) - 1)
        return jax.NamedSharding(mesh, _fit_spec(axes, leaf.shape, mesh))

    return jax.tree.map(one, batch_shape)


def replicated(mesh):
    return jax.NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Decode-state shardings (KV caches / SSM / RG-LRU states)
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


def decode_state_shardings(state_shape: Any, cfg, mesh) -> Any:
    """NamedSharding pytree for a DecodeState shape tree.

    KV caches: batch over ('pod','data'), kv-heads over 'model' when the head
    count divides the axis (GQA kv < model_size replicates KV — the standard
    TP-vs-GQA trade).  SSM / RG-LRU states: channels/heads over 'model'."""
    msize = dict(zip(mesh.axis_names, mesh.shape.values() if hasattr(mesh.shape, "values") else mesh.shape))
    model_n = msize.get("model", 1)
    tp = cfg.attn_shard == "heads"
    kv_div = cfg.n_kv_heads and cfg.n_kv_heads % model_n == 0 and tp
    mdl = "model" if tp else None

    def one(path, leaf):
        name = _path_str(path).rsplit(".", 1)[-1]
        nd = len(leaf.shape)
        if name in ("k", "v", "k_scale", "v_scale"):
            axes = (None,) * (nd - 4) + (BATCH_AXES, None, "model" if kv_div else None, None)
        elif name == "conv":
            axes = (None,) * (nd - 3) + (BATCH_AXES, None, mdl)
        elif name == "ssm":
            axes = (None,) * (nd - 4) + (BATCH_AXES, mdl, None, None)
        elif name == "h":
            axes = (None,) * (nd - 2) + (BATCH_AXES, mdl)
        elif name == "enc_out":
            axes = (BATCH_AXES,) + (None,) * (nd - 1)
        else:  # pos / length / position scalars
            axes = (None,) * nd
        return jax.NamedSharding(mesh, _fit_spec(axes[:nd], leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, state_shape)
