"""Weights drawn from ``--seed`` on the device, in one jitted call, in the
layout and type the program serves them in (its parameter tree, f32).

The program's own ``init`` is not used: the benchmark makes the data that
both the program and the plain reference read, as a checkpoint would.  Each
leaf is drawn by a rule on its name:

- GEMM weights (``w`` of a projection, the vocabulary head): normal, std
  1/sqrt(fan_in), so activations keep their scale through depth;
- the embedding table: normal, std 1/sqrt(d_model), so that a head tied
  to it gives logits of unit scale;
- biases: normal, std 0.1; norm scales (applied as ``1 + scale``): normal,
  std 0.1;
- Mamba-2 leaves as the paper initialises them: ``a_log`` = log U[1, 16],
  ``dt_bias`` = softplus^-1 of a log-uniform dt in [1e-3, 1e-1], ``d_skip``
  near 1, the depthwise conv U[-1/2, 1/2];
- a Mamba-2 conv bias, a leaf ``conv_b`` beside the conv's ``conv_w`` (K
  taps over C channels, the taps on the second axis from the last):
  U[-1/sqrt(K), 1/sqrt(K)], PyTorch's ``Conv1d`` default for a depthwise
  conv of width K.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _key_path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _draw(name: str, key, shape, dtype, shapes: dict):
    """Leaf ``name`` of the tree whose leaves have ``shapes`` (by name)."""
    parent, _, last = name.rpartition("/")
    if name == "embed/w":
        return jax.random.normal(key, shape, dtype) * shape[-1] ** -0.5
    if last == "a_log":
        return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
    if last == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    if last == "d_skip":
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    if last == "conv_w":
        return jax.random.uniform(key, shape, dtype, -0.5, 0.5)
    if last == "conv_b":
        conv_w = f"{parent}/conv_w"
        if conv_w not in shapes:
            raise ValueError(f"conv bias {name!r} has no {conv_w!r} beside it")
        bound = shapes[conv_w][-2] ** -0.5
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    if last == "b" or "norm" in last:
        return 0.1 * jax.random.normal(key, shape, dtype)
    if last == "w":
        return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5
    raise ValueError(f"no weight rule for leaf {name!r}")


#: the ``w`` leaves that are no layer's GEMM: the embedding table and the
#: vocabulary head, which the work count adds once
NOT_LAYER_GEMMS = ("embed/w", "unembed/w")


def gemm_shapes(params) -> set[tuple[int, int]]:
    """The (K, N) of every GEMM weight of the layers of a tree (or of its
    shapes), wherever the tree keeps its layers (``layers/``, a hybrid's
    ``super/`` and ``rem/``): every leaf named ``w`` but the embedding and
    the vocabulary head, stacked axes left off."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    return {tuple(x.shape[-2:]) for p, x in leaves
            if (n := _key_path(p)).endswith("/w") and n not in NOT_LAYER_GEMMS}


def root_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)


def make_params(init_fn, seed: int):
    """Parameters shaped as ``init_fn(key)`` returns them, drawn from
    ``seed`` in one compiled call on the default device."""
    shapes = jax.eval_shape(init_fn, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_key_path(p) for p, _ in leaves]
    shapes_of = {n: s.shape for n, (_, s) in zip(names, leaves)}

    def build(key):
        out = [_draw(n, jax.random.fold_in(key, i), s.shape, s.dtype, shapes_of)
               for i, (n, (_, s)) in enumerate(zip(names, leaves))]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(root_key(seed))
