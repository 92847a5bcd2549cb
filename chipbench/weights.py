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
  near 1, the depthwise conv U[-1/2, 1/2].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _key_path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _draw(name: str, key, shape, dtype):
    last = name.rsplit("/", 1)[-1]
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
    if last == "b" or "norm" in last:
        return 0.1 * jax.random.normal(key, shape, dtype)
    if last == "w":
        return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5
    raise ValueError(f"no weight rule for leaf {name!r}")


def root_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)


def make_params(init_fn, seed: int):
    """Parameters shaped as ``init_fn(key)`` returns them, drawn from
    ``seed`` in one compiled call on the default device."""
    shapes = jax.eval_shape(init_fn, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_key_path(p) for p, _ in leaves]

    def build(key):
        out = [_draw(n, jax.random.fold_in(key, i), s.shape, s.dtype)
               for i, (n, (_, s)) in enumerate(zip(names, leaves))]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(root_key(seed))
