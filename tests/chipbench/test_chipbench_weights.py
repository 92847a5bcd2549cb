"""Weights drawn from the seed (``chipbench.weights``): the smoke trees of
both families draw as they did before the conv bias rule, a conv bias
draws by PyTorch's ``Conv1d`` default, and the GEMM shapes of a tree."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke  # noqa: F401
from chipbench.weights import _key_path, gemm_shapes, make_params

SEED = 2**31 + 77
#: the program's smoke trees (``chipbench_smoke.DENSE`` and ``SSM``): leaf,
#: shape, and the first 16 hex digits of the sha256 of the leaf's bytes as
#: drawn from SEED before the conv bias rule
DRAWN = {
    "dense": {
        "embed/w": ((512, 64), "6c907461efa10ee1"),
        "final_norm": ((64,), "59b9c6f26de99839"),
        "layers/seg0_dense/attn/wk/b": ((2, 64), "f3364e8b2c80e57b"),
        "layers/seg0_dense/attn/wk/w": ((2, 64, 64), "e58e4fa30d504210"),
        "layers/seg0_dense/attn/wo/w": ((2, 64, 64), "750a31f7aa77d6e7"),
        "layers/seg0_dense/attn/wq/b": ((2, 64), "9818883fcf806b35"),
        "layers/seg0_dense/attn/wq/w": ((2, 64, 64), "b124bf393f21d030"),
        "layers/seg0_dense/attn/wv/b": ((2, 64), "29ec45fe031c40e3"),
        "layers/seg0_dense/attn/wv/w": ((2, 64, 64), "f7a7609d0c8ace9f"),
        "layers/seg0_dense/mlp/down/w": ((2, 128, 64), "f64c4ee38667aad7"),
        "layers/seg0_dense/mlp/gate/w": ((2, 64, 128), "92ac6bc22a23ddfb"),
        "layers/seg0_dense/mlp/up/w": ((2, 64, 128), "2a5cf8720fa12f41"),
        "layers/seg0_dense/norm1": ((2, 64), "b7aaafed663c5781"),
        "layers/seg0_dense/norm2": ((2, 64), "2033c94180224e15"),
        "unembed/w": ((64, 512), "ed530ff73aa78833"),
    },
    "ssm": {
        "embed/w": ((256, 64), "de1fbe6dd9c6ee03"),
        "final_norm": ((64,), "59b9c6f26de99839"),
        "layers/seg0_ssm/norm1": ((2, 64), "f3364e8b2c80e57b"),
        "layers/seg0_ssm/ssm/a_log": ((2, 8), "1209a47d3dd9ee61"),
        "layers/seg0_ssm/ssm/conv_w": ((2, 4, 160), "1eb16b32d5c3785b"),
        "layers/seg0_ssm/ssm/d_skip": ((2, 8), "733e69962fae7f3f"),
        "layers/seg0_ssm/ssm/dt_bias": ((2, 8), "424584df5ab3b1f2"),
        "layers/seg0_ssm/ssm/in_proj/w": ((2, 64, 296), "a175967f5db1931f"),
        "layers/seg0_ssm/ssm/norm_scale": ((2, 128), "6a541b7f9902e59e"),
        "layers/seg0_ssm/ssm/out_proj/w": ((2, 128, 64), "f64c4ee38667aad7"),
    },
}


def tree_of(leaves: dict) -> dict:
    """A nested dict of f32 leaves named by their ``a/b/c`` paths."""
    tree: dict = {}
    for name, shape in leaves.items():
        *parents, last = name.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = jnp.zeros(shape, jnp.float32)
    return tree


def drawn(leaves: dict, seed: int = SEED) -> dict:
    tree = make_params(lambda key: tree_of(leaves), seed)
    return {_key_path(p): np.asarray(v) for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("family", sorted(DRAWN))
def test_smoke_trees_draw_as_before(family):
    got = drawn({name: shape for name, (shape, _) in DRAWN[family].items()})
    assert list(got) == list(DRAWN[family])
    for name, (shape, digest) in DRAWN[family].items():
        assert got[name].shape == shape and got[name].dtype == np.float32, name
        assert hashlib.sha256(got[name].tobytes()).hexdigest()[:16] == digest, name


@pytest.mark.parametrize("taps", [4, 2])
def test_conv_bias_draws_uniform_within_one_over_root_width(taps):
    leaves = {"layers/ssm/conv_w": (2, taps, 160), "layers/ssm/conv_b": (2, 160)}
    b = drawn(leaves)["layers/ssm/conv_b"]
    bound = taps ** -0.5
    assert np.abs(b).max() <= bound
    assert np.abs(b).max() > 0.95 * bound and abs(b.mean()) < 0.1 * bound
    assert np.unique(b).size == b.size


def test_conv_bias_needs_its_conv_beside_it():
    with pytest.raises(ValueError, match="conv_w"):
        drawn({"layers/ssm/conv_b": (2, 160)})


def test_gemm_shapes_are_the_layers_projection_weights():
    """The (K, N) that ``Dims.of`` holds the listed layers against: the
    layers' ``w`` leaves, neither the embedding, the head, the conv nor a
    norm."""
    shapes = {f: tree_of({n: s for n, (s, _) in DRAWN[f].items()}) for f in DRAWN}
    assert gemm_shapes(shapes["dense"]) == {(64, 64), (64, 128), (128, 64)}
    assert gemm_shapes(shapes["ssm"]) == {(64, 296), (128, 64)}


def test_gemm_shapes_read_a_hybrid_tree():
    """A hybrid keeps its layers under ``super/`` (stacked over the pattern's
    repeats) and ``rem/`` (one each): their ``w`` leaves are the layers'
    GEMMs; the embedding and the vocabulary head are not, whatever their
    shapes."""
    tree = tree_of({
        "embed/w": (512, 64), "unembed/w": (64, 512), "final_norm": (64,),
        "super/l0_ssm/ssm/in_proj/w": (2, 64, 296), "super/l0_ssm/ssm/conv_w": (2, 4, 160),
        "super/l0_ssm/mlp/down/w": (2, 128, 64), "super/l1_attention/attn/wq/w": (2, 64, 32),
        "rem/l0_ssm/ssm/out_proj/w": (128, 48), "rem/l0_ssm/norm1": (64,),
    })
    assert gemm_shapes(tree) == {(64, 296), (128, 64), (64, 32), (128, 48)}
