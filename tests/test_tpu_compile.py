"""Compile-for-chip tests: the main path's Pallas kernels and steps, compiled
with the TPU compiler for a described (not attached) TPU v5e.

Nothing here runs on a device, so these tests say nothing about results or
times; they catch what interpret mode cannot: Mosaic refusing a block shape,
a kernel that does not fit VMEM, a transform (``jax.grad``) that has no rule
once the kernel is compiled.  Every compiled program must hold a
``tpu_custom_call``, i.e. the kernel really was lowered for the chip.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library at once, and the workers of
a parallel test run all import this file.  Keep every test that needs it in
this one file, so that one worker loads the library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.policy import PAPER_BASELINE
from repro.kernels import blocking
from repro.kernels.limb_matmul.ops import _limb_matmul
from repro.kernels.quantize_mantissa.ops import _quantize_mantissa
from repro.kernels.tile_matmul.ops import F32_KMAX, _tile_matmul, tile_grid
from repro.models import build_model
from repro.models.layers import pmm

# qwen1.5-0.5b GEMMs: fused qkv, mlp up, mlp down, vocab head
QWEN_KN = [(1024, 3072), (1024, 2816), (2816, 1024), (1024, 151936)]
# decode M = 4 slots, prefill M = 512 prompt tokens
QWEN_M = [4, 512]
BLOCK = dict(bm=128, bn=128, bk=512)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Model code resolves ``interpret=None`` from the host backend, which
    is the CPU here; the chip compiles the kernels."""
    monkeypatch.setattr(blocking, "default_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", QWEN_M)
@pytest.mark.parametrize("kd,n", QWEN_KN)
def test_limb_matmul_k3(one_chip, m, kd, n):
    a = _sds((m, kd), jnp.float32, one_chip)
    b = _sds((kd, n), jnp.float32, one_chip)
    hlo = _hlo(lambda a, b: _limb_matmul(
        a, b, 3, rounding="rne", interpret=False, **BLOCK), a, b)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", QWEN_M)
@pytest.mark.parametrize("kd,n", QWEN_KN)
def test_tile_matmul_runtime_map(one_chip, m, kd, n):
    (gm, gn, _), _ = tile_grid(m, n, kd, **BLOCK)
    a = _sds((m, kd), jnp.float32, one_chip)
    b = _sds((kd, n), jnp.float32, one_chip)
    mode_map = _sds((gm, gn), jnp.int32, one_chip)
    hlo = _hlo(lambda a, b, mm: _tile_matmul(
        a, b, mm, kmax=F32_KMAX, rounding="rne", interpret=False, **BLOCK),
        a, b, mode_map)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kd,n", QWEN_KN)
def test_quantize_mantissa_grte(one_chip, kd, n):
    x = _sds((kd, n), jnp.float32, one_chip)
    hlo = _hlo(lambda x: _quantize_mantissa(x, 15, "grte", interpret=False), x)
    assert "tpu_custom_call" in hlo


def test_decode_step_full_width_pallas(one_chip, compiled_kernels):
    cfg = get_config("qwen1.5-0.5b").with_policy(PAPER_BASELINE.with_impl("pallas"))
    model = build_model(cfg)

    def shapes(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.key(0)))
    state = shapes(jax.eval_shape(
        lambda: model.init_decode_state(4, 1024, per_slot=True)))
    tokens = _sds((4, 1), jnp.int32, one_chip)
    hlo = _hlo(model.decode_step, params, tokens, state)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("impl", ["pallas", "tile"])
def test_grad_through_pmm(one_chip, compiled_kernels, impl):
    policy = PAPER_BASELINE.with_impl(impl)
    x = _sds((512, 1024), jnp.float32, one_chip)
    w = _sds((1024, 2816), jnp.float32, one_chip)
    grad = jax.grad(lambda x, w: pmm(x, w, "mlp_up", policy).sum(), (0, 1))
    hlo = _hlo(grad, x, w)
    # the backward GEMMs (dx and dw) both run the kernel
    assert hlo.count("tpu_custom_call") >= 2
