"""Unit tests for the scan-correct HLO cost parser — the roofline's foundation."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import (
    REFERENCE_KIND,
    Cost,
    device_peaks,
    parse_hlo_cost,
    peaks_for,
    roofline_terms,
)


def _flops(fn, *shapes):
    return parse_hlo_cost(jax.jit(fn).lower(*shapes).compile().as_text()).flops


class TestParser:
    def test_single_dot_exact(self):
        a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
        assert _flops(lambda x, y: x @ y, a, b) == 2 * 128 * 256 * 64

    def test_scan_trip_count_multiplies(self):
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

        def scan5(x):
            def body(c, _):
                return c @ c, None

            out, _ = jax.lax.scan(body, x, None, length=5)
            return out

        assert _flops(scan5, a) == 5 * 2 * 64**3

    def test_nested_scans_multiply(self):
        a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

        def nested(x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ ci, None

                c2, _ = jax.lax.scan(inner, c, None, length=3)
                return c2, None
            out, _ = jax.lax.scan(outer, x, None, length=4)
            return out

        assert _flops(nested, a) == 12 * 2 * 32**3

    def test_remat_grad_counts_recompute(self):
        a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

        def loss(x):
            body = jax.checkpoint(lambda c, _: (jnp.tanh(c @ c), None))
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out.sum()

        fl = _flops(jax.grad(loss), a)
        # fwd + recompute + 2 bwd matmuls per layer = ~4 units (allow fusion slack)
        assert fl >= 4 * 3 * 2 * 32**3

    def test_collective_bytes_multi_device(self):
        import os
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.launch.hlo_cost import parse_hlo_cost
            mesh = jax.make_mesh((8,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
            a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
            w = jax.ShapeDtypeStruct((256, 64), jnp.float32)
            sa = jax.NamedSharding(mesh, P(None, "model"))
            sw = jax.NamedSharding(mesh, P("model", None))
            with jax.set_mesh(mesh):
                c = jax.jit(lambda x, y: x @ y, in_shardings=(sa, sw)).lower(a, w).compile()
            cost = parse_hlo_cost(c.as_text())
            assert cost.collective_bytes > 0, "contraction over sharded dim must psum"
            assert "all-reduce" in cost.by_collective
            print("OK", cost.collective_bytes)
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
        out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout

    def test_roofline_terms_dominance(self):
        c = Cost(flops=197e12, hbm_bytes=1.0, collective_bytes=1.0)
        t = roofline_terms(c)
        assert t["dominant"] == "compute" and t["t_compute_s"] == pytest.approx(1.0)
        c = Cost(flops=1.0, hbm_bytes=819e9 * 2, collective_bytes=1.0)
        assert roofline_terms(c)["dominant"] == "memory"
        c = Cost(flops=1.0, hbm_bytes=1.0, collective_bytes=50e9 * 3)
        t = roofline_terms(c)
        assert t["dominant"] == "collective" and t["t_collective_s"] == pytest.approx(3.0)


class TestPeaks:
    def test_v5e_published_peaks(self):
        p = peaks_for("TPU v5 lite")
        assert (p.flops, p.hbm_bw) == (197e12, 819e9)

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks_for("TPU v4")

    def test_host_without_tpu_plans_for_reference_chip(self):
        assert jax.devices()[0].platform != "tpu"
        assert device_peaks() == peaks_for(REFERENCE_KIND)
