"""Sharded Strassen under the production mesh: compute-roofline lever.

Compiles a large per-device-local Strassen matmul over the 16x16 mesh and
reports scan-corrected HLO flops vs the classical leaf — the paper's matrix-
level contribution measured in the dry-run methodology.

    PYTHONPATH=src python -m benchmarks.strassen_sharded
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.strassen import strassen_matmul  # noqa: E402
from repro.launch.hlo_cost import device_peaks, parse_hlo_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def main() -> None:
    mesh = make_production_mesh()
    n = 16384
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    sh_a = jax.NamedSharding(mesh, P("data", None))
    sh_b = jax.NamedSharding(mesh, P(None, "model"))
    print("name,us_per_call,derived")
    base = None
    for depth in (0, 1, 2):
        def fn(x, y, d=depth):
            return strassen_matmul(x, y, depth=d, align=128)

        with jax.set_mesh(mesh):
            compiled = (
                jax.jit(fn, in_shardings=(sh_a, sh_b)).lower(a, a).compile()
            )
        cost = parse_hlo_cost(compiled.as_text())
        base = base or cost.flops
        t_c = cost.flops / device_peaks().flops
        print(
            f"strassen_sharded/depth{depth},0.0,"
            f"flops_per_dev={cost.flops:.4g};t_compute={t_c*1e3:.3f}ms;"
            f"ratio_vs_classical={cost.flops/base:.3f};"
            f"coll_bytes={cost.collective_bytes:.3g}"
        )


if __name__ == "__main__":
    main()
