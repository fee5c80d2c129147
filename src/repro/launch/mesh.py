"""Production mesh definitions.

Single pod = 16x16 (256 chips, v5e-class pod); multi-pod = 2 pods.
A FUNCTION, not a module-level constant — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first jax use).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, as shard_map and jit in_shardings expect)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> jax.sharding.Mesh:
    """Tiny mesh over however many devices the host actually has (tests)."""
    return make_mesh(shape, axes)


# v5e-class hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link (~3 usable links/chip on a
N_ICI_LINKS = 3                 # 2D-torus v5e class part)
HBM_PER_CHIP = 16 * 2**30       # 16 GiB
