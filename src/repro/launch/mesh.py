"""Production meshes (TPU v5e): one 256-chip pod, or 2 pods = 512 chips.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto.

    jax 0.9 makes Explicit axes by default; this code shards through GSPMD
    (``dist.sharding.param_specs`` + ``with_sharding_constraint``), which
    needs Auto axes — on Explicit ones the first gather of a replicated
    embedding by a sharded batch raises ``ShardingTypeError``.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small mesh for CI subprocess tests (needs >= data*model*max(pod,1) devices)."""
    if pod:
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
    "hbm_bytes": 16 * 2**30,     # capacity per chip
}
