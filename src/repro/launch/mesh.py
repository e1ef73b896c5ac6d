"""Production mesh construction. TPU v5e pod targets:
  single pod : (16, 16)    = 256 chips, axes (data, model)
  multi-pod  : (2, 16, 16) = 512 chips, axes (pod, data, model)

Defined as functions (not module constants) so importing never touches jax
device state; the dry-run sets xla_force_host_platform_device_count FIRST.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline terms, EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
HBM_BYTES = 16 * 2**30          # 16 GiB per chip
ICI_BW = 50e9                   # bytes/s per link (~)


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto-typed (sharding propagates
    through the compiler rather than the type system)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int = 0, model: int = 1):
    """Small CPU mesh for tests (n devices must already exist)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
