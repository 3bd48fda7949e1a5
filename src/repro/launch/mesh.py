"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — DP across the
pod axis rides DCN; model parallelism stays inside the pod's ICI domain.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """Mesh over the local devices with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    return _mesh(tuple(shape), tuple(axes))


def make_data_mesh(n_devices=None):
    """1-D ('data',) mesh over ``n_devices`` (default: every local device).

    The mesh the batch-sharded sweep lane (``SweepPlan.shard``) and the
    multi-device CI lane run on — pure DP, no model axis.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    return _mesh((n_devices,), ("data",))


# v5e-class hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW_PER_LINK = 50e9       # B/s, ~4 links/chip in a 2D torus
ICI_LINKS = 4
HBM_BYTES = 16 * 2 ** 30     # v5e HBM capacity
