"""Production mesh construction (TPU v5e target).

Single pod: 256 chips as (16, 16) with axes ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16), axes ("pod","data","model").

Defined as functions (never module-level constants) so importing this
module does not touch jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE any jax import.
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh, shard_map  # noqa: F401  (shard_map is
#                                    re-exported for mesh programs)

__all__ = ["make_production_mesh", "make_host_mesh", "make_cohort_mesh",
           "shard_map", "PEAK_FLOPS_BF16", "HBM_BW", "ICI_BW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))


def make_cohort_mesh(n_devices=None):
    """1-D ``("data",)`` mesh over the visible devices — the client-axis
    sharding domain of the mesh-sharded :class:`~repro.fl.cohort_engine.
    CohortEngine`.  ``n_devices`` caps the mesh to a leading subset of
    ``jax.devices()`` (forced-host-device CI sweeps use 1/2/4/8)."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devices):
        raise ValueError(f"n_devices={n} not in [1, {len(devices)}]")
    return make_mesh((n,), ("data",), devices=devices[:n])


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
