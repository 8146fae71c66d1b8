"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 chips as (pod=2, data=16, model=16) — the ``pod``
axis is the DiLoCo worker boundary (slow inter-pod links carry only the
outer-step delta exchange).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run launcher must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, num_pods: int = 2):
    """Single pod: (16, 16).  Multi-pod: (num_pods, 16, 16) — the default 2
    pods = 512 chips is the required dry-run target; larger DiLoCo fleets
    (one worker per pod) reuse the same axes."""
    shape = (num_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (possibly fake) local devices exist —
    used by tests."""
    return _make_mesh((data, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e-class chip).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (intra-pod)
DCN_BW = 6.25e9               # bytes/s per device across pods (50 Gbit/s —
                              # the slow inter-pod boundary DiLoCo targets)
HBM_PER_CHIP = 16e9           # bytes
