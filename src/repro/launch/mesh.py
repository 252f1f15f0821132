"""Production mesh factories.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before any jax import.

Topology (TPU v5e): one pod = 16x16 = 256 chips, axes (data, model);
multi-pod = 2 pods = 512 chips, axes (pod, data, model) where "pod" is
pure data parallelism over DCN (gradient all-reduce only — DESIGN.md §5).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``: the model's
    ``with_sharding_constraint`` calls (launch/sharding.py) may only name
    Auto axes, and jax defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes kept for code parity)."""
    return make_mesh((1, 1), ("data", "model"))


# Hardware constants used by the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip, one direction)
HBM_BYTES = 16 << 30              # 16 GB per chip
