"""Meshes.

``make_mesh`` is the one mesh constructor of the repo; ``make_pe_mesh``
lays the list-ranking PE grid over the devices there are (one chip is
one PE). Functions (never module-level constants) so importing this
module never touches jax device state — callers set XLA_FLAGS before
any jax initialization.

Target for the LLM substrate: TPU v5e pods — 256 chips per pod arranged
(16, 16); multi-pod runs add a leading "pod" axis over the DCI.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

#: v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~ per-chip eff. for ring)
DCI_BW = 25e9                 # inter-pod bytes/s per chip (conservative)


def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types: inputs are placed with
    ``NamedSharding`` and the programs run under ``shard_map``, so no
    axis takes part in sharding-in-types (jax's Explicit default)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_pe_mesh(devices=None):
    """The ``("row", "col")`` PE grid over ``devices`` (default: all),
    as square as the count allows with row <= col: 1 -> 1x1, 4 -> 2x2,
    8 -> 2x4. Grid indirection routes over both axes (DESIGN.md §5)."""
    n = len(devices if devices is not None else jax.devices())
    rows = max(r for r in range(1, math.isqrt(n) + 1) if n % r == 0)
    return make_mesh((rows, n // rows), ("row", "col"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return make_mesh(shape, axes)
