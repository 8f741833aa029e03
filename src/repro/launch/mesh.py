"""Production mesh construction.

A function (not a module constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS *before* any jax init.
"""
from __future__ import annotations

from repro.runtime import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the same axis names (tests / smoke runs)."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware model used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
