"""Production mesh construction (TPU v5e pods).

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — "pod" is a
pure data-parallel (or pipeline, see parallel/pipeline.py) axis whose
collectives cross the inter-pod DCN/ICI boundary.

Functions, not module constants: importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis Auto: GSPMD propagates shardings
    from the parameter rules, which the model code is written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests on 1-8 CPU devices)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# -- hardware constants for the roofline (TPU v5e) --------------------------
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW_PER_LINK = 50e9          # bytes/s per link (~ per chip per direction)
VMEM_BYTES = 128 * 2**20 // 8   # ~16 MiB usable
HBM_BYTES = 16 * 2**30          # 16 GiB per chip
