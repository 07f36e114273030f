"""Vectorizable transcendentals for the fused ZO hot path.

XLA:CPU lowers ``jnp.sin`` to a scalar libm call per element, which makes
the sine activation a dominant cost of the stacked multi-perturbation PINN
sweep on the CPU.  ``fast_sin`` is the classic Cephes-style argument-
reduction + degree-7 minimax polynomial, built from mul/add/select
primitives that XLA vectorizes and fuses into neighbouring elementwise
work.  Max error ≈ 2 ulp of float32 over |x| ≲ 1e4 — within the FD-stencil
noise floor documented in DESIGN.md §Perf.  Selected by
``PINNConfig.use_fused_kernel``; the sequential photonic-realism path keeps
libm ``jnp.sin``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fast_sin"]

# π/2 split into exactly-representable f32 parts (extended-precision
# reduction: r = x − y·PIO2_1 − y·PIO2_2 − y·PIO2_3 stays accurate for the
# |y| ≲ 1e4 range these activations live in)
_PIO2_1 = 1.5703125
_PIO2_2 = 4.837512969970703e-04
_PIO2_3 = 7.549789948768648e-08
_TWO_OVER_PI = 0.6366197723675814

# Cephes sinf/cosf minimax coefficients on [-π/4, π/4]
_S1, _S2, _S3 = -1.6666654611e-1, 8.3321608736e-3, -1.9515295891e-4
_C1, _C2, _C3 = 4.166664568298827e-2, -1.388731625493765e-3, \
    2.443315711809948e-5


def fast_sin(x: jax.Array) -> jax.Array:
    """sin(x), vectorized: octant reduction + sin/cos polynomials."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    y = jnp.round(xf * _TWO_OVER_PI)
    r = xf - y * _PIO2_1
    r = r - y * _PIO2_2
    r = r - y * _PIO2_3
    q = y.astype(jnp.int32) & 3          # octant pair index
    r2 = r * r
    sin_p = r + r * r2 * (_S1 + r2 * (_S2 + r2 * _S3))
    cos_p = 1.0 - 0.5 * r2 + r2 * r2 * (_C1 + r2 * (_C2 + r2 * _C3))
    use_cos = (q & 1) == 1
    val = jnp.where(use_cos, cos_p, sin_p)
    return jnp.where(q >= 2, -val, val).astype(dtype)
