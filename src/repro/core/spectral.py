"""Spectral (FFT-exact) derivative estimation — the third BP-free estimator.

``fd_estimate`` pays ``2A`` extra inferences per collocation point and
carries the 1/h² float32 noise floor; ``stein_estimate`` pays ``2S`` and
carries Monte-Carlo variance.  Following "Fourier Domain Physics Informed
Neural Network" (arXiv:2409.19895), this module instead samples u on small
per-axis LINE GRIDS through anchor points and recovers ∂_i u and ∂²_i u by
real FFT along each line:

    û_m = rfft(u on the M-point line along axis i),   k̃_m = 2π m / W
    ∂_i u  = irfft( i·k̃ · û )     (Nyquist mode zeroed — odd derivative)
    ∂²_i u = irfft( −k̃² · û )

exact for band-limited u — no truncation/rounding noise floor at all.  The
anchor sits exactly at line index ``M//2``, so all A partials are read off
at the anchor and the residual is evaluated there.  Inference bill per
loss evaluation: ``B·(A·(M−1) + 1)`` distinct rows (the anchor row is
shared by its A lines) vs FD's ``B·(2A+1)`` — with exact derivatives a
much smaller anchor batch carries the same training signal, which is where
the ≥3× inference cut comes from (702 vs 2300 rows a loss on the 10-dim
workloads, tests/test_spectral.py).

Domain periodization (``periodization=``):

  * ``"periodic"`` — u is periodic with period W along each active axis:
    plain rfft, EXACT (f32 roundoff) for trigonometric polynomials with
    max frequency < M/2 (property-tested in tests/test_properties.py).
  * ``"window"`` — u lives on a non-periodic box: the line is a straight
    segment of extent W centered at the anchor (the network is evaluated
    slightly outside the box — an MLP extrapolates smoothly; residuals are
    only ever read AT the anchor).  Two standard trend-removal steps make
    the segment FFT-ready: (1) the least-squares QUADRATIC through the
    samples is subtracted and differentiated analytically — the rfft sees
    only the cubic-and-up residue, so locally-quadratic u is exact by
    construction; (2) the residue is multiplied by a C^∞ bump window w
    with w ≡ 1 on a plateau around the anchor and w → 0 at the segment
    ends, and since w' = w'' = 0 at the anchor, the windowed residue's
    spectral derivatives there are the residue's own.  The documented
    floor at the defaults (plateau 0.25) is ~3e-2 absolute worst-case on
    O(1) smooth functions at M = 8, tightening to ~2e-3 by M = 16
    (WINDOWED_FLOOR below is the M ≥ 8 bound) — the same order as FD's
    h²-truncation + ε/h² rounding floor at h = 1e-2, with 4× fewer
    samples per axis than a matched-accuracy stencil refinement.

Non-smooth closed-form ansatz terms (HJB's ‖x‖₁ kink at the domain edge)
would poison the windowed FFT; problems remove them via the additive
``spectral_carrier`` hook (repro.pde.base): the FFT sees only the smooth
learned part u − β and β's exact derivatives are added back analytically.

``spectral_estimate`` composes the pieces for a callable f; the PINN loss
paths (repro.core.pinn) use the row-level helpers directly so the stacked
multi-perturbation evaluator runs ONE batched forward over the line rows.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stein

__all__ = ["line_offsets", "spectral_window", "spectral_line_rows",
           "line_vals_from_rows_vals", "spectral_derivs",
           "spectral_derivs_ref", "estimate_from_line_vals",
           "spectral_estimate", "num_spectral_inferences",
           "WINDOWED_FLOOR"]

# documented accuracy floor of the windowed (detrend + taper) path on
# O(1)-scale smooth non-periodic functions at the default plateau and any
# M ≥ 8: max |error| of grad and hess_diag at the anchor (see module
# docstring; asserted by tests/test_spectral.py and the hypothesis
# property suite).  Comparable to fd_estimate's documented h² floor.
WINDOWED_FLOOR = 3e-2


def num_spectral_inferences(n_anchors: int, n_active: int,
                            points: int) -> int:
    """Distinct model rows per spectral loss evaluation: the anchor row is
    shared by all A of its lines, so B anchors cost B·(A·(M−1)+1) — vs
    FD's B·(2A+1) (``stein.num_fd_inferences``)."""
    return n_anchors * (n_active * (points - 1) + 1)


def line_offsets(points: int, extent: float) -> jax.Array:
    """(M,) signed offsets along a line with the anchor at index M//2 and
    uniform spacing extent/M (one FFT period of length ``extent``)."""
    c = points // 2
    return (jnp.arange(points) - c) * (extent / points)


@functools.lru_cache(maxsize=None)
def _window_np(points: int, plateau: float) -> np.ndarray:
    """C^∞ bump window over line indices: 1 on the central ``plateau``
    fraction, smooth exp-step taper to 0 at the segment ends.  Cached —
    it only depends on (M, plateau)."""
    c = points // 2
    theta = np.abs((np.arange(points) - c) / points)   # ∈ [0, 0.5)
    r0, r1 = 0.5 * plateau, 0.5
    t = np.clip((theta - r0) / (r1 - r0), 0.0, 1.0)

    def h(y):
        out = np.zeros_like(y)
        pos = y > 0
        out[pos] = np.exp(-1.0 / y[pos])
        return out

    w = h(1.0 - t) / (h(1.0 - t) + h(t))
    return w.astype(np.float32)


def spectral_window(points: int, plateau: float = 0.25) -> jax.Array:
    """The ``"window"`` periodization taper (see ``_window_np``)."""
    return jnp.asarray(_window_np(points, float(plateau)))


@functools.lru_cache(maxsize=None)
def _detrend_basis(points: int, extent: float) -> tuple:
    """(V (M, 3), pinv(V) (3, M)) for the least-squares quadratic
    a + bθ + cθ² over the line offsets θ_j — the trend removed (and
    differentiated analytically: ∂ = b, ∂² = 2c) before the rfft."""
    c = points // 2
    theta = (np.arange(points) - c) * (extent / points)
    V = np.stack([np.ones(points), theta, theta * theta], axis=1)
    return (V.astype(np.float32),
            np.linalg.pinv(V).astype(np.float32))


def spectral_line_rows(x: jax.Array, n_active: int, points: int,
                       extent: float) -> jax.Array:
    """Deduped line-grid rows for a batch of anchors.

    x: (B, D) anchor rows (trailing D − n_active coefficient slots are
    never shifted).  Returns (B·(A·(M−1)+1), D): the B anchor rows first,
    then the per-axis line points excluding the (shared) center index, in
    (anchor, axis, offset) order — the layout
    ``line_vals_from_rows_vals`` inverts.
    """
    B, D = x.shape
    A, M = n_active, points
    c = M // 2
    off = line_offsets(M, extent).astype(x.dtype)
    off_rest = jnp.concatenate([off[:c], off[c + 1:]])          # (M-1,)
    eye = jnp.eye(A, D, dtype=x.dtype)                          # (A, D)
    rest = (x[:, None, None, :]
            + eye[None, :, None, :] * off_rest[None, None, :, None])
    return jnp.concatenate([x, rest.reshape(B * A * (M - 1), D)], axis=0)


def line_vals_from_rows_vals(vals: jax.Array, n_anchors: int,
                             n_active: int, points: int) -> jax.Array:
    """Invert the ``spectral_line_rows`` layout: values over the deduped
    rows (..., B·(A·(M−1)+1)) → full line values (..., B, A, M) with the
    shared anchor value re-inserted at the center index of every line."""
    B, A, M = n_anchors, n_active, points
    c = M // 2
    u0 = vals[..., :B]
    rest = vals[..., B:].reshape(vals.shape[:-1] + (B, A, M - 1))
    center = jnp.broadcast_to(u0[..., :, None, None],
                              rest.shape[:-1] + (1,))
    return jnp.concatenate([rest[..., :c], center, rest[..., c:]], axis=-1)


def _freqs(points: int, extent: float) -> jax.Array:
    """Angular frequencies k̃_m = 2π m / extent for rfft of length M."""
    return (2.0 * jnp.pi / extent) * jnp.arange(points // 2 + 1,
                                                dtype=jnp.float32)


def spectral_derivs(line_vals: jax.Array, extent: float,
                    periodization="window",
                    plateau: float = 0.25) -> tuple:
    """(∂u, ∂²u) at the anchor (center index) of each line.

    line_vals: (..., M) u-samples along lines (any leading axes: batch,
    axis, SPSA-perturbation stack).  ``"periodic"`` differentiates the
    raw samples; ``"window"`` removes the least-squares quadratic trend
    (differentiated analytically — locally-quadratic u is exact) and
    applies the C^∞ taper to the residue first (exact at the anchor:
    w = 1, w' = w'' = 0 there).

    ``periodization`` may also be a PER-AXIS tuple — e.g. ns-2d's
    ("periodic", "periodic", "window") for a periodic box with a
    non-periodic time axis.  Mixed tuples require the lines' axis
    dimension at position −2 (the (..., B, A, M) layout of
    ``line_vals_from_rows_vals``): entry ``a`` periodizes the lines of
    active axis ``a``.  A uniform tuple collapses to its scalar form.
    """
    if not isinstance(periodization, str):
        ps = tuple(periodization)
        if not ps:
            raise ValueError("empty periodization tuple")
        if all(p == ps[0] for p in ps):
            return spectral_derivs(line_vals, extent, ps[0], plateau)
        if line_vals.ndim < 2 or line_vals.shape[-2] != len(ps):
            raise ValueError(
                f"per-axis periodization of {len(ps)} entries needs lines "
                f"shaped (..., {len(ps)}, M); got {line_vals.shape}")
        per_axis = [spectral_derivs(line_vals[..., a, :], extent, p, plateau)
                    for a, p in enumerate(ps)]
        return (jnp.stack([d1 for d1, _ in per_axis], axis=-1),
                jnp.stack([d2 for _, d2 in per_axis], axis=-1))
    M = line_vals.shape[-1]
    c = M // 2
    trend1 = trend2 = None
    if periodization == "window":
        V, P = _detrend_basis(M, float(extent))
        coef = jnp.einsum("km,...m->...k",
                          jnp.asarray(P, dtype=line_vals.dtype), line_vals)
        trend = jnp.einsum("...k,mk->...m", coef,
                           jnp.asarray(V, dtype=line_vals.dtype))
        trend1, trend2 = coef[..., 1], 2.0 * coef[..., 2]
        w = spectral_window(M, plateau).astype(line_vals.dtype)
        v = (line_vals - trend) * w
    elif periodization == "periodic":
        v = line_vals
    else:
        raise ValueError(f"unknown periodization {periodization!r}; "
                         "expected 'window' or 'periodic'")
    F = jnp.fft.rfft(v, axis=-1)
    k = _freqs(M, extent)
    k1 = k if M % 2 else k.at[-1].set(0.0)   # Nyquist: odd derivative → 0
    d1 = jnp.fft.irfft(F * (1j * k1), n=M, axis=-1)[..., c]
    d2 = jnp.fft.irfft(F * -(k * k), n=M, axis=-1)[..., c]
    if trend1 is not None:
        d1 = d1 + trend1
        d2 = d2 + trend2
    return d1.astype(line_vals.dtype), d2.astype(line_vals.dtype)


def spectral_derivs_ref(line_vals, extent: float,
                        periodization="window",
                        plateau: float = 0.25) -> tuple:
    """Naive O(M²) DFT oracle for ``spectral_derivs`` (numpy float64,
    per-mode cos/sin sums, explicit lstsq detrend) — the reference the
    vectorized rfft path is tested against, mirroring the kernels'
    jnp-oracle discipline.  Per-axis periodization tuples loop the axes
    at position −2, matching ``spectral_derivs``."""
    if not isinstance(periodization, str):
        ps = tuple(periodization)
        v = np.asarray(line_vals, dtype=np.float64)
        if all(p == ps[0] for p in ps):
            return spectral_derivs_ref(line_vals, extent, ps[0], plateau)
        if v.ndim < 2 or v.shape[-2] != len(ps):
            raise ValueError(
                f"per-axis periodization of {len(ps)} entries needs lines "
                f"shaped (..., {len(ps)}, M); got {v.shape}")
        per_axis = [spectral_derivs_ref(v[..., a, :], extent, p, plateau)
                    for a, p in enumerate(ps)]
        return (np.stack([d1 for d1, _ in per_axis], axis=-1),
                np.stack([d2 for _, d2 in per_axis], axis=-1))
    v = np.asarray(line_vals, dtype=np.float64)
    M = v.shape[-1]
    c = M // 2
    d1 = np.zeros(v.shape[:-1])
    d2 = np.zeros(v.shape[:-1])
    if periodization == "window":
        theta = (np.arange(M) - c) * (extent / M)
        V = np.stack([np.ones(M), theta, theta * theta], axis=1)
        coef = v @ np.linalg.pinv(V).T
        v = (v - coef @ V.T) * _window_np(M, plateau).astype(np.float64)
        d1 += coef[..., 1]
        d2 += 2.0 * coef[..., 2]
    elif periodization != "periodic":
        raise ValueError(periodization)
    j = np.arange(M)
    for m in range(M // 2 + 1):
        km = 2.0 * np.pi * m / extent
        scale = (1.0 if m in (0, M - m) else 2.0) / M
        cm = np.sum(v * np.cos(2 * np.pi * m * j / M), axis=-1) * scale
        sm = np.sum(v * np.sin(2 * np.pi * m * j / M), axis=-1) * scale
        cos_c = np.cos(2 * np.pi * m * c / M)
        sin_c = np.sin(2 * np.pi * m * c / M)
        if not (M % 2 == 0 and m == M // 2):   # Nyquist odd derivative → 0
            d1 += km * (-cm * sin_c + sm * cos_c)
        d2 += -km * km * (cm * cos_c + sm * sin_c)
    return d1, d2


def estimate_from_line_vals(vals: jax.Array, anchors: jax.Array,
                            n_active: int, points: int, extent: float,
                            periodization="window",
                            carrier=None) -> stein.DerivativeEstimate:
    """Assemble a ``DerivativeEstimate`` from u-values over the deduped
    line rows — the entry point the PINN loss paths share with
    ``spectral_estimate`` (they evaluate u themselves through the stacked
    multi-perturbation forward).

    vals: (..., R) values over ``spectral_line_rows(anchors, ...)`` rows
    (any leading axes — e.g. the SPSA perturbation stack P).  ``carrier``
    is either None, a ``(β(rows), ∇β(anchors), diag∇²β(anchors))`` triple,
    or a callable ``rows, anchors -> triple | None`` (the
    ``PDEProblem.spectral_carrier`` hook; a None return means "no
    closed-form part" and is treated like a missing carrier).  Returned
    leaves are (..., B, A) — the unified ``DerivativeEstimate`` width
    contract, with u the TRUE u at the anchors (carrier included).
    """
    B = anchors.shape[0]
    u0 = vals[..., :B]
    if callable(carrier):
        rows = spectral_line_rows(anchors, n_active, points, extent)
        carrier = carrier(rows, anchors)
    if carrier is not None:
        beta, bgrad, bhess = carrier
        vals = vals - beta
    lines = line_vals_from_rows_vals(vals, B, n_active, points)
    grad, hess = spectral_derivs(lines, extent, periodization)
    if carrier is not None:
        grad = grad + bgrad
        hess = hess + bhess
    return stein.DerivativeEstimate(u=u0, grad=grad, hess_diag=hess)


def spectral_estimate(f: Callable[[jax.Array], jax.Array], x: jax.Array,
                      points: int = 32, extent: float = 1.0,
                      periodization="window",
                      n_active: int | None = None,
                      carrier=None) -> stein.DerivativeEstimate:
    """FFT-exact derivatives of ``f`` at the anchors ``x`` via ONE batched
    forward over the per-axis line grids.

    x: (B, D) anchors.  ``n_active`` restricts the differentiated
    coordinates to the first A columns (A = D when None) — coefficient
    slots are never shifted.  ``carrier`` optionally supplies the
    closed-form additive part β of f (see ``PDEProblem.spectral_carrier``
    and ``estimate_from_line_vals``) whose exact derivatives are added
    back after the FFT differentiates the smooth remainder f − β.
    Returned leaves are (B, A).
    """
    A = x.shape[1] if n_active is None else n_active
    rows = spectral_line_rows(x, A, points, extent)
    if callable(carrier):
        carrier = carrier(rows, x)
    return estimate_from_line_vals(f(rows), x, A, points, extent,
                                   periodization, carrier)
