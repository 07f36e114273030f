"""High-dimensional heat equation with a closed-form Gaussian solution.

Terminal-value convention (same orientation as the HJB benchmark):

    ∂_t u + κ Δ_x u = 0,   u(x, 1) = exp(−‖x−c‖² / (4s)),
    x ∈ [0,1]^D, t ∈ [0,1],  c = ½·1,  s = D/4.

Running the heat kernel backward in τ = s + κ(1−t) gives the exact solution

    u(x, t) = (s / τ)^{D/2} · exp(−‖x−c‖² / (4 τ)),   τ = s + κ (1 − t),

a spreading Gaussian, FOR EVERY diffusivity κ — which is what makes heat the
cleanest coefficient family in the registry: one conditioned model can be
verified analytically at each sampled κ.  The width offset ``s = D/4``
scales with dimension so the κ=1 amplitude ratio between t=1 and t=0,
(1 + 1/s)^{−D/2} ≈ e^{−2}, is dimension-independent — u stays O(1) at any D
instead of vanishing like a normalized heat kernel would.

Ansatz: u = (1−t)·f + g(x) with g the terminal Gaussian — the terminal
condition is exact for every κ, so the training loss is the residual alone.

Conditioning (``kappa_range`` set): rows gain a trailing κ slot sampled
per point; the fixed ``kappa`` argument instead pins a single diffusivity
(a dedicated fixed-coefficient checkpoint).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import stein
from repro.pde import base


class HeatProblem(base.PDEProblem):
    """Backward heat equation u_t + κΔu = 0 with Gaussian terminal data."""

    time_dependent = True
    has_boundary_loss = False
    # u ∈ [e⁻²·e^{−D/16·…}, 1] is O(1); the residual is a pure sum of D FD
    # second differences, each carrying ~ε/h² = 1e-3 f32 rounding → the
    # mean-squared exact-solution residual sits near D·1e-6 ≲ 1e-3.  The
    # h²-truncation term is smaller (u⁗ ~ (4s)⁻² ≪ 1).  Conditioned rows
    # scale that floor by κ² ≤ 4 over the default range — still ≪ tol;
    # the registry smoke test asserts the declared-estimator floor too.
    residual_tol = 1e-2

    def __init__(self, space_dim: int = 20, margin: float = 0.02,
                 kappa: float = 1.0,
                 kappa_range: tuple[float, float] | None = None):
        self.space_dim = space_dim
        self.name = f"heat-{space_dim}d"
        self.margin = margin
        self.s = space_dim / 4.0
        self.center = 0.5
        self.kappa = float(kappa)
        if kappa_range is not None:
            self.coeff_spec = base.CoeffSpec(
                ("kappa",), (kappa_range[0],), (kappa_range[1],))
            self.name += "-kappa"
        # Backward heat on a box is only well-posed with spatial boundary
        # data: residual + terminal condition alone admit a family of
        # solutions, and a trained model drifts to one of the others (the
        # more so the larger κ).  The κ-family work exposed this, so every
        # non-legacy instance (conditioned, or a dedicated κ≠1 pin) trains
        # against closed-form Dirichlet faces; the legacy κ=1 problem keeps
        # its historical residual-only loss bit-for-bit.
        self.has_boundary_loss = (kappa_range is not None
                                  or self.kappa != 1.0)

    def _kappa(self, xt: jax.Array):
        """κ per row (conditioned) or the fixed scalar."""
        if self.coeff_spec is None:
            return self.kappa
        return xt[..., self.in_dim]

    def sample_collocation(self, key: jax.Array, n: int) -> jax.Array:
        return self._sample_with_coeffs(
            key, n, lambda k: base.uniform_box(k, n, self.in_dim,
                                               self.margin,
                                               1.0 - self.margin))

    def _terminal(self, x: jax.Array) -> jax.Array:
        """g(x) = exp(−‖x−c‖²/(4s)) — the t=1 slice of the exact solution."""
        q = jnp.sum((x - self.center) ** 2, axis=-1)
        return jnp.exp(-q / (4.0 * self.s))

    def ansatz(self, f: jax.Array, xt: jax.Array) -> jax.Array:
        """u = (1−t)·f + g(x) (terminal condition exact for every κ)."""
        D = self.space_dim
        x, t = xt[..., :D], xt[..., D]
        return (1.0 - t) * f + self._terminal(x)

    def boundary_batch(self, key: jax.Array, n: int):
        """n Dirichlet rows on the spatial faces of the box: one coordinate
        pinned to a face, t (and κ, when conditioned) sampled — targets are
        the closed-form solution, i.e. the boundary data of the well-posed
        problem, per coefficient instance."""
        D = self.space_dim
        kx, kt, kf, ks, kc = jax.random.split(key, 5)
        x = jax.random.uniform(kx, (n, D), minval=self.margin,
                               maxval=1.0 - self.margin)
        face = jax.random.randint(kf, (n,), 0, D)
        side = jax.random.randint(ks, (n,), 0, 2).astype(x.dtype)
        x = x.at[jnp.arange(n), face].set(side)
        t = jax.random.uniform(kt, (n, 1), minval=self.margin,
                               maxval=1.0 - self.margin)
        xt = jnp.concatenate([x, t], axis=-1)
        if self.coeff_spec is not None:
            xt = jnp.concatenate(
                [xt, self.coeff_spec.sample(kc, n).astype(xt.dtype)],
                axis=-1)
        return xt, self.exact_solution(xt)

    def spectral_carrier(self, rows: jax.Array, anchors: jax.Array):
        """β = g(x), the terminal Gaussian in the ansatz u = (1−t)·f + g.
        Smooth but sharply curved relative to the learned part, so
        differentiating it analytically (∂_i g = −(x_i−c)/(2s)·g,
        ∂²_i g = (−1/(2s) + (x_i−c)²/(4s²))·g, ∂_t g = 0) removes its
        contribution from the windowed-FFT error budget entirely."""
        D = self.space_dim
        beta = self._terminal(rows[..., :D])
        xa = anchors[..., :D] - self.center
        ga = self._terminal(anchors[..., :D])[..., None]
        grad_x = -xa / (2.0 * self.s) * ga
        hess_x = (-1.0 / (2.0 * self.s)
                  + xa * xa / (4.0 * self.s * self.s)) * ga
        zeros_t = jnp.zeros_like(anchors[..., D:D + 1])
        return (beta,
                jnp.concatenate([grad_x, zeros_t], axis=-1),
                jnp.concatenate([hess_x, zeros_t], axis=-1))

    def residual(self, est: stein.DerivativeEstimate,
                 xt: jax.Array) -> jax.Array:
        """residual = u_t + κ Δ_x u."""
        D = self.space_dim
        u_t = est.grad[..., D]
        lap = jnp.sum(est.hess_diag[..., :D], axis=-1)
        if self.coeff_spec is None and self.kappa == 1.0:
            return u_t + lap   # legacy path, bit-identical
        return u_t + self._kappa(xt) * lap

    def exact_solution(self, xt: jax.Array) -> jax.Array:
        D = self.space_dim
        x, t = xt[..., :D], xt[..., D]
        if self.coeff_spec is None and self.kappa == 1.0:
            tau = self.s + 1.0 - t   # legacy expression, bit-identical
        else:
            tau = self.s + self._kappa(xt) * (1.0 - t)
        q = jnp.sum((x - self.center) ** 2, axis=-1)
        return (self.s / tau) ** (self.space_dim / 2.0) \
            * jnp.exp(-q / (4.0 * tau))


@base.register("heat-10d")
def _heat_10d() -> HeatProblem:
    return HeatProblem(space_dim=10)


@base.register("heat-20d")
def _heat_20d() -> HeatProblem:
    return HeatProblem(space_dim=20)


@base.register("heat-10d-kappa")
def _heat_10d_kappa() -> HeatProblem:
    """Conditioned family: diffusivity κ ∈ [0.5, 2.0] as an input slot."""
    return HeatProblem(space_dim=10, kappa_range=(0.5, 2.0))
