"""PDE problem interface + registry — the workload layer of the solver stack.

The paper's framework (tensorized, BP-free PINN training) is
problem-agnostic: the model (``repro.core.pinn.TensorPinn``), the BP-free
derivative estimators (``repro.core.stein``) and the ZO optimizer
(``repro.core.zoo``) never need to know which PDE they are solving.  A
``PDEProblem`` packages everything that IS problem-specific:

  * the collocation domain and sampler,
  * the hard-constraint ansatz transform ``u = T(f, xt)`` that bakes the
    terminal/initial condition into the network output,
  * the pointwise residual as a function of a ``DerivativeEstimate``
    (paper Eq. 4's L_r integrand),
  * the composite loss as a tuple of ``LossTerm``s (``loss_terms()``):
    one collocation (residual) term plus any number of boundary / data
    terms, each with its own sampler, target and scale weight — paper
    Eq. 4's L = L_r + λ·L_b generalized to L = Σ_k w_k·L_k.  The legacy
    ``has_boundary_loss``/``bc_weight``/``boundary_batch`` trio is kept
    as a deprecated shim that the default ``loss_terms()`` synthesizes
    terms from,
  * an optional ``Domain`` normalization layer: problems on a non-unit
    box declare it once here, sample collocation in UNIT-box coordinates,
    and the loss engine folds the analytic Jacobian factors into the
    residual via ``scale_estimate`` — FD/spectral steps are taken in
    normalized coordinates, the PDE is stated in raw ones,
  * an optional closed-form exact solution (validation MSE + tests).

Contract for the fused multi-perturbation ZO hot path (DESIGN.md §PDE):
``ansatz`` and ``residual`` must be pure jnp functions that broadcast over
arbitrary *leading* axes of the network values ``f`` / the estimate leaves —
the stacked evaluator feeds them ``(P, ...)``-shaped values for all P SPSA
perturbations at once, and the FD stencil transform feeds ``(2·Din+1, B)``
values against ``(2·Din+1, B, in_dim)`` points.  Problems that satisfy this
get the densify-once / stacked-TT-contraction / shared-stencil path for
free; nothing else about the kernel plumbing is problem-specific.

Register with the module-level decorator::

    @register("heat-20d")
    def _make() -> PDEProblem:
        return HeatProblem(space_dim=20)

and resolve by name: ``get_problem("heat-20d")``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stein

__all__ = ["CoeffSpec", "Domain", "LossTerm", "PDEProblem", "register",
           "get_problem", "available", "fd_stencil_points",
           "estimate_from_u_stencil", "estimate_for_problem"]


# ------------------------------------------------------ domain normalization

@dataclasses.dataclass(frozen=True)
class Domain:
    """Axis-aligned box [lo, hi]^D mapped to the unit box at the registry
    boundary.

    A problem that declares a ``Domain`` samples collocation/boundary/data
    rows in UNIT-box coordinates z = (x − lo) / (hi − lo): the network,
    the FD stencils and the spectral line grids all operate on z (uniform
    O(1) inputs, one shared step/extent convention across problems), while
    the PDE residual is stated in raw coordinates x.  The chain rule is a
    pure diagonal rescale — ∂_x = ∂_z / s, ∂²_x = ∂²_z / s² with
    s = hi − lo per axis — which ``PDEProblem.scale_estimate`` folds into
    every ``DerivativeEstimate`` before ``residual`` sees it.  Problems
    with ``domain = None`` (all pre-existing ones) keep raw rows and the
    identity scaling: that path is bit-identical to the pre-Domain stack.
    """

    lo: tuple
    hi: tuple

    def __post_init__(self):
        object.__setattr__(self, "lo", tuple(float(v) for v in self.lo))
        object.__setattr__(self, "hi", tuple(float(v) for v in self.hi))
        if len(self.lo) != len(self.hi):
            raise ValueError("Domain lo/hi length mismatch")
        if not self.lo:
            raise ValueError("Domain needs at least one axis")
        for a, b in zip(self.lo, self.hi):
            if not a < b:
                raise ValueError(f"Domain axis needs lo < hi, got [{a}, {b}]")

    @property
    def dim(self) -> int:
        return len(self.lo)

    @property
    def scales(self) -> np.ndarray:
        """(D,) per-axis Jacobian factors s = hi − lo of x = lo + s·z."""
        return np.asarray(self.hi, dtype=np.float32) \
            - np.asarray(self.lo, dtype=np.float32)

    @property
    def is_unit(self) -> bool:
        return all(a == 0.0 and b == 1.0 for a, b in zip(self.lo, self.hi))

    def from_unit(self, z: jax.Array) -> jax.Array:
        """Unit-box rows (..., ≥D) → raw coordinates on the first D columns
        (trailing coefficient slots pass through untouched)."""
        lo = jnp.asarray(self.lo, dtype=z.dtype)
        s = jnp.asarray(self.scales, dtype=z.dtype)
        head = lo + s * z[..., :self.dim]
        return jnp.concatenate([head, z[..., self.dim:]], axis=-1) \
            if z.shape[-1] > self.dim else head

    def to_unit(self, x: jax.Array) -> jax.Array:
        """Inverse of ``from_unit``: raw rows → unit-box coordinates."""
        lo = jnp.asarray(self.lo, dtype=x.dtype)
        s = jnp.asarray(self.scales, dtype=x.dtype)
        head = (x[..., :self.dim] - lo) / s
        return jnp.concatenate([head, x[..., self.dim:]], axis=-1) \
            if x.shape[-1] > self.dim else head


# ------------------------------------------------------------ composite loss

_TERM_KINDS = ("collocation", "boundary", "data")


@dataclasses.dataclass(frozen=True)
class LossTerm:
    """One weighted term of the composite PINN loss L = Σ_k w_k·L_k.

    ``kind`` fixes the assembly the loss engine (repro.core.pinn) applies:

      * ``"collocation"`` — the PDE residual term: ``sample(key, n)``
        draws interior rows and L_k = mean(residual²) through the
        problem's derivative estimator.  Exactly one per problem.
      * ``"boundary"`` — pointwise match on sampled boundary/initial rows:
        ``sample(key, n) -> (xb, ub)`` and L_k = mean((u(xb) − ub)²)
        (paper Eq. 4's L_b).
      * ``"data"`` — same pointwise-match assembly on measured samples
        ``(x_d, u_d)`` anywhere in the domain — the data-fitting term of
        data-assimilating PINNs.  Kept as a distinct kind because the
        rows mean something different (noisy observations, not exact
        constraints) even though the math coincides.

    ``weight`` is the term's scale w_k; ``sample`` is a counter-keyed
    ``(key, n) -> batch`` sampler the trainer/data pipeline drives.
    """

    name: str
    kind: str
    weight: float = 1.0
    sample: Callable | None = None

    def __post_init__(self):
        if self.kind not in _TERM_KINDS:
            raise ValueError(f"unknown LossTerm kind {self.kind!r}; "
                             f"expected one of {_TERM_KINDS}")
        object.__setattr__(self, "weight", float(self.weight))


# ------------------------------------------------------- coefficient families

@dataclasses.dataclass(frozen=True)
class CoeffSpec:
    """Named PDE-coefficient vector with sampling ranges.

    A coefficient-conditioned problem (``PDEProblem.coeff_spec`` set)
    operates on *augmented rows* of width ``net_dim = in_dim + n``: the
    physical point first, then the coefficient values in ``names`` order,
    in RAW units (the model normalizes them to [0,1] input slots
    internally).  ``sample_collocation`` appends a fresh per-point draw,
    so the stacked evaluator, the FD stencil machinery, the serving slot
    pool and the stencil cache all see coefficients as ordinary input
    columns — perturbations × coefficients is just perturbations × rows.

    ``dist`` is ``"uniform"`` or ``"loguniform"`` (log-uniform needs
    strictly positive ranges — rates/volatilities/diffusivities).
    """

    names: tuple
    lo: tuple
    hi: tuple
    dist: str = "uniform"

    def __post_init__(self):
        object.__setattr__(self, "names", tuple(self.names))
        object.__setattr__(self, "lo", tuple(float(v) for v in self.lo))
        object.__setattr__(self, "hi", tuple(float(v) for v in self.hi))
        if not (len(self.names) == len(self.lo) == len(self.hi)):
            raise ValueError("names/lo/hi length mismatch")
        if not self.names:
            raise ValueError("CoeffSpec needs at least one coefficient")
        if self.dist not in ("uniform", "loguniform"):
            raise ValueError(f"unknown coefficient dist {self.dist!r}")
        for nm, a, b in zip(self.names, self.lo, self.hi):
            if not a < b:
                raise ValueError(f"coefficient {nm!r}: need lo < hi, "
                                 f"got [{a}, {b}]")
            if self.dist == "loguniform" and a <= 0.0:
                raise ValueError(f"coefficient {nm!r}: loguniform needs "
                                 f"lo > 0, got {a}")

    @property
    def n(self) -> int:
        return len(self.names)

    def sample(self, key: jax.Array, n: int) -> jax.Array:
        """(n, K) coefficient draws in RAW units."""
        lo = jnp.asarray(self.lo)
        hi = jnp.asarray(self.hi)
        u = jax.random.uniform(key, (n, self.n))
        if self.dist == "loguniform":
            return jnp.exp(jnp.log(lo) + u * (jnp.log(hi) - jnp.log(lo)))
        return lo + u * (hi - lo)

    def normalize(self, c: jax.Array) -> jax.Array:
        """Raw units → [0,1] network input slots (log-space for
        loguniform, so the net sees the sampling measure uniformly)."""
        lo = jnp.asarray(self.lo, dtype=c.dtype)
        hi = jnp.asarray(self.hi, dtype=c.dtype)
        if self.dist == "loguniform":
            return ((jnp.log(c) - jnp.log(lo))
                    / (jnp.log(hi) - jnp.log(lo)))
        return (c - lo) / (hi - lo)

    def defaults(self) -> np.ndarray:
        """(K,) mid-range coefficients (geometric mid for loguniform)."""
        lo, hi = np.asarray(self.lo), np.asarray(self.hi)
        if self.dist == "loguniform":
            return np.sqrt(lo * hi)
        return 0.5 * (lo + hi)

    def check_in_range(self, c, rtol: float = 1e-6) -> None:
        """Raise ValueError on a wrong-arity or out-of-range coefficient
        vector (numpy-friendly: used at the serving boundary, where
        silent extrapolation outside the trained range must be an
        error, not a quietly wrong answer)."""
        c = np.asarray(c, dtype=np.float64).reshape(-1)
        if c.shape[0] != self.n:
            raise ValueError(
                f"expected {self.n} coefficient(s) ({', '.join(self.names)}),"
                f" got {c.shape[0]}")
        lo, hi = np.asarray(self.lo), np.asarray(self.hi)
        slack = rtol * (hi - lo)
        bad = (c < lo - slack) | (c > hi + slack)
        if bad.any():
            msgs = [f"{nm}={v:g} outside trained range [{a:g}, {b:g}]"
                    for nm, v, a, b, m in
                    zip(self.names, c, lo, hi, bad) if m]
            raise ValueError("; ".join(msgs))

    def with_ranges(self, overrides: dict, dist: str | None = None
                    ) -> "CoeffSpec":
        """New spec with ``{name: (lo, hi)}`` range overrides applied."""
        unknown = set(overrides) - set(self.names)
        if unknown:
            raise ValueError(f"unknown coefficient(s) {sorted(unknown)}; "
                             f"this family has {list(self.names)}")
        lo = list(self.lo)
        hi = list(self.hi)
        for nm, (a, b) in overrides.items():
            i = self.names.index(nm)
            lo[i], hi[i] = float(a), float(b)
        return CoeffSpec(self.names, tuple(lo), tuple(hi),
                         self.dist if dist is None else dist)

    def to_meta(self) -> dict:
        return {"names": list(self.names), "lo": list(self.lo),
                "hi": list(self.hi), "dist": self.dist}

    @staticmethod
    def from_meta(meta: dict) -> "CoeffSpec":
        return CoeffSpec(tuple(meta["names"]), tuple(meta["lo"]),
                         tuple(meta["hi"]), meta.get("dist", "uniform"))


class PDEProblem:
    """Base class: one PDE workload for the tensorized BP-free PINN stack.

    Subclasses set the class/instance attributes and implement the four
    methods below.  ``residual_tol`` documents the problem's estimator
    noise floor: the mean-squared residual of the *exact* solution under
    BOTH the float32 central-difference estimator at ``fd_step``
    (truncation h²·u⁗/12 plus rounding ε·|u|/h², summed over the
    Laplacian) AND the problem's own declared ``estimator`` — the registry
    smoke test asserts it for every problem via ``estimate_for_problem``.
    """

    name: str = ""
    space_dim: int = 0
    time_dependent: bool = True   # input is (x, t); False → input is x only
    # Deprecated trio (pre-loss-term API): ``loss_terms()`` below
    # synthesizes a "boundary"-kind term from it, so existing problems and
    # callers keep working bit-identically.  New problems should override
    # ``loss_terms()`` (or the has_*/weight attrs) instead.
    has_boundary_loss: bool = False
    bc_weight: float = 1.0        # λ in L = L_r + λ·L_b (paper Eq. 4)
    # data-fitting term (kind="data"): noisy/measured samples of u fitted
    # by the same pointwise-match assembly as the boundary term
    has_data_loss: bool = False
    data_weight: float = 1.0
    fd_step: float = 1e-2         # recommended FD step for this problem
    residual_tol: float = 5e-2    # documented FD noise floor (see above)
    coeff_spec: CoeffSpec | None = None  # set → coefficient-conditioned
    domain: Domain | None = None  # set → samplers emit UNIT-box rows and
    #                               the loss engine folds the Jacobian
    #                               factors into every DerivativeEstimate
    #                               (None keeps raw rows + identity scale —
    #                               bit-identical legacy path)
    _term_weights: dict = {}      # per-instance overrides, set_term_weights

    # Per-problem derivative-estimator choice (repro.core.pinn resolves
    # PINNConfig.deriv == "auto" to this; every shipped problem keeps
    # "fd" so pre-PR trajectories stay bit-identical).  The spectral
    # estimator samples per-axis line grids of ``spectral_points`` points
    # spanning ``spectral_extent`` in each active coordinate and recovers
    # derivatives by rfft; ``spectral_periodization`` picks how a
    # non-periodic box is made FFT-ready ("window" = C^∞ taper of
    # u − u(anchor) on an unwrapped line segment, "periodic" = raw rfft
    # for genuinely periodic solutions; a per-axis TUPLE mixes the two —
    # e.g. ns-2d's periodic space × windowed time).  See repro.core.spectral.
    estimator: str = "fd"                 # "fd" | "stein" | "spectral"
    spectral_points: int = 16             # line-grid size M (per axis)
    spectral_extent: float = 1.0          # line length W (one FFT period)
    spectral_periodization: str | tuple = "window"

    @property
    def in_dim(self) -> int:
        """Physical input width (x [, t]) — FD stencils differentiate
        exactly these coordinates, never the coefficient slots."""
        return self.space_dim + (1 if self.time_dependent else 0)

    @property
    def n_coeffs(self) -> int:
        return 0 if self.coeff_spec is None else self.coeff_spec.n

    @property
    def net_dim(self) -> int:
        """Row width the network consumes: in_dim + n_coeffs.  Every
        point-shaped array in the stack (collocation batches, stencils,
        serving slots, cache keys) uses rows of this width."""
        return self.in_dim + self.n_coeffs

    def split_coeffs(self, xt: jax.Array):
        """(..., net_dim) rows → ((..., in_dim) points, (..., K) coeffs)."""
        return xt[..., :self.in_dim], xt[..., self.in_dim:self.net_dim]

    def attach_coeffs(self, pts: jax.Array, coeffs) -> jax.Array:
        """(n, in_dim) points + one (K,) coefficient vector → (n, net_dim)
        augmented rows (the serving path: one scenario per request)."""
        if self.coeff_spec is None:
            return pts
        c = jnp.broadcast_to(
            jnp.asarray(coeffs, dtype=pts.dtype).reshape(-1),
            (pts.shape[0], self.n_coeffs))
        return jnp.concatenate([pts, c], axis=-1)

    def _sample_with_coeffs(self, key: jax.Array, n: int,
                            point_sampler) -> jax.Array:
        """Shared sampler plumbing: unconditioned problems keep the
        legacy unsplit-key draw (bit-identical to pre-conditioning
        checkpoints); conditioned problems split the key and append a
        fresh per-point coefficient draw."""
        if self.coeff_spec is None:
            return point_sampler(key)
        kx, kc = jax.random.split(key)
        pts = point_sampler(kx)
        return jnp.concatenate(
            [pts, self.coeff_spec.sample(kc, n).astype(pts.dtype)], axis=-1)

    # ------------------------------------------------------------- interface
    def sample_collocation(self, key: jax.Array, n: int) -> jax.Array:
        """(n, in_dim) interior points, margined so FD stencils stay inside
        the domain (and away from any kinks of the ansatz)."""
        raise NotImplementedError

    def ansatz(self, f: jax.Array, xt: jax.Array) -> jax.Array:
        """Hard-constraint transform u = T(f, xt).

        ``xt``: (..., in_dim) points; ``f``: network values broadcastable
        against ``xt[..., 0]`` — possibly with EXTRA leading axes (the
        stacked perturbation axis P).  Must be elementwise-cheap pure jnp.
        """
        raise NotImplementedError

    def residual(self, est: stein.DerivativeEstimate,
                 xt: jax.Array) -> jax.Array:
        """Pointwise PDE residual (B,) from a derivative estimate of u."""
        raise NotImplementedError

    def boundary_batch(self, key: jax.Array, n: int):
        """(xb, ub) boundary points + target values for L_b, or None.

        Deprecated entry point (use ``loss_terms()``): only meaningful
        when ``has_boundary_loss``; the trainer samples a fresh batch per
        step and the loss adds ``bc_weight · mean((u(xb) − ub)²)``.
        """
        return None

    def data_batch(self, key: jax.Array, n: int):
        """(x_d, u_d) measured/observed sample rows + values for the
        data-fitting term, or None.  Only meaningful when
        ``has_data_loss``; must be deterministic per key (noise drawn
        from the key), so the counter-based data pipeline replays the
        same observations on restart."""
        return None

    # ------------------------------------------------------ composite loss
    def loss_terms(self) -> tuple:
        """The problem's composite loss as ``LossTerm``s, in evaluation
        order: the collocation (residual) term first, then any boundary /
        data terms.  The default synthesizes terms from the deprecated
        ``has_boundary_loss``/``bc_weight``/``boundary_batch`` trio and
        the data hooks, so legacy problems get the engine for free;
        problems with richer structure override this (and should route
        the result through ``_apply_term_weights`` so train-time
        ``set_term_weights`` overrides keep working)."""
        terms = [LossTerm("residual", "collocation", 1.0,
                          self.sample_collocation)]
        if self.has_boundary_loss:
            terms.append(LossTerm("boundary", "boundary", self.bc_weight,
                                  self.boundary_batch))
        if self.has_data_loss:
            terms.append(LossTerm("data", "data", self.data_weight,
                                  self.data_batch))
        return self._apply_term_weights(terms)

    def _apply_term_weights(self, terms) -> tuple:
        """Apply per-instance ``set_term_weights`` overrides to a term
        list — the shared tail of every ``loss_terms`` implementation."""
        ov = self._term_weights
        if ov:
            terms = [dataclasses.replace(t, weight=ov.get(t.name, t.weight))
                     for t in terms]
        return tuple(terms)

    def set_term_weights(self, weights: dict) -> None:
        """Override term weights by name at runtime (``--term-weight``):
        unknown names raise.  Overrides are per-instance and serialized
        into checkpoint meta (``term_weights()``), so serving/validation
        reconstruct the trained loss exactly."""
        known = {t.name for t in self.loss_terms()}
        unknown = set(weights) - known
        if unknown:
            raise ValueError(f"unknown loss term(s) {sorted(unknown)}; "
                             f"{self.name or type(self).__name__} has "
                             f"{sorted(known)}")
        merged = dict(self._term_weights)
        merged.update({k: float(v) for k, v in weights.items()})
        self._term_weights = merged

    def term_weights(self) -> dict:
        """Effective ``{name: weight}`` of ``loss_terms()`` — the
        checkpoint-meta form (overrides applied)."""
        return {t.name: t.weight for t in self.loss_terms()}

    # ------------------------------------------------- domain normalization
    def scale_estimate(self, est: stein.DerivativeEstimate
                       ) -> stein.DerivativeEstimate:
        """Fold the ``Domain`` Jacobian into a unit-box derivative
        estimate: ∂_x = ∂_z / s, ∂²_x = ∂²_z / s² per active axis.  The
        loss engine applies this before every ``residual`` call; with no
        domain (or the unit box) the estimate is returned UNCHANGED — the
        same object, so legacy computation graphs are bit-identical."""
        if self.domain is None or self.domain.is_unit:
            return est
        s = jnp.asarray(self.domain.scales[:est.grad.shape[-1]],
                        dtype=est.grad.dtype)
        return stein.DerivativeEstimate(u=est.u, grad=est.grad / s,
                                        hess_diag=est.hess_diag / (s * s))

    # --------------------------------------------------- input feature map
    def embed_features(self, xt: jax.Array):
        """Optional input feature map (..., net_dim) → (..., feature_dim)
        applied INSIDE the network embedding, before padding — e.g. the
        Fourier features (cos 2πz, sin 2πz, …) that make a network exactly
        periodic so the spectral estimator's ``"periodic"`` mode is valid.
        Overriding disables the ``fd_fast`` rank-1 layer-1 trick (it
        assumes an affine embedding); ``core.pinn`` resolves ``fd_fast``
        to plain ``fd`` for such problems.  None (default) keeps the
        legacy coeff-normalize + zero-pad embedding bit-identically."""
        return None

    @property
    def feature_dim(self) -> int:
        """Network input width after ``embed_features`` (net_dim when the
        problem has no feature map)."""
        return self.net_dim

    @property
    def has_feature_map(self) -> bool:
        return type(self).embed_features is not PDEProblem.embed_features

    def exact_solution(self, xt: jax.Array) -> jax.Array | None:
        """Closed-form u(xt) for validation, or None if unknown."""
        return None

    def spectral_carrier(self, rows: jax.Array, anchors: jax.Array):
        """Closed-form additive ansatz part β with analytic derivatives,
        or None.

        The spectral estimator differentiates by FFT along line segments
        that may cross kinks of the hard-constraint ansatz (HJB's ‖x‖₁
        has one at x_i = 0) — non-smooth closed-form terms would leave
        O(1) Gibbs error in the Hessian.  A problem whose ansatz is
        u = s + β with s the smooth learned part and β closed-form
        returns ``(β(rows), ∇β(anchors), diag∇²β(anchors))`` here: shapes
        ``(R,)``, ``(B, A)``, ``(B, A)`` for ``rows`` (R, net_dim) and
        ``anchors`` (B, net_dim), A = in_dim.  The FFT then sees only
        u − β and β's exact derivatives are added back at the anchors.
        Returning None (default) differentiates u directly.
        """
        return None

    @property
    def has_exact_solution(self) -> bool:
        return type(self).exact_solution is not PDEProblem.exact_solution

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"{type(self).__name__}(name={self.name!r}, "
                f"space_dim={self.space_dim})")


# ---------------------------------------------------------------- FD helpers

def fd_stencil_points(xt: jax.Array, h: float,
                      n_active: int | None = None) -> jax.Array:
    """(2A+1, B, D) central-difference stencil
    [x, x+h·e_1, ..., x+h·e_A, x−h·e_1, ..., x−h·e_A] of ``stein.fd_estimate``
    — the point layout every stencil evaluator in the repo shares.

    ``n_active`` restricts the differentiated coordinates to the first A
    columns: coefficient-conditioned rows carry K trailing coefficient
    slots that the PDE never differentiates, so their stencils shift only
    the physical ``in_dim`` prefix (A = D when None — bit-identical to the
    unrestricted form)."""
    B, D = xt.shape
    A = D if n_active is None else n_active
    eye = jnp.eye(A, D, dtype=xt.dtype) * jnp.asarray(h, dtype=xt.dtype)
    plus = xt[None, :, :] + eye[:, None, :]
    minus = xt[None, :, :] - eye[:, None, :]
    return jnp.concatenate([xt[None], plus, minus], axis=0)


def estimate_from_u_stencil(vals: jax.Array, h: float
                            ) -> stein.DerivativeEstimate:
    """Assemble (u, ∇u, diag H) from u-values on the central-difference
    stencil: vals (2D+1, B) → DerivativeEstimate with (B, D) leaves."""
    D = (vals.shape[0] - 1) // 2
    u0, up, um = vals[0], vals[1:D + 1], vals[D + 1:]
    return stein.DerivativeEstimate(
        u=u0,
        grad=((up - um) / (2.0 * h)).T,
        hess_diag=((up - 2.0 * u0[None] + um) / (h * h)).T)


def uniform_box(key: jax.Array, n: int, dim: int, lo: float,
                hi: float) -> jax.Array:
    """Uniform sample in [lo, hi]^dim — the common collocation primitive."""
    return jax.random.uniform(key, (n, dim), minval=lo, maxval=hi)


def estimate_for_problem(problem: PDEProblem, f: Callable, xt: jax.Array,
                         key: jax.Array | None = None,
                         estimator: str | None = None
                         ) -> stein.DerivativeEstimate:
    """Derivative estimate of a callable u at rows ``xt`` under the
    problem's DECLARED estimator (or an explicit override), with the
    domain Jacobian folded in — the single dispatch the registry smoke
    test and ad-hoc validation share, so "evaluate the
    residual the way this problem is trained" is one call.

    ``f(rows) -> values`` must accept arbitrarily-shaped leading axes
    (the spectral path feeds line rows).  ``key`` is only consulted by
    the stein estimator.
    """
    deriv = problem.estimator if estimator is None else estimator
    if deriv == "spectral":
        from repro.core import spectral as spectral_lib
        est = spectral_lib.spectral_estimate(
            f, xt, points=problem.spectral_points,
            extent=problem.spectral_extent,
            periodization=problem.spectral_periodization,
            n_active=problem.in_dim, carrier=problem.spectral_carrier)
    elif deriv == "stein":
        if key is None:
            raise ValueError("stein estimator needs a PRNG key")
        est = stein.stein_estimate(f, xt, key, n_active=problem.in_dim)
    elif deriv in ("fd", "fd_fast"):
        est = stein.fd_estimate(f, xt, h=problem.fd_step,
                                n_active=problem.in_dim)
    else:
        raise ValueError(f"unknown estimator {deriv!r}")
    return problem.scale_estimate(est)


# ------------------------------------------------------------------ registry

_REGISTRY: dict[str, Callable[[], PDEProblem]] = {}


def register(name: str):
    """Decorator: register a zero-arg factory under ``name``."""
    def deco(factory: Callable[[], PDEProblem]):
        if name in _REGISTRY:
            raise ValueError(f"PDE {name!r} already registered")
        _REGISTRY[name] = factory
        return factory
    return deco


def get_problem(name: str) -> PDEProblem:
    """Instantiate the registered problem ``name`` (fresh instance)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown PDE {name!r}; known: {sorted(_REGISTRY)}")
    prob = _REGISTRY[name]()
    if not prob.name:
        prob.name = name
    return prob


def available() -> tuple:
    """Registered problem names, sorted."""
    return tuple(sorted(_REGISTRY))
