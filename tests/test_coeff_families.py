"""Coefficient-conditioned PDE families (DESIGN.md §Parameterized families).

One conditioned ``TensorPinn`` per family — the coefficient vector rides in
extra input slots — trained once per module and then verified ANALYTICALLY
at ≥5 sampled coefficients: every registered family has a closed-form
solution parameterized by its coefficients, so per-coefficient validation
MSE against the exact solution is the ground-truth test that conditioning
actually works (not just that a residual went down).

Training here is the off-chip BP baseline (AdamW) purely for test budget —
the conditioned input contract is identical for the ZO paths
(tests/test_pde.py covers their parity on conditioned problems).

Documented tolerances (mean-squared error against the closed form on 400
held-out interior points, per coefficient draw; solution scales are O(1) in
every family):

  * ``heat-10d-kappa``     — 8e-2: the spreading-Gaussian family; trained
    with closed-form Dirichlet faces (backward heat on a box is residual-
    unique only WITH boundary data).  Observed ≤ 2e-2 at this budget; the
    tolerance leaves ~4x seed margin.
  * ``hjb-10d-lam``        — 1e-2: log-sum family, observed ≤ 6e-4.
  * ``black-scholes-8d-rs``— 1e-2: two-coefficient (r, sigma) geometric-
    Brownian family, observed ≤ 2e-3.

The serve-time arm pins the other half of the contract: a coefficient
outside the TRAINED range is rejected at submit, never extrapolated.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pde as pde_lib
from repro.core import pinn
from repro.data import pde_collocation_iterator
from repro.optim import get_optimizer

FAMILIES = {
    # pde -> (training steps, documented per-coefficient val-MSE tolerance)
    "heat-10d-kappa": (800, 8e-2),
    "hjb-10d-lam": (400, 1e-2),
    "black-scholes-8d-rs": (400, 1e-2),
}

_trained: dict = {}     # pde -> (model, params); one training run per family


def _train_family(pde: str):
    if pde in _trained:
        return _trained[pde]
    steps, _ = FAMILIES[pde]
    cfg = pinn.PINNConfig(hidden=48, mode="tt", tt_rank=2, tt_L=3, pde=pde)
    model = pinn.TensorPinn(cfg)
    prob = model.problem
    params = model.init(jax.random.PRNGKey(0))
    mask = model.trainable_mask(params)
    opt = get_optimizer("adamw", lr=3e-3)
    aux = opt.init(params)
    colloc = pde_collocation_iterator(128, seed=0, pde=pde)

    @jax.jit
    def step(params, aux, xt, bc):
        lf = lambda p: pinn.residual_loss(model, p, xt, bc=bc)
        loss, grads = jax.value_and_grad(lf)(params)
        grads = jax.tree.map(lambda g, t: g if t else jnp.zeros_like(g),
                             grads, mask)
        new_params, new_aux = opt.update(grads, aux, params)
        return new_params, new_aux, loss

    bc_key = jax.random.PRNGKey(5)
    for i in range(steps):
        bc = (prob.boundary_batch(jax.random.fold_in(bc_key, i), 32)
              if prob.has_boundary_loss else None)
        params, aux, _ = step(params, aux, next(colloc), bc)
    _trained[pde] = (model, params)
    return model, params


@pytest.mark.parametrize("pde", sorted(FAMILIES))
def test_trained_family_matches_closed_form_per_coefficient(pde):
    """≥5 sampled coefficient vectors, each verified against the family's
    closed-form solution within the documented tolerance — one conditioned
    checkpoint covering the whole range."""
    model, params = _train_family(pde)
    prob = model.problem
    spec = prob.coeff_spec
    assert spec is not None and prob.net_dim == prob.in_dim + spec.n
    draws = np.asarray(spec.sample(jax.random.PRNGKey(42), 5))
    assert draws.shape == (5, spec.n)
    pts = prob.sample_collocation(jax.random.PRNGKey(7),
                                  400)[:, :prob.in_dim]
    _, tol = FAMILIES[pde]
    mses = {}
    for c in draws:
        val = prob.attach_coeffs(pts, jnp.asarray(c))
        mses[tuple(np.round(c, 4))] = float(
            pinn.validation_mse(model, params, val))
    assert all(m < tol for m in mses.values()), (pde, tol, mses)
    # the coefficient input genuinely conditions the output: evaluating the
    # SAME points under the extreme draws gives different fields
    lo = prob.attach_coeffs(pts, jnp.asarray(spec.lo, np.float32))
    hi = prob.attach_coeffs(pts, jnp.asarray(spec.hi, np.float32))
    u_lo = np.asarray(model.u(params, lo))
    u_hi = np.asarray(model.u(params, hi))
    assert not np.allclose(u_lo, u_hi)


@pytest.mark.parametrize("pde", sorted(FAMILIES))
def test_exact_solution_satisfies_residual_per_coefficient(pde):
    """Model-free closed-form check at 5 draws: the documented exact
    solution must satisfy its own coefficient-instantiated residual (FD
    estimate on the exact u), per draw — guards the analytic expressions
    the trained-model test calibrates against."""
    from repro.core import stein
    prob = pde_lib.get_problem(pde)
    spec = prob.coeff_spec
    draws = np.asarray(spec.sample(jax.random.PRNGKey(3), 5))
    pts = prob.sample_collocation(jax.random.PRNGKey(11),
                                  200)[:, :prob.in_dim]
    for c in draws:
        xt = prob.attach_coeffs(pts, jnp.asarray(c))
        est = stein.fd_estimate(prob.exact_solution, xt, h=prob.fd_step,
                                n_active=prob.in_dim)
        r = prob.residual(est, xt)
        assert float(jnp.mean(r * r)) < prob.residual_tol, (pde, c)


# per family: the two held-out coefficient vectors near the range's ends
EXTREMES = {
    "heat-10d-kappa": ((0.6,), (1.8,)),
    "hjb-10d-lam": ((0.06,), (0.14,)),
    "black-scholes-8d-rs": ((0.02, 0.25), (0.09, 0.55)),
}


@pytest.mark.parametrize("pde", sorted(FAMILIES))
def test_true_coefficient_beats_the_opposite_extreme(pde):
    """The coefficient slots are load-bearing: at each end of the range the
    trained family evaluated with the TRUE coefficient is closer to the
    closed form than the same model evaluated with the opposite end."""
    model, params = _train_family(pde)
    prob = model.problem
    pts = prob.sample_collocation(jax.random.PRNGKey(7),
                                  400)[:, :prob.in_dim]
    a, b = EXTREMES[pde]
    for true, other in ((a, b), (b, a)):
        exact = prob.exact_solution(prob.attach_coeffs(pts, jnp.asarray(true)))
        mse = {c: float(jnp.mean((model.u(params, prob.attach_coeffs(
            pts, jnp.asarray(c))) - exact) ** 2)) for c in (true, other)}
        assert mse[true] < mse[other], (pde, true, mse)


def test_unconditioned_path_bit_identical_through_conditioning_seams():
    """The seams the conditioning generalized leave the unconditioned path
    bit for bit as it was: the default vs an explicit kappa=1 heat problem
    (u-stencils and stacked losses), ``shared_x=None`` vs ``True`` in the
    stacked TT dispatch, ``n_active=None`` vs ``in_dim`` in the FD
    estimate."""
    from repro.core import stein, tt
    from repro.kernels import ops
    from repro.pde.heat import HeatProblem
    p_default, p_explicit = HeatProblem(space_dim=10), \
        HeatProblem(space_dim=10, kappa=1.0)
    cfg = pinn.PINNConfig(hidden=32, mode="tt", tt_rank=2, tt_L=3,
                          pde="heat-10d", deriv="fd_fast")
    m0 = pinn.TensorPinn(cfg, problem=p_default)
    m1 = pinn.TensorPinn(cfg, problem=p_explicit)
    key = jax.random.PRNGKey(0)
    params = m0.init(key)
    xt = p_default.sample_collocation(jax.random.fold_in(key, 1), 16)
    sp = jax.tree.map(lambda l: jnp.broadcast_to(l, (3,) + l.shape), params)
    stencil = lambda m: np.asarray(jax.jit(
        lambda p: m.fd_u_stencil(p, xt, m.fd_step))(params))
    stacked = lambda m: np.asarray(jax.jit(
        lambda s: pinn.residual_losses_stacked(m, s, xt))(sp))
    np.testing.assert_array_equal(stencil(m0), stencil(m1))
    np.testing.assert_array_equal(stacked(m0), stacked(m1))

    spec = tt.auto_factorize(32, 32, L=3, max_rank=2)
    keys = jax.random.split(jax.random.fold_in(key, 2), 3)
    stacks = tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                   for i in range(spec.L))
    x = jax.random.normal(jax.random.fold_in(key, 3), (16, 32))
    np.testing.assert_array_equal(
        np.asarray(ops.tt_linear_batched(x, stacks, spec)),
        np.asarray(ops.tt_linear_batched(x, stacks, spec, shared_x=True)))

    def fd(n_active):
        def est(x):
            e = stein.fd_estimate(lambda pts: m0.u(params, pts), x,
                                  h=m0.fd_step, n_active=n_active)
            return e.grad, e.hess_diag
        return jax.jit(est)(xt)
    for a, b in zip(fd(None), fd(p_default.in_dim)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_out_of_range_coefficient_rejected_at_serve_time():
    """Regression for the serve-time contract: the family model is only
    valid INSIDE the trained coefficient box, and the engine refuses to
    extrapolate (full engine-path version in tests/test_serve_pde.py)."""
    from repro.serving import PdeServingEngine, PointRequest, SolverRegistry
    model, params = _train_family("hjb-10d-lam")
    reg = SolverRegistry()
    reg.register("fam", model, params)
    eng = PdeServingEngine(reg, slots=2, slot_points=16)
    prob = model.problem
    pts = np.asarray(prob.sample_collocation(jax.random.PRNGKey(1), 6),
                     np.float32)[:, :prob.in_dim]
    lo, hi = prob.coeff_spec.lo[0], prob.coeff_spec.hi[0]
    for bad in (lo * 0.5, hi * 2.0):
        with pytest.raises(ValueError, match="outside trained range"):
            eng.submit(PointRequest("fam", pts, coeffs=[bad]))
    ok = eng.submit(PointRequest("fam", pts,
                                 coeffs=[(lo + hi) / 2.0]))
    eng.run()
    assert ok.done


def test_coeff_spec_meta_roundtrip():
    """CoeffSpec survives the checkpoint meta.json round trip (json types
    only), including the distribution tag."""
    import json
    spec = pde_lib.CoeffSpec(("r", "sigma"), (0.01, 0.2), (0.1, 0.6),
                             dist="loguniform")
    back = pde_lib.CoeffSpec.from_meta(json.loads(json.dumps(spec.to_meta())))
    assert back == spec
