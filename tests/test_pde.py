"""Tests for the PDE problem registry (repro.pde) and the problem-
parameterized solver stack (TensorPinn + generic losses).

Per registered problem:
  * the FD residual of the exact solution sits below the problem's
    documented noise floor (``residual_tol``),
  * the fused stacked evaluator matches a sequential loop of scalar losses
    (the PR-1 parity harness, problem-parameterized),
plus registry semantics, boundary-loss (L_b) behavior, the stacked vmap
fallback's per-perturbation PRNG key splitting, and the backward-compatible
HJB-era aliases.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pde
from repro.core import pinn, stein, zoo

ALL_PDES = pde.available()
EXACT_PDES = [n for n in ALL_PDES if pde.get_problem(n).has_exact_solution]

# CPU-sized model per problem for parity tests (the 100-dim problem pays
# 2·101+1 stencil inferences per loss, so it gets a smaller batch)
PARITY_BATCH = {"black-scholes-100d": 4, "black-scholes-100d-rs": 4}


def _tiny_model(name: str, deriv: str = "fd_fast", **over) -> pinn.TensorPinn:
    cfg = pinn.PINNConfig(hidden=32, mode="tt", tt_rank=2, tt_L=2,
                          deriv=deriv, pde=name, **over)
    return pinn.TensorPinn(cfg)


# ------------------------------------------------------------------ registry

def test_registry_contains_workload_suite():
    for name in ("hjb-20d", "heat-10d", "heat-20d", "black-scholes-100d",
                 "helmholtz-2d"):
        assert name in ALL_PDES
        prob = pde.get_problem(name)
        assert prob.name == name
        assert prob.in_dim == prob.space_dim + int(prob.time_dependent)


def test_registry_unknown_name_raises():
    with pytest.raises(KeyError):
        pde.get_problem("not-a-pde")


def test_register_rejects_duplicates():
    with pytest.raises(ValueError):
        @pde.register("hjb-20d")
        def dup():
            return pde.HJBProblem()


def test_collocation_shapes_and_domain():
    for name in ALL_PDES:
        prob = pde.get_problem(name)
        xt = prob.sample_collocation(jax.random.PRNGKey(0), 32)
        # conditioned problems sample augmented rows: point + coefficients
        assert xt.shape == (32, prob.net_dim)
        assert bool(jnp.all(jnp.isfinite(xt)))


# -------------------------------------------- exact solutions vs FD residual

@pytest.mark.parametrize("name", EXACT_PDES)
def test_exact_solution_residual_below_noise_floor(name):
    """Plug the exact u into the generic FD estimator: the mean-squared
    residual must sit below the problem's documented floor (truncation
    h²·u⁗/12 + f32 rounding ε·|u|/h², summed over the Laplacian).
    ``scale_estimate`` folds the Domain Jacobian in first — the identity
    (same object) for every unit-box problem."""
    prob = pde.get_problem(name)
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 64)
    est = stein.fd_estimate(prob.exact_solution, xt, h=prob.fd_step,
                            n_active=prob.in_dim)
    r = prob.residual(prob.scale_estimate(est), xt)
    assert float(jnp.mean(r * r)) < prob.residual_tol, name


@pytest.mark.parametrize("name", EXACT_PDES)
def test_registry_smoke_declared_estimator_floor(name):
    """Registry smoke test: every problem's exact-solution residual sits
    below its documented ``residual_tol`` under its DECLARED default
    estimator, evaluated through the shared ``estimate_for_problem``
    dispatch (catches floor drift when new problems/estimators land)."""
    prob = pde.get_problem(name)
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 64)
    est = pde.estimate_for_problem(prob, prob.exact_solution, xt,
                                   key=jax.random.PRNGKey(1))
    r = prob.residual(est, xt)
    assert float(jnp.mean(r * r)) < prob.residual_tol, \
        (name, prob.estimator, float(jnp.mean(r * r)))


@pytest.mark.parametrize("name", EXACT_PDES)
def test_ansatz_plus_zero_network_validation_is_finite(name):
    """validation_mse against the exact solution runs for every problem
    that has one (and the ansatz/exact pair is consistent at t=1 where the
    hard constraint pins the terminal value)."""
    model = _tiny_model(name)
    params = model.init(jax.random.PRNGKey(0))
    xt = model.problem.sample_collocation(jax.random.PRNGKey(1), 16)
    mse = pinn.validation_mse(model, params, xt)
    assert bool(jnp.isfinite(mse))


def test_terminal_condition_exact_for_hard_constraint_problems():
    """Terminal-value problems bake u(x, T) into the ansatz: at t=1 the
    ansatz must agree with the exact solution regardless of f."""
    for name in ("hjb-20d", "heat-10d", "black-scholes-100d"):
        prob = pde.get_problem(name)
        xt = prob.sample_collocation(jax.random.PRNGKey(0), 9)
        xt = xt.at[:, -1].set(1.0)                       # t = 1
        f = jax.random.normal(jax.random.PRNGKey(1), (9,))
        np.testing.assert_allclose(np.asarray(prob.ansatz(f, xt)),
                                   np.asarray(prob.exact_solution(xt)),
                                   atol=1e-5, rtol=1e-5)


def test_validation_mse_raises_without_exact_solution():
    class NoExact(pde.HJBProblem):
        exact_solution = pde.PDEProblem.exact_solution

    model = pinn.TensorPinn(
        pinn.PINNConfig(hidden=16, mode="dense"), problem=NoExact())
    params = model.init(jax.random.PRNGKey(0))
    xt = model.problem.sample_collocation(jax.random.PRNGKey(1), 4)
    with pytest.raises(ValueError):
        pinn.validation_mse(model, params, xt)


def test_problem_fd_step_wired_into_model():
    """The solver's effective FD step defers to the problem's recommended
    step (the one residual_tol is documented at); an explicit, non-default
    config value still wins."""
    class SmallStep(pde.HJBProblem):
        fd_step = 5e-3

    cfg = pinn.PINNConfig(hidden=16, mode="dense")
    assert pinn.TensorPinn(cfg, problem=SmallStep()).fd_step == 5e-3
    cfg_over = pinn.PINNConfig(hidden=16, mode="dense", fd_step=2e-2)
    assert pinn.TensorPinn(cfg_over, problem=SmallStep()).fd_step == 2e-2


# -------------------------------------------------- stacked/sequential parity

@pytest.mark.parametrize("name", ALL_PDES)
@pytest.mark.parametrize("deriv", ["fd", "fd_fast"])
def test_stacked_losses_match_sequential_per_problem(name, deriv):
    """The PR-1 parity harness, per problem: residual_losses_stacked (the
    fused multi-perturbation evaluator) == a python loop of residual_loss
    over the stack — boundary term included where the problem has one."""
    batch = PARITY_BATCH.get(name, 8)
    model = _tiny_model(name, deriv=deriv)
    prob = model.problem
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    plist = [model.init(k) for k in keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
    xt = prob.sample_collocation(jax.random.PRNGKey(1), batch)
    bc = (prob.boundary_batch(jax.random.PRNGKey(2), batch)
          if prob.has_boundary_loss else None)
    seq = jnp.stack([pinn.residual_loss(model, p, xt, bc=bc) for p in plist])
    bat = pinn.residual_losses_stacked(model, stacked, xt, bc=bc)
    np.testing.assert_allclose(np.asarray(bat), np.asarray(seq),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["tonn", "onn"])
def test_photonic_noise_stacked_matches_sequential(mode):
    """The paper's Table-1 on-chip rows: photonic parametrization with the
    fabrication-noise model ON.  The batched mesh engine (stacked
    densification for tonn, stacked mesh matvecs for onn) must reproduce a
    sequential loop of scalar losses on the same (shared-chip) noise —
    boundary term included via helmholtz for tonn."""
    from repro.core import photonic
    name = "helmholtz-2d" if mode == "tonn" else "heat-10d"
    nm = photonic.NoiseModel(enabled=True)
    cfg = pinn.PINNConfig(hidden=16, mode=mode, tt_rank=2, tt_L=2,
                          deriv="fd_fast", pde=name, noise=nm)
    model = pinn.TensorPinn(cfg)
    prob = model.problem
    plist = [model.init(k) for k in jax.random.split(jax.random.PRNGKey(0), 3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
    noise = model.sample_noise(jax.random.PRNGKey(5))
    xt = prob.sample_collocation(jax.random.PRNGKey(1), 8)
    bc = (prob.boundary_batch(jax.random.PRNGKey(2), 8)
          if prob.has_boundary_loss else None)
    seq = jnp.stack([pinn.residual_loss(model, p, xt, noise, bc=bc)
                     for p in plist])
    bat = pinn.residual_losses_stacked(model, stacked, xt, noise, bc=bc)
    np.testing.assert_allclose(np.asarray(bat), np.asarray(seq),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("name", ["heat-10d", "helmholtz-2d"])
def test_fused_kernel_stacked_matches_unfused_per_problem(name):
    """use_fused_kernel (stacked TT contraction + Kronecker head +
    polynomial sine) against the unfused chain on non-HJB problems:
    u-stencils strictly, losses at the 1/h² FD noise floor (DESIGN.md)."""
    model = _tiny_model(name)
    model_f = pinn.TensorPinn(
        dataclasses.replace(model.cfg, use_fused_kernel=True))
    plist = [model.init(k) for k in jax.random.split(jax.random.PRNGKey(0), 3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
    xt = model.problem.sample_collocation(jax.random.PRNGKey(1), 6)
    h = model.fd_step
    np.testing.assert_allclose(
        np.asarray(model_f.fd_u_stencil_stacked(stacked, xt, h)),
        np.asarray(model.fd_u_stencil_stacked(stacked, xt, h)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(pinn.residual_losses_stacked(model_f, stacked, xt)),
        np.asarray(pinn.residual_losses_stacked(model, stacked, xt)),
        rtol=2e-2, atol=1e-4)


# --------------------------------------------------------- boundary loss L_b

def test_boundary_batch_on_boundary_with_zero_target():
    prob = pde.get_problem("helmholtz-2d")
    xb, ub = prob.boundary_batch(jax.random.PRNGKey(0), 128)
    assert xb.shape == (128, 2) and ub.shape == (128,)
    on_edge = jnp.any((xb == 0.0) | (xb == 1.0), axis=-1)
    assert bool(jnp.all(on_edge))
    np.testing.assert_array_equal(np.asarray(ub), 0.0)
    # the exact solution satisfies the Dirichlet condition
    np.testing.assert_allclose(np.asarray(prob.exact_solution(xb)), 0.0,
                               atol=1e-5)


def test_boundary_term_changes_loss_and_is_weighted():
    model = _tiny_model("helmholtz-2d")
    params = model.init(jax.random.PRNGKey(0))
    prob = model.problem
    xt = prob.sample_collocation(jax.random.PRNGKey(1), 16)
    bc = prob.boundary_batch(jax.random.PRNGKey(2), 16)
    l_r = pinn.residual_loss(model, params, xt)
    l_rb = pinn.residual_loss(model, params, xt, bc=bc)
    xb, ub = bc
    expected_b = float(jnp.mean((model.u(params, xb) - ub) ** 2))
    assert float(l_rb) == pytest.approx(
        float(l_r) + prob.bc_weight * expected_b, rel=1e-5)


# ----------------------------------------------------- loss-term engine

def _legacy_residual_loss(model, params, xt, bc):
    """The pre-term-engine ``residual_loss`` formula, inlined verbatim
    (fd_fast stencil path, no noise): L_r + bc_weight · MSE(u(xb), ub).
    The engine refactor must reproduce it BIT-identically."""
    params, noise = model.prepare_params(params, None)
    vals = model.fd_u_stencil(params, xt, model.fd_step, noise)
    est = pde.estimate_from_u_stencil(vals, model.fd_step)
    r = model.problem.residual(est, xt)
    loss = jnp.mean(r * r)
    if bc is not None:
        xb, ub = bc
        loss = loss + model.problem.bc_weight * jnp.mean(
            (model.u(params, xb, noise) - ub) ** 2)
    return loss


def _legacy_residual_losses_stacked(model, stacked, xt, bc):
    """The pre-term-engine stacked formula, inlined verbatim."""
    prepared = model.prepare_params_stacked(stacked, None)
    h = model.fd_step
    vals = model.fd_u_stencil_stacked(prepared, xt, h)
    def per_stack(v):
        est = pde.estimate_from_u_stencil(v, h)
        r = model.problem.residual(est, xt)
        return jnp.mean(r * r)
    losses = jax.vmap(per_stack)(vals)
    if bc is not None:
        xb, ub = bc
        losses = losses + model.problem.bc_weight * jnp.mean(
            (model.u_stacked(prepared, xb) - ub) ** 2, axis=-1)
    return losses


@pytest.mark.parametrize("name", ALL_PDES)
def test_term_engine_reproduces_legacy_loss_bit_identically(name):
    """Satellite regression for the composite-loss refactor: for EVERY
    registered problem the engine's L = Σ w_k·L_k assembly reproduces the
    pre-engine ``L_r + λ·L_b`` values bit-identically (np.array_equal, no
    tolerance), scalar and stacked.  Domain-normalized / feature-mapped
    problems postdate the legacy path and are exercised by their own
    tests instead."""
    model = _tiny_model(name, deriv="fd_fast")
    prob = model.problem
    if (prob.domain is not None and not prob.domain.is_unit) \
            or prob.has_feature_map:
        pytest.skip("no pre-engine semantics to preserve")
    batch = PARITY_BATCH.get(name, 8)
    params = model.init(jax.random.PRNGKey(0))
    xt = prob.sample_collocation(jax.random.PRNGKey(1), batch)
    bc = (prob.boundary_batch(jax.random.PRNGKey(2), batch)
          if prob.has_boundary_loss else None)
    np.testing.assert_array_equal(
        np.asarray(pinn.residual_loss(model, params, xt, bc=bc)),
        np.asarray(_legacy_residual_loss(model, params, xt, bc)))
    stacked = jax.tree.map(lambda p: jnp.stack([p, p, p]), params)
    np.testing.assert_array_equal(
        np.asarray(pinn.residual_losses_stacked(model, stacked, xt, bc=bc)),
        np.asarray(_legacy_residual_losses_stacked(model, stacked, xt, bc)))


def test_bc_and_term_batches_paths_agree_bit_identically():
    """The deprecated ``bc=`` convention maps onto the problem's boundary
    term: routing the SAME batch through ``term_batches=`` must produce
    the same loss bit for bit (scalar and stacked)."""
    for name in ("helmholtz-2d", "ns-2d"):
        model = _tiny_model(name)
        prob = model.problem
        params = model.init(jax.random.PRNGKey(0))
        xt = prob.sample_collocation(jax.random.PRNGKey(1), 8)
        bc = prob.boundary_batch(jax.random.PRNGKey(2), 8)
        b_name = next(t.name for t in prob.loss_terms()
                      if t.kind == "boundary")
        l_bc = pinn.residual_loss(model, params, xt, bc=bc)
        l_tb = pinn.residual_loss(model, params, xt,
                                  term_batches={b_name: bc})
        np.testing.assert_array_equal(np.asarray(l_bc), np.asarray(l_tb))
        stacked = jax.tree.map(lambda p: jnp.stack([p, p]), params)
        np.testing.assert_array_equal(
            np.asarray(pinn.residual_losses_stacked(
                model, stacked, xt, bc=bc)),
            np.asarray(pinn.residual_losses_stacked(
                model, stacked, xt, term_batches={b_name: bc})))


def test_term_plan_rejects_ambiguous_and_unknown():
    model = _tiny_model("helmholtz-2d")
    prob = model.problem
    params = model.init(jax.random.PRNGKey(0))
    xt = prob.sample_collocation(jax.random.PRNGKey(1), 4)
    bc = prob.boundary_batch(jax.random.PRNGKey(2), 4)
    with pytest.raises(ValueError, match="not both"):
        pinn.residual_loss(model, params, xt, bc=bc,
                           term_batches={"boundary": bc})
    with pytest.raises(ValueError, match="unknown loss term"):
        pinn.residual_loss(model, params, xt, term_batches={"nope": bc})


def test_set_term_weights_override_and_validation():
    """``set_term_weights`` rescales the composite loss per term, rejects
    unknown names, and stays per-instance (a fresh registry instance is
    unaffected)."""
    model = _tiny_model("helmholtz-2d")
    prob = model.problem
    params = model.init(jax.random.PRNGKey(0))
    xt = prob.sample_collocation(jax.random.PRNGKey(1), 8)
    bc = prob.boundary_batch(jax.random.PRNGKey(2), 8)
    l_r = float(pinn.residual_loss(model, params, xt))
    l_b = float(pinn.per_term_losses(
        model, params, xt, term_batches={"boundary": bc})["boundary"])
    prob.set_term_weights({"boundary": 3.0, "residual": 0.5})
    assert prob.term_weights() == {"residual": 0.5, "boundary": 3.0}
    l = float(pinn.residual_loss(model, params, xt,
                                 term_batches={"boundary": bc}))
    assert l == pytest.approx(0.5 * l_r + 3.0 * l_b, rel=1e-5)
    with pytest.raises(ValueError):
        prob.set_term_weights({"not-a-term": 1.0})
    assert pde.get_problem("helmholtz-2d").term_weights() == {
        "residual": 1.0, "boundary": 1.0}


def test_term_weights_roundtrip_through_checkpoint_meta(tmp_path):
    """Satellite 2 acceptance: weights set at train time serialize into
    checkpoint meta and are restored onto the problem at serve time."""
    from repro.launch.train import main as train_main
    from repro.serving.registry import SolverRegistry
    train_main(["--arch", "tensor-pinn", "--pde", "ns-2d", "--reduced",
                "--steps", "2", "--batch", "8", "--hidden", "16",
                "--pinn-mode", "tt", "--zo-samples", "3",
                "--log-every", "100", "--ckpt-dir", str(tmp_path),
                "--term-weight", "ic=2.5,data=0.25"])
    solver = SolverRegistry().load_checkpoint("ns", tmp_path)
    assert solver.model.problem.term_weights() == {
        "residual": 1.0, "ic": 2.5, "data": 0.25}


# ------------------------------------- stacked vmap fallback PRNG key split

def test_stacked_stein_fallback_splits_key_per_perturbation():
    """Regression (PR-2): the stacked-loss vmap fallback reused ONE key for
    all P perturbations, correlating the Stein estimates across the SPSA
    stack.  Contract: stacked entry i must equal the scalar loss evaluated
    with jax.random.split(key, P)[i], so identical stacked params still see
    DISTINCT estimator noise."""
    model = _tiny_model("hjb-20d", deriv="stein",
                        stein_samples=4, stein_sigma=5e-2)
    params = model.init(jax.random.PRNGKey(0))
    P = 3
    stacked = jax.tree.map(lambda p: jnp.stack([p] * P), params)
    xt = model.problem.sample_collocation(jax.random.PRNGKey(1), 8)
    key = jax.random.PRNGKey(7)
    losses = pinn.residual_losses_stacked(model, stacked, xt, key=key)
    # identical params + distinct noise → distinct Stein losses
    assert len(set(np.asarray(losses).tolist())) == P, losses
    # and each entry reproduces the scalar path under the split contract
    keys = jax.random.split(key, P)
    for i in range(P):
        li = pinn.residual_loss(model, params, xt, key=keys[i])
        assert float(losses[i]) == pytest.approx(float(li), rel=1e-6)


# ------------------------------------------------------- deprecated aliases

def test_hjb_aliases_match_generic_api():
    cfg = pinn.PINNConfig(hidden=32, mode="tt", tt_rank=2, tt_L=2)
    model = pinn.HJBPinn(cfg)
    assert isinstance(model, pinn.TensorPinn)
    assert model.problem.name == "hjb-20d"
    params = model.init(jax.random.PRNGKey(0))
    xt = pinn.sample_collocation(jax.random.PRNGKey(1), 8)
    # sampler shim is bit-identical to the problem's own
    np.testing.assert_array_equal(
        np.asarray(xt),
        np.asarray(model.problem.sample_collocation(jax.random.PRNGKey(1), 8)))
    np.testing.assert_array_equal(
        np.asarray(pinn.hjb_exact_solution(xt)),
        np.asarray(model.problem.exact_solution(xt)))
    l_alias = pinn.hjb_residual_loss(model, params, xt)
    l_new = pinn.residual_loss(model, params, xt)
    assert float(l_alias) == float(l_new)
    stacked = jax.tree.map(lambda p: jnp.stack([p, p]), params)
    np.testing.assert_array_equal(
        np.asarray(pinn.hjb_residual_losses_stacked(model, stacked, xt)),
        np.asarray(pinn.residual_losses_stacked(model, stacked, xt)))


def test_hjbpinn_honors_config_space_dim():
    cfg = pinn.PINNConfig(hidden=16, mode="dense", space_dim=10)
    model = pinn.HJBPinn(cfg)
    assert model.space_dim == 10 and model.in_dim == 11
    params = model.init(jax.random.PRNGKey(0))
    xt = pinn.sample_collocation(jax.random.PRNGKey(1), 4, space_dim=10)
    assert model.u(params, xt).shape == (4,)


# --------------------------------------------------------------- end to end

def test_train_cli_pinn_branch_runs_heat(tmp_path):
    """Acceptance: the launcher trains a non-HJB workload with ZO-signSGD
    end to end through the fused stacked path."""
    from repro.launch.train import main as train_main
    train_main(["--arch", "hjb-pinn", "--pde", "heat-10d", "--reduced",
                "--steps", "3", "--batch", "8", "--hidden", "16",
                "--pinn-mode", "tt", "--zo-samples", "3",
                "--log-every", "100"])


def test_train_watchdog_leaves_log_line_out_of_next_step(monkeypatch):
    """The host work of a log step (per-term losses, validation, the
    print) is no step's time: the watchdog's interval for the step after
    a log line starts when the line is done."""
    import time

    from repro.launch import train
    made = []

    class Recording(train.StragglerWatchdog):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    def slow_print(*a, **k):
        if a and str(a[0]).startswith("step "):
            time.sleep(0.5)
        print(*a, **k)

    monkeypatch.setattr(train, "StragglerWatchdog", Recording)
    monkeypatch.setattr(train, "print", slow_print, raising=False)
    train.main(["--arch", "hjb-pinn", "--pde", "heat-10d", "--reduced",
                "--steps", "6", "--batch", "8", "--hidden", "16",
                "--pinn-mode", "tt", "--zo-samples", "3",
                "--log-every", "3"])
    durations = {s.step: s.duration_s for s in made[-1].history}
    assert sorted(durations) == list(range(6))
    # log lines at steps 0 and 3; steps 1 and 4 complete after them
    assert durations[1] < 0.5 and durations[4] < 0.5


def test_train_cli_pinn_branch_runs_boundary_problem(tmp_path):
    from repro.launch.train import main as train_main
    train_main(["--arch", "tensor-pinn", "--pde", "helmholtz-2d", "--reduced",
                "--steps", "3", "--batch", "8", "--hidden", "16",
                "--pinn-mode", "tt", "--zo-samples", "3",
                "--log-every", "100"])


def test_zo_training_improves_heat_loss():
    """A short fused ZO-signSGD run on heat-10d must reduce the residual
    loss — the end-to-end claim on a non-HJB workload."""
    model = _tiny_model("heat-10d", use_fused_kernel=True)
    prob = model.problem
    params = model.init(jax.random.PRNGKey(0))
    scfg = zoo.SPSAConfig(num_samples=6, mu=0.01)
    state = zoo.ZOState.create(1)
    val = prob.sample_collocation(jax.random.PRNGKey(2), 256)

    @jax.jit
    def step(params, state, xt, lr):
        lf = lambda p: pinn.residual_loss(model, p, xt)
        blf = lambda sp: pinn.residual_losses_stacked(model, sp, xt)
        return zoo.zo_signsgd_step(lf, params, state, lr=lr, cfg=scfg,
                                   batched_loss_fn=blf)

    l0 = float(pinn.residual_loss(model, params, val))
    for i in range(60):
        xt = prob.sample_collocation(
            jax.random.fold_in(jax.random.PRNGKey(9), i), 32)
        params, state, _ = step(params, state, xt, 2e-3 * 0.5 ** (i / 30))
    l1 = float(pinn.residual_loss(model, params, val))
    assert l1 < 0.7 * l0, (l0, l1)
