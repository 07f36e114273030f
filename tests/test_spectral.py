"""Spectral (FFT-exact) derivative estimator: line-grid geometry, the
rfft-vs-naive-DFT oracle, periodization/carrier contracts, the unified
DerivativeEstimate width contract, and the pinn dispatch seam (sequential
== stacked, "auto" resolution, fd off-path bit-identity)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pde as pde_lib
from repro.core import pinn, spectral, stein


# ----------------------------------------------------------- line geometry

def test_line_rows_layout_and_count():
    B, D, A, M, W = 3, 5, 4, 8, 1.0
    x = jax.random.uniform(jax.random.PRNGKey(0), (B, D))
    rows = spectral.spectral_line_rows(x, A, M, W)
    assert rows.shape == (spectral.num_spectral_inferences(B, A, M), D)
    # anchors block first, untouched
    np.testing.assert_array_equal(np.asarray(rows[:B]), np.asarray(x))
    # inactive (coefficient) columns are never shifted: anchors first,
    # then each anchor's A·(M−1) line rows consecutively
    np.testing.assert_array_equal(
        np.asarray(rows[B:, A:]),
        np.asarray(jnp.repeat(x[:, A:], A * (M - 1), axis=0)))
    # each line is the anchor shifted along exactly one axis by the
    # centered offsets (anchor offset 0 excluded — it is deduped)
    rest = np.asarray(rows[B:]).reshape(B, A, M - 1, D)
    off = np.asarray(spectral.line_offsets(M, W))
    off_rest = np.concatenate([off[:M // 2], off[M // 2 + 1:]])
    for b in range(B):
        for a in range(A):
            delta = rest[b, a] - np.asarray(x)[b]
            np.testing.assert_allclose(delta[:, a], off_rest, atol=1e-7)
            delta[:, a] = 0.0
            np.testing.assert_array_equal(delta, 0.0)


def test_line_vals_roundtrip_reinserts_anchor():
    B, A, M = 3, 4, 8
    R = spectral.num_spectral_inferences(B, A, M)
    vals = jnp.arange(2 * R, dtype=jnp.float32).reshape(2, R)  # leading P=2
    lines = spectral.line_vals_from_rows_vals(vals, B, A, M)
    assert lines.shape == (2, B, A, M)
    # the center index of every line is the (shared) anchor value
    np.testing.assert_array_equal(
        np.asarray(lines[..., M // 2]),
        np.asarray(jnp.broadcast_to(vals[:, :B, None], (2, B, A))))


def test_window_is_one_at_anchor_and_tapers():
    for M in (8, 16, 32):
        w = np.asarray(spectral.spectral_window(M))
        assert w[M // 2] == 1.0
        assert w[0] == 0.0          # segment end: exact zero
        assert (w >= 0.0).all() and (w <= 1.0).all()


# ------------------------------------------------------------- rfft vs ref

@pytest.mark.parametrize("periodization", ["window", "periodic"])
@pytest.mark.parametrize("M", [8, 16, 17])
def test_spectral_derivs_match_naive_dft_oracle(periodization, M):
    lines = jax.random.normal(jax.random.PRNGKey(1), (3, 5, M))
    d1, d2 = spectral.spectral_derivs(lines, 1.0, periodization)
    r1, r2 = spectral.spectral_derivs_ref(np.asarray(lines), 1.0,
                                          periodization)
    np.testing.assert_allclose(np.asarray(d1), r1, atol=1e-3)
    np.testing.assert_allclose(np.asarray(d2), r2, atol=5e-2)


def test_unknown_periodization_raises():
    lines = jnp.zeros((2, 8))
    with pytest.raises(ValueError):
        spectral.spectral_derivs(lines, 1.0, "mirror")
    with pytest.raises(ValueError):
        spectral.spectral_derivs_ref(np.zeros((2, 8)), 1.0, "mirror")


# ------------------------------------------------ estimator accuracy floors

def test_periodic_mode_exact_on_band_limited():
    """Trig polynomial with max frequency < M/2: exact to f32 roundoff."""
    M = 16
    rs = np.random.RandomState(0)
    coef = rs.randn(3, 2)

    def f(x):
        out = 0.0
        for m in range(1, 4):
            out = out + coef[m - 1, 0] * jnp.cos(2 * jnp.pi * m * x) \
                      + coef[m - 1, 1] * jnp.sin(2 * jnp.pi * m * x)
        return jnp.sum(out, axis=-1)

    x = jax.random.uniform(jax.random.PRNGKey(0), (5, 3))
    est = spectral.spectral_estimate(f, x, points=M, extent=1.0,
                                     periodization="periodic")
    g = jax.vmap(jax.grad(lambda p: f(p[None])[0]))(x)
    h = jax.vmap(lambda p: jnp.diag(
        jax.hessian(lambda q: f(q[None])[0])(p)))(x)
    # second derivatives reach (2π·3)² ≈ 355 · |coef|: scale the roundoff
    np.testing.assert_allclose(np.asarray(est.grad), np.asarray(g),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(est.hess_diag), np.asarray(h),
                               atol=1e-2)


def test_windowed_mode_exact_on_quadratics():
    """The LSQ detrend makes locally-quadratic u exact by construction."""
    rs = np.random.RandomState(1)
    A = jnp.asarray(rs.randn(4, 4) * 0.1)
    b = jnp.asarray(rs.randn(4))
    f = lambda x: jnp.einsum("bi,ij,bj->b", x, A, x) + x @ b
    x = jax.random.uniform(jax.random.PRNGKey(0), (6, 4))
    est = spectral.spectral_estimate(f, x, points=8, extent=1.0)
    g = jax.vmap(jax.grad(lambda p: f(p[None])[0]))(x)
    np.testing.assert_allclose(np.asarray(est.grad), np.asarray(g),
                               atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(est.hess_diag),
        np.tile(np.asarray(jnp.diag(A + A.T)), (6, 1)), atol=2e-3)


@pytest.mark.parametrize("M", [8, 16])
def test_windowed_floor_on_smooth_nonperiodic(M):
    """Smooth non-periodic u: windowed-mode error within WINDOWED_FLOOR."""
    f = lambda x: jnp.sum(jnp.exp(-x) + 0.3 * x ** 3, axis=-1)
    x = jax.random.uniform(jax.random.PRNGKey(2), (6, 4))
    est = spectral.spectral_estimate(f, x, points=M, extent=1.0)
    g = jax.vmap(jax.grad(lambda p: f(p[None])[0]))(x)
    h = jax.vmap(lambda p: jnp.diag(
        jax.hessian(lambda q: f(q[None])[0])(p)))(x)
    assert float(jnp.max(jnp.abs(est.grad - g))) < spectral.WINDOWED_FLOOR
    assert float(jnp.max(jnp.abs(est.hess_diag - h))) \
        < spectral.WINDOWED_FLOOR


# --------------------------------------------------------- carrier contract

@pytest.mark.parametrize("name", ["hjb-10d", "heat-10d",
                                  "black-scholes-100d"])
def test_carrier_drives_exact_solution_residual_below_fd_floor(name):
    """The whole point of the estimator: on the exact solution, the
    carrier-assisted spectral residual sits orders of magnitude below the
    problem's documented FD noise floor (hjb's ‖x‖₁ kink included)."""
    prob = pde_lib.get_problem(name)
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 32)
    est = spectral.spectral_estimate(
        prob.exact_solution, xt, points=16, extent=prob.spectral_extent,
        periodization=prob.spectral_periodization,
        n_active=prob.in_dim, carrier=prob.spectral_carrier)
    r = prob.residual(est, xt)
    assert float(jnp.mean(r * r)) < 0.01 * prob.residual_tol


def test_hjb_without_carrier_is_poisoned_by_the_kink():
    """Negative control: lines crossing the ‖x‖₁ kink at the domain edge
    leave O(1) error without the carrier — the hook is load-bearing."""
    prob = pde_lib.get_problem("hjb-10d")
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 64)
    with_c = spectral.spectral_estimate(
        prob.exact_solution, xt, points=16, extent=prob.spectral_extent,
        n_active=prob.in_dim, carrier=prob.spectral_carrier)
    without = spectral.spectral_estimate(
        prob.exact_solution, xt, points=16, extent=prob.spectral_extent,
        n_active=prob.in_dim)
    err_with = float(jnp.mean(prob.residual(with_c, xt) ** 2))
    err_without = float(jnp.mean(prob.residual(without, xt) ** 2))
    assert err_with < 1e-4
    assert err_without > 100 * err_with


def test_default_spectral_carrier_is_none():
    assert pde_lib.get_problem("helmholtz-2d").spectral_carrier(
        jnp.zeros((4, 2)), jnp.zeros((2, 2))) is None


# ------------------------------------------- width contract (S3 regression)

def test_estimator_width_contract_on_conditioned_problem():
    """fd, stein and spectral all return (B, A) leaves on conditioned
    rows (A = in_dim < net_dim) and agree on the derivatives of the
    closed-form solution."""
    prob = pde_lib.get_problem("heat-10d-kappa")
    A, D = prob.in_dim, prob.net_dim
    assert A < D
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 4)
    f = prob.exact_solution
    fd = stein.fd_estimate(f, xt, h=1e-2, n_active=A)
    sn = stein.stein_estimate(f, xt, jax.random.PRNGKey(1), sigma=5e-2,
                              num_samples=4096, n_active=A)
    sp = spectral.spectral_estimate(f, xt, points=16, n_active=A,
                                    carrier=prob.spectral_carrier)
    for est in (fd, sn, sp):
        assert est.grad.shape == (4, A)
        assert est.hess_diag.shape == (4, A)
    np.testing.assert_allclose(np.asarray(sp.grad), np.asarray(fd.grad),
                               atol=spectral.WINDOWED_FLOOR + 1e-3)
    # stein is Monte-Carlo: loose agreement, but same contract and scale
    np.testing.assert_allclose(np.asarray(sn.grad), np.asarray(fd.grad),
                               atol=0.2)


def test_num_fd_inferences_counts_base_row():
    assert stein.num_fd_inferences(10) == 21
    assert stein.num_fd_inferences(12, n_active=11) == 23


# --------------------------------------------------------- pinn dispatch

def _model(deriv, pde="heat-10d", mode="tt", hidden=64, **kw):
    cfg = pinn.PINNConfig(hidden=hidden, mode=mode, tt_rank=2, tt_L=3,
                          deriv=deriv, pde=pde, **kw)
    return pinn.TensorPinn(cfg)


def test_spectral_sequential_equals_stacked_row():
    model = _model("spectral", spectral_points=8)
    params = model.init(jax.random.PRNGKey(0))
    xt = model.problem.sample_collocation(jax.random.PRNGKey(1), 16)
    l_seq = pinn.residual_loss(model, params, xt)
    sp = jax.tree.map(lambda x: jnp.stack([x, x * 1.01]), params)
    l_st = pinn.residual_losses_stacked(model, sp, xt)
    assert l_st.shape == (2,)
    np.testing.assert_allclose(float(l_seq), float(l_st[0]), rtol=1e-6)


@pytest.mark.parametrize("pde", ["hjb-10d", "heat-10d-kappa"])
def test_spectral_stacked_on_fused_modes(pde):
    """The fused tonn path carries the spectral line rows like any other
    shared-x batch; conditioned problems keep coeff slots undisturbed."""
    model = _model("spectral", pde=pde, mode="tonn")
    params = model.init(jax.random.PRNGKey(0))
    xt = model.problem.sample_collocation(jax.random.PRNGKey(2), 8)
    sp = jax.tree.map(lambda x: jnp.stack([x, x]), params)
    losses = pinn.residual_losses_stacked(model, sp, xt)
    assert np.isfinite(np.asarray(losses)).all()
    assert float(losses[0]) == float(losses[1])


def test_auto_deriv_resolves_to_problem_estimator_bit_identically():
    """cfg.deriv="auto" + problem.estimator="fd" (every shipped problem)
    must produce the exact fd loss — the bit-identity invariant."""
    params = _model("fd").init(jax.random.PRNGKey(0))
    xt = pde_lib.get_problem("heat-10d").sample_collocation(
        jax.random.PRNGKey(1), 16)
    l_fd = pinn.residual_loss(_model("fd"), params, xt)
    l_auto = pinn.residual_loss(_model("auto"), params, xt)
    assert float(l_fd) == float(l_auto)
    # and "auto" follows a problem that opts into spectral
    m = _model("auto")
    m.problem.estimator = "spectral"
    l_sp = pinn.residual_loss(m, params, xt)
    l_sp_explicit = pinn.residual_loss(_model("spectral"), params, xt)
    assert float(l_sp) == float(l_sp_explicit)
    assert float(l_sp) != float(l_fd)


def test_estimator_seam_leaves_fd_fast_and_stein_bit_identical():
    """The estimator dispatch seam perturbs no bit of the other estimators:
    "auto" on an fd problem equals fd on the stacked path too, a set
    ``spectral_points`` is inert for fd_fast, and "auto" deferring to a
    problem whose estimator is "stein" equals an explicit "stein"."""
    from repro.pde.heat import HeatProblem
    m = lambda deriv, **kw: _model(deriv, hidden=32, **kw)
    params = m("fd").init(jax.random.PRNGKey(0))
    xt = pde_lib.get_problem("heat-10d").sample_collocation(
        jax.random.PRNGKey(1), 16)
    sp = jax.tree.map(lambda l: jnp.broadcast_to(l, (3,) + l.shape), params)
    k = jax.random.PRNGKey(2)
    loss = lambda model, **kw: np.asarray(jax.jit(
        lambda p: pinn.residual_loss(model, p, xt, **kw))(params))
    stacked = lambda model: np.asarray(jax.jit(
        lambda s: pinn.residual_losses_stacked(model, s, xt))(sp))
    np.testing.assert_array_equal(stacked(m("fd")), stacked(m("auto")))
    fast, fast_sp = m("fd_fast"), m("fd_fast", spectral_points=8)
    np.testing.assert_array_equal(loss(fast), loss(fast_sp))
    np.testing.assert_array_equal(stacked(fast), stacked(fast_sp))
    p_stein = HeatProblem(space_dim=10)
    p_stein.estimator = "stein"
    m_auto = pinn.TensorPinn(m("auto").cfg, problem=p_stein)
    np.testing.assert_array_equal(loss(m("stein"), key=k),
                                  loss(m_auto, key=k))


def test_spectral_inference_bill_vs_fd():
    """At the shipped sizes on the 10-dim workloads (A = 11 active axes)
    the spectral estimator's 9 anchors at M = 8 cost 702 inferences a loss
    against fd's 2300 at the paper's batch 100: ≥ 3× fewer."""
    fd = stein.num_fd_inferences(11) * 100
    sp = spectral.num_spectral_inferences(9, 11, 8)
    assert (fd, sp) == (2300, 702)
    assert fd / sp >= 3.0


def test_ns2d_loss_is_the_periodic_line_assembly():
    """ns-2d resolves to the spectral estimator with its declared per-axis
    periodization, and its loss is reproduced bit for bit from a manual
    line assembly (rows → forward → per-axis FFT → scale → residual), so
    no fd fallback can hide in it."""
    model = _model("auto", pde="ns-2d", hidden=32)
    problem = model.problem
    assert pinn._resolve_deriv(model.cfg, problem) == "spectral"
    assert tuple(problem.spectral_periodization) == (
        "periodic", "periodic", "window")
    params = model.init(jax.random.PRNGKey(0))
    prepared, _ = model.prepare_params(params, None)
    xt = problem.sample_collocation(jax.random.PRNGKey(0), 32)
    M = problem.spectral_points
    rows = spectral.spectral_line_rows(xt, model.in_dim, M,
                                       problem.spectral_extent)
    est = spectral.estimate_from_line_vals(
        model.u(prepared, rows), xt, model.in_dim, M,
        problem.spectral_extent, problem.spectral_periodization,
        carrier=problem.spectral_carrier(rows, xt))
    r = problem.residual(problem.scale_estimate(est), xt)
    np.testing.assert_array_equal(
        np.asarray(jnp.mean(r * r)),
        np.asarray(pinn.residual_loss(model, params, xt)))


def test_config_meta_roundtrips_spectral_fields():
    cfg = pinn.PINNConfig(deriv="spectral", spectral_points=24)
    meta = pinn.config_to_meta(cfg)
    assert meta["deriv"] == "spectral" and meta["spectral_points"] == 24
    back = pinn.config_from_meta(meta)
    assert back.deriv == "spectral" and back.spectral_points == 24
    # old checkpoints (no spectral keys) load with defaults
    old = {k: v for k, v in meta.items()
           if k not in ("spectral_points",)}
    assert pinn.config_from_meta(old).spectral_points is None


def test_line_grid_iterator_matches_collocation_stream():
    from repro.data import pipeline
    it = pipeline.pde_line_grid_iterator(8, seed=3, pde="heat-10d",
                                         points=8)
    anchors, rows = next(it)
    colloc = next(pipeline.pde_collocation_iterator(8, seed=3,
                                                    pde="heat-10d"))
    np.testing.assert_array_equal(np.asarray(anchors), np.asarray(colloc))
    prob = pde_lib.get_problem("heat-10d")
    np.testing.assert_array_equal(
        np.asarray(rows),
        np.asarray(spectral.spectral_line_rows(
            anchors, prob.in_dim, 8, prob.spectral_extent)))
    # counter-based: step 2 differs, restart at start_step reproduces it
    a2, _ = next(it)
    assert not np.array_equal(np.asarray(anchors), np.asarray(a2))
    it2 = pipeline.pde_line_grid_iterator(8, seed=3, pde="heat-10d",
                                          points=8, start_step=1)
    np.testing.assert_array_equal(np.asarray(next(it2)[0]), np.asarray(a2))


# ------------------------------------------------- per-axis periodization

def _mixed_line_vals(B=4, M=16, seed=0):
    """(B, 3, M) line values: two band-limited periodic axes + one smooth
    non-periodic axis — the ns-2d layout (x, y periodic, t windowed)."""
    rs = np.random.RandomState(seed)
    theta = np.arange(M) / M                               # offsets / extent
    phase = rs.rand(B, 1) * 2 * np.pi
    ax0 = np.cos(2 * np.pi * theta[None] + phase)          # freq 1
    ax1 = np.sin(4 * np.pi * theta[None] + phase)          # freq 2
    ax2 = np.exp(-0.5 * (theta[None] - 0.3) ** 2) + rs.rand(B, 1)
    return jnp.asarray(np.stack([ax0, ax1, ax2], axis=1), dtype=jnp.float32)


def test_mixed_periodization_matches_ref_oracle():
    """Per-axis ("periodic", "periodic", "window") tuples: the vectorized
    rfft path must match the naive float64 DFT oracle axis by axis."""
    lines = _mixed_line_vals()
    ps = ("periodic", "periodic", "window")
    d1, d2 = spectral.spectral_derivs(lines, 1.0, ps)
    r1, r2 = spectral.spectral_derivs_ref(lines, 1.0, ps)
    assert d1.shape == d2.shape == (4, 3)
    np.testing.assert_allclose(np.asarray(d1), r1, atol=1e-3)
    np.testing.assert_allclose(np.asarray(d2), r2, atol=2e-2)
    # each column equals the scalar-mode call on that axis's lines
    for a, p in enumerate(ps):
        s1, s2 = spectral.spectral_derivs(lines[:, a, :], 1.0, p)
        np.testing.assert_array_equal(np.asarray(d1[:, a]), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(d2[:, a]), np.asarray(s2))


def test_uniform_periodization_tuple_collapses_to_scalar():
    """A uniform tuple is the scalar mode bit for bit — and needs NO
    (..., A, M) axis layout (it recurses before the shape check)."""
    lines = _mixed_line_vals()[:, 0, :]                    # (B, M), no axis
    for p in ("window", "periodic"):
        t1, t2 = spectral.spectral_derivs(lines, 1.0, (p, p, p))
        s1, s2 = spectral.spectral_derivs(lines, 1.0, p)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(t2), np.asarray(s2))


def test_periodization_tuple_error_cases():
    lines = _mixed_line_vals()
    with pytest.raises(ValueError, match="empty periodization"):
        spectral.spectral_derivs(lines, 1.0, ())
    with pytest.raises(ValueError, match="per-axis periodization"):
        # 2-entry mixed tuple against 3 line axes
        spectral.spectral_derivs(lines, 1.0, ("periodic", "window"))
    with pytest.raises(ValueError, match="per-axis periodization"):
        spectral.spectral_derivs_ref(lines, 1.0, ("periodic", "window"))
    with pytest.raises(ValueError, match="per-axis periodization"):
        # mixed tuple needs an axis dimension at position -2
        spectral.spectral_derivs(lines[:, 0, :], 1.0,
                                 ("periodic", "periodic", "window"))


def test_ns2d_estimator_uses_periodic_axes_exactly():
    """The declared ns-2d spectral configuration end to end: periodic x/y
    derivatives of the band-limited ω* are FFT-exact (≲ f32 roundoff),
    strictly tighter than the windowed floor, while the windowed t axis
    stays within its documented budget."""
    prob = pde_lib.get_problem("ns-2d")
    xt = prob.sample_collocation(jax.random.PRNGKey(0), 32)
    est = pde_lib.estimate_for_problem(prob, prob.exact_solution, xt)
    raw = prob.domain.from_unit(xt)
    w = prob._omega_star(raw)
    # exact raw-coordinate derivatives of ω* = 2 cos x cos y e^{-2νt}
    w_x = -2.0 * jnp.sin(raw[:, 0]) * jnp.cos(raw[:, 1]) * prob._decay(raw[:, 2])
    np.testing.assert_allclose(np.asarray(est.grad[:, 0]), np.asarray(w_x),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(est.hess_diag[:, 0]),
                               np.asarray(-w), atol=1e-4)
    np.testing.assert_allclose(np.asarray(est.grad[:, 2]),
                               np.asarray(-2.0 * prob.nu * w), atol=5e-3)
