"""The paper's ONN ("ONN on-chip", Table 1): every weight ``W = U Σ Vᵀ`` of
two rectangular MZI meshes, densified once per loss and multiplied.

Covers the densify-then-multiply route against applying the meshes to the
rows, and the program's first ZO-signSGD step against the plain reference
of the benchmark (``bench/reference/onn_pinn.py``, which imports nothing
of the program) on seeded random weights, at a small width with the
fabrication noise on.
"""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.hjb_pinn import pinn_config
from repro.core import pinn

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
HIDDEN = 48


def _bench_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "test_onn_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _model(hidden: int = HIDDEN) -> pinn.TensorPinn:
    return pinn.TensorPinn(pinn_config("hjb-20d", "onn", noise=True,
                                       hidden=hidden))


def test_onn_builds_no_tt_layer():
    assert _model().specs == []
    assert pinn.TensorPinn(pinn_config("hjb-20d", "dense")).specs == []


@pytest.mark.parametrize("stacked", [False, True], ids=["one", "stack"])
def test_onn_densify_then_multiply_matches_mesh_on_rows(stacked):
    """Layer i through its densified W (what the loss runs) against its two
    meshes applied to the rows: one f32 product of at most 48 terms against
    2·48 rotation levels of f32 rounding, so a few ulp of |z| ≈ 1."""
    model = _model()
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    noise = model.sample_noise(jax.random.fold_in(key, 99))
    # layer 0's rows are inputs zero-padded to its mesh's ports, as
    # ``_embed`` makes them: the densified layer reads their first
    # ``feat_in`` entries only
    x0 = model._embed(jax.random.uniform(jax.random.PRNGKey(1),
                                         (37, model.net_in)))
    assert model.photonic[0].in_dim == model.in_pad == HIDDEN
    x1 = jax.random.normal(jax.random.PRNGKey(2), (37, HIDDEN))
    if stacked:
        P = 3
        sp = jax.tree.map(lambda a: jnp.stack(
            [a * (1.0 + 0.05 * k) for k in range(P)]), params)
        prepared = model.prepare_params_stacked(sp, noise)
        assert "p0" not in prepared and prepared["wt1"].shape == (
            P, HIDDEN, HIDDEN)
        for i, x in enumerate((x0, x1)):
            pm = model.photonic[i]
            want = pm.apply_stacked(sp[f"p{i}"], x, model.cfg.noise,
                                    noise[f"p{i}"])
            got = model._layer_matvec_stacked(prepared, i, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-6)
    else:
        prepared, eff_noise = model.prepare_params(params, noise)
        assert eff_noise is None
        for i, x in enumerate((x0, x1)):
            pm = model.photonic[i]
            want = pm.apply(params[f"p{i}"], x, model.cfg.noise,
                            noise[f"p{i}"])
            got = model._layer_matvec(prepared, None, i, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("kernel_mode", ["ref", "interpret"])
def test_onn_first_step_matches_plain_reference(kernel_mode, monkeypatch):
    """The benchmark's training path (``bench/drivers/zo_train.py``: the
    program's stacked loss, ``zoo.zo_signsgd_step`` with the trainable
    mask) against the plain reference, both from one seed.

    The stencil u-values of the seeded start compare at f32 forward
    tolerance (two 48-level meshes and two products).  The first loss
    compares at the FD noise floor of DESIGN.md §Perf: the stencil's
    second differences amplify that rounding 1/h² = 1e4-fold, so the
    losses of two f32 paths differ by 1e-3..1e-2 relative (0.1 bounds it,
    the contract's bound on losses).  The first update is compared by
    the cell's ``update1_gap`` limit: every counted leaf moves by ±lr."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", kernel_mode)
    sys.path[:0] = [str(BENCH), str(BENCH / "drivers")]
    try:
        zo_train = _bench_module(BENCH / "drivers" / "zo_train.py")
        harness = _bench_module(BENCH / "harness.py")
    finally:
        del sys.path[:2]
    cfg = dict(harness.load_json(BENCH / "configs" / "onn-hjb20d.json"),
               hidden=HIDDEN)
    job = dict(harness.load_json(BENCH / "traffic" / "train.json"),
               batch=16, zo_samples=4)
    limits = harness.load_json(BENCH / "checks" / "onn-hjb20d.train.json")
    ref = _bench_module(BENCH / "reference" / "onn_pinn.py")
    seed = 2_718_281_829

    model = harness.make_model(cfg)
    params, noise = ref.seeded_start(cfg, seed)
    xt = ref.collocation(cfg, seed, 0, job["batch"])
    stacked = jax.tree.map(lambda a: a[None], params)
    got_u = model.fd_u_stencil_stacked(
        model.prepare_params_stacked(stacked, noise), xt, model.fd_step)[0]
    want_u = ref.stencil_u(params, noise, xt, cfg, "highest")
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-6, atol=0)

    step, params, aux, colloc, terms = zo_train.build(cfg, job, seed,
                                                      zo_train.Hooks())
    _, _, losses, snaps = zo_train.first_steps(step, params, aux, colloc,
                                               terms, job["lr"], 2)
    r = ref.zo_signsgd_steps(cfg, job, seed, 2)
    got = zo_train.readings(losses, snaps, r)
    assert got["leaves_counted"] >= 6
    assert got["loss0_gap"] <= 0.1, got["loss0_gap"]
    assert got["update1_gap"] <= limits["update1_gap"], got["update1_gap"]
