"""Unit tests for the runtime policy layer: the straggler watchdog's
median+MAD classifier (window gating, patience firing/reset) and the
elastic controllers' checkpoint-restore resize bookkeeping — all synthetic
step times / host devices, no hardware (DESIGN.md §Fault tolerance)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.runtime import StragglerWatchdog
from repro.runtime.elastic import ElasticController, ZOElasticController


# ----------------------------------------------------------------- watchdog

def _feed(wd, durations, start=0):
    return [wd.end_step(start + i, duration_s=d)
            for i, d in enumerate(durations)]


def test_watchdog_needs_window_before_classifying():
    """The first 5 steps can never classify (no robust baseline yet), even
    for an absurd outlier — no false positives during warmup."""
    fired = []
    wd = StragglerWatchdog(threshold=3.0, patience=1,
                           on_straggle=fired.append)
    stats = _feed(wd, [0.1, 0.1, 0.1, 0.1, 100.0])
    assert not any(s.is_straggler for s in stats) and fired == []
    # 6th step: window has 5 samples, baseline live — outlier flagged
    assert wd.end_step(5, duration_s=100.0).is_straggler
    assert len(fired) == 1                  # patience=1 fires immediately


def test_watchdog_median_mad_classification():
    """Classification is median + threshold*MAD on the PRIOR window: a
    step just above the noise band is flagged, one inside it is not."""
    wd = StragglerWatchdog(threshold=3.0, patience=10)
    _feed(wd, [0.10, 0.12, 0.11, 0.09, 0.10, 0.11, 0.10, 0.12])
    # median 0.105, MAD 0.005 -> cutoff 0.12
    assert not wd.end_step(8, duration_s=0.115).is_straggler
    assert wd.end_step(9, duration_s=0.25).is_straggler
    st = wd.history[-1]
    assert st.duration_s == 0.25 and 0.09 <= st.median_s <= 0.13


def test_watchdog_patience_firing_and_reset():
    """The callback fires only after ``patience`` CONSECUTIVE stragglers,
    then resets; a clean step in between resets the count too."""
    fired = []
    wd = StragglerWatchdog(threshold=3.0, patience=3,
                           on_straggle=fired.append)
    base = [0.1] * 8
    _feed(wd, base)
    # two stragglers, a clean step, two more: never 3 consecutive
    for i, d in enumerate([5.0, 5.0, 0.1, 5.0, 5.0]):
        wd.end_step(10 + i, duration_s=d)
    assert fired == [] and wd.consecutive == 2
    # third consecutive: fires once, counter resets to 0
    st = wd.end_step(20, duration_s=5.0)
    assert len(fired) == 1 and fired[0] is st
    assert wd.consecutive == 0
    # outliers inflate the window's MAD; rebuild a tight baseline before
    # checking that the NEXT patience run fires again (no sticky state)
    _feed(wd, [0.1] * 8, start=30)
    for i in range(3):
        wd.end_step(40 + i, duration_s=5.0)
    assert len(fired) == 2


def test_watchdog_wall_clock_path():
    """start_step/end_step without an explicit duration measures real
    elapsed time (the trainer's usage)."""
    wd = StragglerWatchdog()
    wd.start_step()
    st = wd.end_step(0)
    assert st.duration_s >= 0 and wd.history == [st]


# -------------------------------------------------------------- elastic

def _zo_mesh(n_devices: int):
    # all test hosts are 1-device CPU: a (1, 1) ("pert", "batch") mesh per
    # "surviving" count keeps the controller logic the thing under test
    return jax.make_mesh((1, 1), ("pert", "batch"))


def test_zo_elastic_resume_restores_tree_and_rebuilds_step(tmp_path):
    """ZOElasticController.resume: newest checkpoint restored bit-exact,
    mesh/step rebuilt via the injected factories for the NEW device count,
    meta passed through — no re-sharding pass (replicated params)."""
    mgr = CheckpointManager(tmp_path, keep=2, save_every=1)
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
            "zo": {"key": jax.random.PRNGKey(7)}}
    mgr.save(3, tree, {"step": 3, "lr": 1e-3})
    stale = jax.tree.map(jnp.zeros_like, tree)
    mgr.save(5, tree, {"step": 5, "lr": 5e-4})   # newest wins

    built = []
    ctrl = ZOElasticController(
        ckpt=mgr, make_mesh=_zo_mesh,
        build_step=lambda mesh: built.append(mesh) or (lambda *a: "step"))
    mesh, step_fn, restored, meta = ctrl.resume(4, stale)
    assert built == [mesh] and mesh.axis_names == ("pert", "batch")
    assert step_fn() == "step"
    assert meta["step"] == 5 and meta["lr"] == 5e-4
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_zo_elastic_resume_without_checkpoint_raises(tmp_path):
    """No complete checkpoint -> the restore raises (the caller decides
    whether to cold-start); the controller must not invent state."""
    ctrl = ZOElasticController(
        ckpt=CheckpointManager(tmp_path, keep=2),
        make_mesh=_zo_mesh, build_step=lambda mesh: lambda *a: None)
    with pytest.raises(FileNotFoundError):
        ctrl.resume(2, {"params": {"w": jnp.zeros(2)}})


def test_zo_elastic_repeated_resizes_bookkeeping(tmp_path):
    """Shrink then grow: each resume rebuilds mesh+step fresh (one build
    per event, no caching of a dead mesh) and always restores the newest
    checkpoint at that moment."""
    mgr = CheckpointManager(tmp_path, keep=3, save_every=1)
    like = {"params": {"w": jnp.zeros(3)}}
    mgr.save(1, {"params": {"w": jnp.ones(3)}}, {"step": 1})
    builds = []
    ctrl = ZOElasticController(
        ckpt=mgr, make_mesh=_zo_mesh,
        build_step=lambda mesh: builds.append(mesh) or (lambda *a: None))
    _, _, t1, m1 = ctrl.resume(8, like)
    mgr.save(2, {"params": {"w": jnp.full(3, 2.0)}}, {"step": 2})
    _, _, t2, m2 = ctrl.resume(4, like)
    assert len(builds) == 2                  # one rebuild per resize event
    assert (m1["step"], m2["step"]) == (1, 2)
    np.testing.assert_array_equal(np.asarray(t1["params"]["w"]), 1.0)
    np.testing.assert_array_equal(np.asarray(t2["params"]["w"]), 2.0)


def test_bp_elastic_resume_remeshes_params(tmp_path):
    """ElasticController (BP/LM arm): restored arrays are re-placed on the
    new mesh and sharding fallbacks are surfaced in the report."""
    mgr = CheckpointManager(tmp_path, keep=2, save_every=1)
    params = {"w": jnp.arange(8.0).reshape(2, 4)}
    mgr.save(2, params, {"step": 2})
    ctrl = ElasticController(
        ckpt=mgr,
        make_mesh=lambda n: jax.make_mesh((1, 1), ("data", "model")),
        build_step=lambda mesh: lambda *a: "bp-step")
    mesh, step_fn, restored, info = ctrl.resume(1, jax.tree.map(
        jnp.zeros_like, params))
    assert step_fn() == "bp-step"
    assert info["meta"]["step"] == 2 and isinstance(info["fallbacks"], list)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(params["w"]))


# ------------------------------------------------------------ compile cache

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.runtime import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-dir", "checkout-dir"])
def test_compile_cache_directory(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the cache goes and
    nothing in code overrides it; otherwise the cache sits at the fixed
    git-ignored ``<checkout>/.jax_cache``.  Run in a child process: the
    cache directory is process-global once a compile has used it."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    from repro.runtime.compile_cache import DEFAULT_CACHE_DIR
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)   # the suite turns it off
    want = DEFAULT_CACHE_DIR
    if from_env:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=from_env)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(want), str(want)]
    if from_env:
        assert any(want.iterdir()), "no cache entry written"
    else:
        ignored = (DEFAULT_CACHE_DIR.parent / ".gitignore").read_text()
        assert f"{DEFAULT_CACHE_DIR.name}/" in ignored.split()
