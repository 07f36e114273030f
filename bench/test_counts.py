"""The operation and byte counts of bench/counts.py against what the compiled
step really launches.

Run by path (not part of the repository's test suite):
    python -m pytest bench/test_counts.py

The stacked loss of the training configuration is compiled, at the
configuration's widths, for one chip of a described TPU v5e (nothing runs;
the topology is described inside a fixture).  The test checks that the
``tt_contract`` and ``mesh_apply_stacked`` kernels in the compiled HLO are
the calls the count functions assume, with the operand shapes they assume.
"""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(BENCH / "drivers")]

import counts  # noqa: E402
import harness  # noqa: E402

CUSTOM = re.compile(
    r"%(?P<name>[A-Za-z_]\w*?)(?:\.\d+)? = f32\[(?P<out>[\d,]*)\][^\n]*?"
    r"custom_call_target=\"tpu_custom_call\", "
    r"operand_layout_constraints=\{f32\[(?P<first>[\d,]*)\]")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def compiled_calls(cfg: dict, job: dict, sharding) -> list:
    """(kernel, output shape, first operand shape) of each Pallas call in
    the compiled stacked loss over N + 1 parameter sets."""
    from repro.core import pinn, zoo
    old = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = "pallas"
    try:
        model = harness.make_model(cfg)
        key = jax.random.PRNGKey(0)
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=sharding)
        params = jax.eval_shape(model.init, key)
        mask = model.trainable_mask(params)
        stack = jax.eval_shape(
            lambda p: zoo.sample_perturbations(key, p, job["zo_samples"] + 1,
                                               mask), params)
        noise = jax.eval_shape(model.sample_noise, key)
        xt = jax.ShapeDtypeStruct((job["batch"], cfg["space_dim"] + 1),
                                  jnp.float32, sharding=sharding)
        text = jax.jit(lambda sp, x, nz: pinn.residual_losses_stacked(
            model, sp, x, nz)).lower(
                jax.tree.map(spec, stack), xt,
                jax.tree.map(spec, noise)).compile().as_text()
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_MODE"]
        else:
            os.environ["REPRO_KERNEL_MODE"] = old
    calls = []
    for m in CUSTOM.finditer(text):
        dims = lambda s: tuple(int(d) for d in s.split(",") if d)
        calls.append((m["name"], dims(m["out"]), dims(m["first"])))
    return calls


def test_kernel_calls_match_counts(one_chip):
    files = harness.cell_files("tonn-hjb20d.train")
    cfg, job = files["config"], files["traffic"]
    sets = job["zo_samples"] + 1
    M, N = cfg["hidden"], cfg["hidden"]
    calls = compiled_calls(cfg, job, one_chip)

    tt_calls = sorted((out, first) for name, out, first in calls
                      if name == "tt_contract")
    want = sorted(((sets, rows, M), (rows, N) if shared else (sets, rows, N))
                  for _, rows, shared in counts.tt_calls_per_step(cfg, job,
                                                                  sets))
    assert tt_calls == want

    mesh = [c for c in calls if c[0] == "mesh_apply_stacked"]
    assert len(mesh) == len(counts.mesh_calls_per_step(cfg, sets))
    assert {c[0] for c in calls} <= {"tt_contract", "mesh_apply_stacked"}


def test_tt_count_is_factorized():
    """The layer-2 count is the core contraction chain, far below a dense
    M × N product, and it is memory-bound at the v5e peaks."""
    files = harness.cell_files("tonn-hjb20d.train")
    cfg, job = files["config"], files["traffic"]
    rows = 43 * job["batch"]
    c = counts.tt_call(cfg, rows, 11, shared_x=False)
    dense = 2 * rows * 11 * cfg["hidden"] ** 2
    assert c["flops"] == 2 * 4 * 8 * 8 * 128 * rows * 11   # 4 cores, each 8x8 per 128 columns
    assert c["flops"] < dense / 10
    peak = harness.load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    least = counts.tt_least_seconds(cfg, job, 11, peak)
    assert least["bound"] == "memory"
