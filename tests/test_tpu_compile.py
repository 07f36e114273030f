"""Compile-only checks of the PINN Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at the paper's width (two TT layers
of ``PAPER_TONN_SPEC``, which take the Kronecker body, and a spec without
an interior rank 1 that keeps the dense one; P = N+1 = 11 perturbations,
43 × 100 stencil rows, the serving pool of 8 × 256 points, the tonn core
meshes, the onn layers' 1024-port meshes) and compiles it with the TPU compiler for one chip of a described
``v5e:2x2`` topology.  The compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, unsupported relayouts), so these guard every kernel edit
at no chip time.  The topology is described inside a fixture, so no worker
loads the TPU library while collecting.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.hjb_pinn import pinn_config
from repro.core import photonic, pinn, tt
from repro.kernels import mesh_apply, quant, tt_contract

P = 11                      # N = 10 SPSA perturbations + the base point
STENCIL_ROWS = 43 * 100     # (2·21 + 1) stencil rows × batch 100
POOL_ROWS = 8 * 256         # serving pool: 8 slots × 256 points
SPEC = tt.PAPER_TONN_SPEC


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda dims: jax.ShapeDtypeStruct(dims, jnp.float32,
                                             sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def test_tt_contract_serving_pool_compiles(shape):
    cores = [shape(s) for s in SPEC.core_shapes]
    _assert_kernel(lambda x, *c: tt_contract.tt_contract(x, c, SPEC),
                   shape((POOL_ROWS, SPEC.in_dim)), *cores)


@pytest.mark.parametrize("shared_x", [True, False],
                         ids=["shared-x", "per-p-x"])
def test_tt_contract_batched_zo_step_compiles(shape, shared_x):
    cores = [shape((P,) + s) for s in SPEC.core_shapes]
    x = shape((STENCIL_ROWS, SPEC.in_dim) if shared_x
              else (P, STENCIL_ROWS, SPEC.in_dim))
    _assert_kernel(
        lambda x, *c: tt_contract.tt_contract_batched(x, c, SPEC), x, *cores)


@pytest.mark.parametrize("rows", [21, 100], ids=["columns", "layer0"])
def test_tt_contract_batched_small_tiles_compile(shape, rows):
    """Layer 0's calls of a step: the 21 stencil columns and the 100-row
    batch, shared across P; tiles below and off the VPU stage's chunk."""
    cores = [shape((P,) + s) for s in SPEC.core_shapes]
    _assert_kernel(
        lambda x, *c: tt_contract.tt_contract_batched(x, c, SPEC),
        shape((rows, SPEC.in_dim)), *cores)


def test_tt_contract_dense_body_compiles(shape):
    """A spec of the paper's width with ranks (1, 2, 2, 2, 1) has no
    interior rank 1 and keeps the dense-W body."""
    spec = tt.auto_factorize(1024, 1024, L=4, max_rank=2)
    assert tt_contract.kron_factors(spec) is None
    cores = [shape((P,) + s) for s in spec.core_shapes]
    _assert_kernel(
        lambda x, *c: tt_contract.tt_contract_batched(x, c, spec),
        shape((P, STENCIL_ROWS, spec.in_dim)), *cores)


def test_tt_contract_batched_quant_int8_compiles(shape):
    qcfg = quant.QuantConfig(enabled=True, dtype="int8")
    cores = [shape((P,) + s) for s in SPEC.core_shapes]
    _assert_kernel(
        lambda x, *c: tt_contract.tt_contract_batched_quant(x, c, SPEC, qcfg),
        shape((P, STENCIL_ROWS, SPEC.in_dim)), *cores)


# the unfoldings of the paper's TT cores: (r·m) × (n·r') = 4 × 16 and 16 × 4
@pytest.mark.parametrize("ports", [4, 16])
@pytest.mark.parametrize("shared_x", [True, False],
                         ids=["identity-feed", "per-s-x"])
def test_mesh_apply_stacked_tonn_mesh_compiles(shape, ports, shared_x):
    layout = photonic.rectangular_layout(ports)
    x = shape((ports, ports) if shared_x else (P, ports, ports))
    _assert_kernel(
        lambda ph, d, x: mesh_apply.mesh_apply_stacked_pallas(
            layout, ph, d, x, transpose=shared_x),
        shape((P,) + layout.phase_shape()), shape((ports,)), x)


# the onn layers' 1024-port meshes: layer 1's Vᵀ on the shared identity
# feed and its U on the per-entry result; layer 0's Vᵀ and U on the 21
# columns that its zero-padded input reads
@pytest.mark.parametrize("rows,shared_x", [(1024, True), (1024, False),
                                           (21, True), (21, False)],
                         ids=["identity-feed", "per-s-x",
                              "layer0-identity-feed", "layer0-columns"])
def test_mesh_rect_onn_mesh_compiles(shape, rows, shared_x):
    layout = photonic.rectangular_layout(1024)
    x = shape((rows, 1024) if shared_x else (P, rows, 1024))
    _assert_kernel(
        lambda ph, d, x: mesh_apply.mesh_apply_rect_pallas(
            layout, ph, d, x, transpose=shared_x),
        shape((P,) + layout.phase_shape()), shape((1024,)), x)


@pytest.mark.parametrize("n_stack", [3, P])
def test_stacked_onn_layer_rows_do_not_depend_on_stack_size(
        one_chip, n_stack, monkeypatch):
    """The onn layers multiply by their densified W one entry at a time on
    TPU, so no product or reduction of the stacked forward carries the
    stack axis (DESIGN.md §Distributed); the meshes densify in the
    ``mesh_rect`` kernel."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    model = pinn.TensorPinn(pinn_config("hjb-20d", "onn", noise=True))
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    stacked = jax.tree.map(sds, jax.eval_shape(lambda: jax.tree.map(
        lambda a: jnp.stack([a] * n_stack),
        model.init(jax.random.PRNGKey(0)))))
    noise = jax.tree.map(sds, jax.eval_shape(
        lambda: model.sample_noise(jax.random.PRNGKey(1))))
    rows = jax.ShapeDtypeStruct((100, model.problem.net_dim), jnp.float32,
                                sharding=one_chip)
    text = jax.jit(lambda sp, nz, x: model.f_stacked(
        model.prepare_params_stacked(sp, nz), x)).lower(
            stacked, noise, rows).compile().as_text()
    kernels = set(re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                             r"custom_call_target=\"tpu_custom_call\"", text))
    assert kernels == {"mesh_rect"}, kernels
    outs = re.findall(r"= f32\[([\d,]*)\]\{[^}]*\} "
                      r"(?:reduce|dot|convolution)\(", text)
    assert [o for o in outs if o.startswith("100,")], outs   # per entry
    assert not [o for o in outs if o.startswith(f"{n_stack},")], outs


@pytest.mark.parametrize("n_stack", [3, P])
def test_stacked_head_rows_do_not_depend_on_stack_size(one_chip, n_stack,
                                                       monkeypatch):
    """Perturbation sharding evaluates 3-entry slices of the 11-entry stack
    (DESIGN.md §Distributed), so no reduction of the stacked forward may
    carry the stack axis: XLA on TPU tiles such an output by its size,
    which changes each entry's rounding with P."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    model = pinn.TensorPinn(pinn_config("hjb-20d", "tt"))
    stacked = jax.eval_shape(lambda: jax.tree.map(
        lambda a: jnp.stack([a] * n_stack),
        model.init(jax.random.PRNGKey(0))))
    stacked = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), stacked)
    rows = jax.ShapeDtypeStruct((100, model.problem.net_dim), jnp.float32,
                                sharding=one_chip)
    text = jax.jit(model.f_stacked).lower(stacked, rows).compile().as_text()
    outs = re.findall(r"= f32\[([\d,]*)\]\{[^}]*\} reduce\(", text)
    assert "100" in outs                    # the head, one entry at a time
    assert not [o for o in outs if o.startswith(f"{n_stack},")], outs
