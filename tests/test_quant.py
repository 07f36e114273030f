"""Quantization layer (DESIGN.md §Quantization): block-scaled primitives,
quantized-kernel vs fake-quant-oracle parity, DAC phase quantization, QAT
threading through the PINN/ZO stack, and the f32 off-path invariant
(quantization disabled == bit-identical to the unquantized build)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pinn, tt, zoo
from repro.kernels import ops, quant, ref
from repro.kernels import tt_contract as ttc

INT8 = quant.QuantConfig(enabled=True, dtype="int8", block=32)
FP8 = quant.QuantConfig(enabled=True, dtype="fp8_e4m3", block=32)
QCFGS = [INT8, FP8]


# ---------------------------------------------------------------- primitives

@pytest.mark.parametrize("qcfg", QCFGS, ids=lambda q: q.dtype)
@pytest.mark.parametrize("shape", [(64,), (2, 4, 8, 2), (37,), (1,)])
def test_blockwise_roundtrip_shape_and_padding(qcfg, shape):
    """quantize→dequantize recovers shape exactly (incl. non-block-multiple
    sizes via zero padding) and values to 8-bit block-scaled accuracy."""
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(0), shape)
    q, scales = quant.quantize_blockwise(x, qcfg)
    n = int(np.prod(shape))
    padded = -(-n // qcfg.block) * qcfg.block
    assert q.shape == (padded,) and scales.shape == (padded // qcfg.block,)
    y = quant.dequantize_blockwise(q, scales, x.shape, qcfg)
    assert y.shape == x.shape
    # int8: rounding err ≤ scale/2 = absmax/254; fp8-e4m3: 3 mantissa bits
    # → ≤ 2^-4 relative (per element, bounded here by the block absmax)
    eps = 1 / 254 if qcfg.dtype == "int8" else 1 / 16
    blk_max = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(y - x))) <= blk_max * eps + 1e-7


@pytest.mark.parametrize("qcfg", QCFGS, ids=lambda q: q.dtype)
def test_fake_quant_idempotent(qcfg):
    """Q(Q(x)) == Q(x) bitwise: accidental double application can't drift
    (the ops/photonic hooks rely on this)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    fq = quant.fake_quant(x, qcfg)
    np.testing.assert_array_equal(np.asarray(quant.fake_quant(fq, qcfg)),
                                  np.asarray(fq))
    assert (np.asarray(fq) != np.asarray(x)).any()   # it actually quantizes


def test_fake_quant_disabled_is_identity():
    x = jax.random.normal(jax.random.PRNGKey(2), (17,))
    off = quant.QuantConfig(enabled=False)
    assert quant.fake_quant(x, off) is x
    phase_only = quant.QuantConfig(enabled=True, dtype=None, phase_bits=6)
    assert quant.fake_quant(x, phase_only) is x
    assert not phase_only.weights and phase_only.phases


def test_block_scales_are_per_block():
    """A huge value in one block must not destroy the resolution of the
    others — the whole point of block scaling over per-tensor absmax."""
    x = jnp.concatenate([jnp.full((32,), 1000.0),
                         0.01 * jnp.arange(32, dtype=jnp.float32)])
    y = quant.fake_quant(x, INT8)
    # second block keeps ~1e-4 resolution despite the 1000x outlier block
    assert float(jnp.max(jnp.abs(y[32:] - x[32:]))) < 2e-3


def test_quantize_phases_grid_and_idempotence():
    bits = 6
    step = 2 * np.pi / (1 << bits)
    ph = jax.random.uniform(jax.random.PRNGKey(3), (4, 8),
                            minval=-np.pi, maxval=np.pi)
    pq = quant.quantize_phases(ph, bits)
    codes = np.asarray(pq) / step
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(quant.quantize_phases(pq, bits)),
                                  np.asarray(pq))
    assert float(jnp.max(jnp.abs(pq - ph))) <= step / 2 + 1e-6


def test_quant_config_validation_and_tag():
    with pytest.raises(ValueError, match="unknown quant dtype"):
        quant.QuantConfig(dtype="int4")
    with pytest.raises(ValueError, match="phase_bits"):
        quant.QuantConfig(phase_bits=0)
    assert quant.QuantConfig(enabled=False).tag() == ""
    assert INT8.tag() == "int8b32"
    full = quant.QuantConfig(enabled=True, dtype="fp8_e4m3", block=16,
                             phase_bits=8)
    assert full.tag() == "fp8_e4m3b16+pb8"
    assert quant.quantized_bytes_per_param(INT8) == 1.125
    assert quant.quantized_bytes_per_param(quant.QuantConfig()) == 4.0


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("qcfg", QCFGS, ids=lambda q: q.dtype)
@pytest.mark.parametrize("shared_x", [True, False])
def test_quant_kernel_matches_fake_quant_oracle(qcfg, shared_x):
    """The quantized Pallas kernel (interpret) dequantizes the exact
    ``quantize_blockwise`` output the jnp oracle fake-quants — parity to
    the repo's documented f32 kernel floor (1e-5)."""
    spec = tt.auto_factorize(96, 48, L=3, max_rank=4)
    P, B = 5, 33
    keys = jax.random.split(jax.random.PRNGKey(0), P)
    stacks = tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                   for i in range(spec.L))
    shape = (B, spec.in_dim) if shared_x else (P, B, spec.in_dim)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    y_ref = ref.tt_contract_batched_quant_ref(x, stacks, spec, qcfg)
    y_k = ttc.tt_contract_batched_quant(x, stacks, spec, qcfg,
                                        interpret=True)
    assert y_k.shape == (P, B, spec.out_dim)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    # and the quantization is visible vs the unquantized chain
    y_f32 = ref.tt_contract_batched_ref(x, stacks, spec)
    assert (np.asarray(y_ref) != np.asarray(y_f32)).any()


@pytest.mark.parametrize("qcfg", QCFGS, ids=lambda q: q.dtype)
def test_quant_kernel_kron_body_matches_fake_quant_oracle(qcfg):
    """The paper spec dequantizes, then takes the Kronecker body."""
    spec = tt.PAPER_TONN_SPEC
    assert ops.tt_path(spec, "interpret") == "kron"
    P, B = 3, 21
    keys = jax.random.split(jax.random.PRNGKey(4), P)
    stacks = tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                   for i in range(spec.L))
    x = jax.random.normal(jax.random.PRNGKey(5), (P, B, spec.in_dim))
    y_ref = ref.tt_contract_batched_quant_ref(x, stacks, spec, qcfg)
    y_k = ttc.tt_contract_batched_quant(x, stacks, spec, qcfg,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("qcfg", QCFGS, ids=lambda q: q.dtype)
def test_ops_dispatch_quant_ref_equals_interpret(qcfg):
    """ops.tt_linear[_batched] with quant: the ref (fake-quant jnp) and
    interpret (narrow-dtype kernel) dispatch arms agree."""
    spec = tt.auto_factorize(64, 64, L=2, max_rank=2)
    P, B = 3, 16
    keys = jax.random.split(jax.random.PRNGKey(2), P)
    stacks = tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                   for i in range(spec.L))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, spec.in_dim))
    yb_ref = ops.tt_linear_batched(x, stacks, spec, mode="ref", quant=qcfg)
    yb_int = ops.tt_linear_batched(x, stacks, spec, mode="interpret",
                                   quant=qcfg)
    np.testing.assert_allclose(np.asarray(yb_int), np.asarray(yb_ref),
                               atol=1e-5, rtol=1e-5)
    cores = [s[0] for s in stacks]
    y_ref = ops.tt_linear(x, cores, spec, mode="ref", quant=qcfg)
    y_int = ops.tt_linear(x, cores, spec, mode="interpret", quant=qcfg)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


def test_mesh_apply_stacked_quantizes_commanded_phases():
    """ops.mesh_apply_stacked with phase_bits equals applying the DAC snap
    to the phases first — in every dispatch mode."""
    from repro.core import photonic
    layout = photonic.rectangular_layout(8)
    S = 3
    phases = jax.random.normal(jax.random.PRNGKey(4),
                               (S,) + layout.phase_shape())
    diag = jnp.ones((8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 8))
    qcfg = quant.QuantConfig(enabled=True, dtype=None, phase_bits=6)
    snapped = quant.quantize_phases(phases, 6)
    for mode in ("ref", "interpret"):
        y_q = ops.mesh_apply_stacked(layout, phases, diag, x, mode=mode,
                                     quant=qcfg)
        y_snap = ops.mesh_apply_stacked(layout, snapped, diag, x, mode=mode)
        np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_snap))


# -------------------------------------------------- kernel_mode validation

def test_kernel_mode_rejects_unknown_value(monkeypatch):
    """A typo'd REPRO_KERNEL_MODE must raise with the allowed values, not
    silently dispatch to the compiled-Pallas branch."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "reff")
    with pytest.raises(ValueError, match="pallas, interpret, ref"):
        ops.kernel_mode()
    for mode in ops.KERNEL_MODES:
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
        assert ops.kernel_mode() == mode
    monkeypatch.delenv("REPRO_KERNEL_MODE")
    assert ops.kernel_mode() in ops.KERNEL_MODES   # backend default


# ----------------------------------------------------- PINN / QAT threading

def _models(mode, qcfg, pde="heat-10d"):
    base = pinn.PINNConfig(hidden=64, mode=mode, tt_rank=2, tt_L=3, pde=pde,
                           deriv="fd_fast", use_fused_kernel=True)
    return pinn.TensorPinn(base), pinn.TensorPinn(
        dataclasses.replace(base, quant=qcfg))


@pytest.mark.parametrize("mode", ["tt", "tonn", "onn"])
def test_f32_off_path_bit_identical(mode):
    """The f32 invariant: quant disabled (explicitly or by default) gives
    bit-identical u-stencils and stacked losses to the unquantized model."""
    m0, _ = _models(mode, INT8)
    mdis = pinn.TensorPinn(dataclasses.replace(
        m0.cfg, quant=quant.QuantConfig(enabled=False, dtype="int8",
                                        phase_bits=4)))
    key = jax.random.PRNGKey(0)
    params = m0.init(key)
    xt = m0.problem.sample_collocation(jax.random.fold_in(key, 1), 16)
    v0 = m0.fd_u_stencil(m0.prepare_params(params, None)[0], xt, m0.fd_step)
    v1 = mdis.fd_u_stencil(mdis.prepare_params(params, None)[0], xt,
                           mdis.fd_step)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    P = 3
    sp = jax.tree.map(lambda l: jnp.broadcast_to(l, (P,) + l.shape), params)
    np.testing.assert_array_equal(
        np.asarray(pinn.residual_losses_stacked(m0, sp, xt)),
        np.asarray(pinn.residual_losses_stacked(mdis, sp, xt)))


@pytest.mark.parametrize("mode", ["tt", "tonn", "onn"])
def test_qat_stacked_matches_sequential(mode):
    """Under quantization the fused stacked loss still matches the scalar
    loss per stacked entry (same FD-noise-floor contract as f32 — the
    quantized weights are identical in both paths, so the documented
    1/h²-amplified tolerance carries over)."""
    qcfg = dataclasses.replace(INT8, phase_bits=6)
    _, mq = _models(mode, qcfg)
    key = jax.random.PRNGKey(1)
    params = mq.init(key)
    xt = mq.problem.sample_collocation(jax.random.fold_in(key, 2), 24)
    P = 4
    sp = jax.tree.map(lambda l: jnp.broadcast_to(l, (P,) + l.shape), params)
    stacked = np.asarray(pinn.residual_losses_stacked(mq, sp, xt))
    seq = float(pinn.residual_loss(mq, params, xt))
    np.testing.assert_allclose(stacked, np.full(P, seq), rtol=1e-1)


def test_qat_zo_step_runs_and_preserves_buffers():
    """Quantization lives inside the loss: a ZO step under QAT runs through
    the unchanged zoo protocol and the ±1 photonic diag buffers stay
    bit-frozen (trainable_mask semantics are orthogonal to quant)."""
    _, mq = _models("tonn", dataclasses.replace(INT8, phase_bits=6))
    key = jax.random.PRNGKey(2)
    params = mq.init(key)
    xt = mq.problem.sample_collocation(jax.random.fold_in(key, 3), 16)
    mask = mq.trainable_mask(params)
    scfg = zoo.SPSAConfig(num_samples=4, mu=0.01)
    state = zoo.ZOState.create(7)
    lf = lambda p: pinn.residual_loss(mq, p, xt)
    blf = lambda sp: pinn.residual_losses_stacked(mq, sp, xt)
    new_params, _, loss = zoo.zo_signsgd_step(
        lf, params, state, lr=1e-3, cfg=scfg, batched_loss_fn=blf,
        trainable_mask=mask)
    assert np.isfinite(float(loss))
    for i in range(len(mq.specs)):
        for k in range(mq.specs[i].L):
            for b in ("diag_u", "diag_v"):
                np.testing.assert_array_equal(
                    np.asarray(new_params[f"pcores{i}"][k][b]),
                    np.asarray(params[f"pcores{i}"][k][b]))


def test_phase_bits_change_tonn_forward_only_when_enabled():
    """DAC quantization bites the tonn mesh phases (and only when
    enabled)."""
    base = pinn.PINNConfig(hidden=64, mode="tonn", tt_rank=2, tt_L=3,
                           pde="heat-10d")
    m0 = pinn.TensorPinn(base)
    mq = pinn.TensorPinn(dataclasses.replace(
        base, quant=quant.QuantConfig(enabled=True, dtype=None,
                                      phase_bits=4)))
    key = jax.random.PRNGKey(4)
    params = m0.init(key)
    xt = m0.problem.sample_collocation(jax.random.fold_in(key, 5), 8)
    u0, uq = np.asarray(m0.u(params, xt)), np.asarray(mq.u(params, xt))
    assert (u0 != uq).any()
    # 4 bits is coarse but the forward stays sane
    assert np.all(np.isfinite(uq))


def test_config_meta_roundtrip_with_quant():
    """Checkpoint metadata: QuantConfig survives the JSON roundtrip like
    NoiseModel, and unknown future fields are ignored."""
    qcfg = quant.QuantConfig(enabled=True, dtype="fp8_e4m3", block=16,
                             phase_bits=8)
    cfg = pinn.PINNConfig(hidden=32, mode="tt", tt_rank=2, tt_L=3,
                          quant=qcfg)
    meta = json.loads(json.dumps(pinn.config_to_meta(cfg)))
    assert pinn.config_from_meta(meta) == cfg
    meta["quant"]["from_the_future"] = True
    assert pinn.config_from_meta(meta) == cfg
    # old checkpoints without a quant key default to disabled
    del meta["quant"]
    assert pinn.config_from_meta(meta).quant == quant.QuantConfig(
        enabled=False)
