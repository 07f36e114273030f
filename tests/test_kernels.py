"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle, swept
over shapes and dtypes.  Hypothesis property tests live in
tests/test_properties.py behind ``pytest.importorskip``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import photonic, tt
from repro.kernels import ops, ref


# ---------------------------------------------------------------- tt_contract

TT_CASES = [
    # (out, in, L, rank, batch)
    (64, 64, 2, 2, 16),
    (128, 96, 3, 4, 33),     # unaligned batch
    (1024, 1024, 4, 2, 64),  # the paper's TONN layer
    (256, 512, 4, 8, 7),
    (48, 60, 3, 16, 128),    # rank > unfolding rank (clamped internally)
    (1024, 1024, 4, 4, 21),  # rank 4, no interior 1: the dense body
]


@pytest.mark.parametrize("out_dim,in_dim,L,rank,batch", TT_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tt_contract_matches_ref(out_dim, in_dim, L, rank, batch, dtype):
    spec = tt.auto_factorize(out_dim, in_dim, L=L, max_rank=rank)
    cores = [c.astype(dtype) for c in tt.tt_init(jax.random.PRNGKey(0), spec)]
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, in_dim), dtype=dtype)
    y_ref = ref.tt_contract_ref(x, cores, spec)
    y_k = ops.tt_linear(x, cores, spec, mode="interpret")
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)


def test_tt_contract_batch_dims():
    """Leading batch dims of any rank are flattened and restored."""
    spec = tt.auto_factorize(32, 32, L=2, max_rank=4)
    cores = tt.tt_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 32))
    y = ops.tt_linear(x, cores, spec, mode="interpret")
    assert y.shape == (3, 5, 32)
    y_flat = ops.tt_linear(x.reshape(15, 32), cores, spec, mode="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_flat).reshape(3, 5, 32),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------- tt_contract_batched (ZO)

BATCHED_CASES = [
    # (out, in, L, rank, P, batch)
    (64, 64, 2, 2, 4, 16),
    (1024, 1024, 4, 2, 10, 32),  # the paper's TONN layer, N=10 SPSA samples
    (96, 48, 3, 4, 3, 33),       # unaligned batch
]


@pytest.mark.parametrize("out_dim,in_dim,L,rank,P,batch", BATCHED_CASES)
@pytest.mark.parametrize("shared_x", [True, False])
def test_tt_contract_batched_matches_stacked_matvec(out_dim, in_dim, L, rank,
                                                    P, batch, shared_x):
    """One launch over the (P, batch-tile) grid == P independent unfused
    chains, for both a shared input and per-perturbation activations."""
    from repro.kernels import tt_contract as ttc
    spec = tt.auto_factorize(out_dim, in_dim, L=L, max_rank=rank)
    keys = jax.random.split(jax.random.PRNGKey(0), P)
    stacks = tuple(
        jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
        for i in range(spec.L))
    shape = (batch, in_dim) if shared_x else (P, batch, in_dim)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    y_k = ttc.tt_contract_batched(x, stacks, spec, interpret=True)
    assert y_k.shape == (P, batch, out_dim)
    y_loop = jnp.stack([
        tt.tt_matvec([s[p] for s in stacks],
                     x if shared_x else x[p], spec)
        for p in range(P)])
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_loop),
                               atol=1e-5, rtol=1e-5)


def _paper_stacks(P):
    spec = tt.PAPER_TONN_SPEC
    keys = jax.random.split(jax.random.PRNGKey(0), P)
    return spec, tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                       for i in range(spec.L))


@pytest.mark.parametrize("batch", [37, 64], ids=["unaligned", "aligned"])
@pytest.mark.parametrize("shared_x", [True, False])
def test_tt_contract_kron_body_matches_ref_and_stacked_matvec(batch,
                                                              shared_x):
    """The paper spec takes the Kronecker body; it matches the jnp oracle
    and P independent unfused chains, shared and per-P x, and the P = 1
    entry point matches ``tt_contract_ref``."""
    from repro.kernels import tt_contract as ttc
    P = 3
    spec, stacks = _paper_stacks(P)
    assert ops.tt_path(spec, "interpret") == "kron"
    shape = (batch, spec.in_dim) if shared_x else (P, batch, spec.in_dim)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    y_k = ttc.tt_contract_batched(x, stacks, spec, interpret=True)
    assert y_k.shape == (P, batch, spec.out_dim)
    y_ref = ref.tt_contract_batched_ref(x, stacks, spec)
    y_loop = jnp.stack([
        tt.tt_matvec([s[p] for s in stacks], x if shared_x else x[p], spec)
        for p in range(P)])
    for want in (y_ref, y_loop):
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
    cores = [s[1] for s in stacks]
    x1 = x if shared_x else x[1]
    np.testing.assert_allclose(
        np.asarray(ops.tt_linear(x1, cores, spec, mode="interpret")),
        np.asarray(ref.tt_contract_ref(x1, cores, spec)),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shared_x", [True, False])
def test_tt_contract_kron_rows_do_not_depend_on_batch(shared_x):
    """Rows [:k] of a k-row call equal the same rows of a longer call, bit
    for bit: served-vs-direct and stack-slice parity rest on it."""
    from repro.kernels import tt_contract as ttc
    P = 2
    spec, stacks = _paper_stacks(P)
    long, k = 40, 5     # a looped chunk and a tail, against a tail alone
    shape = (long, spec.in_dim) if shared_x else (P, long, spec.in_dim)
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    full = np.asarray(ttc.tt_contract_batched(x, stacks, spec,
                                              interpret=True))
    xk = x[:k] if shared_x else x[:, :k]
    part = np.asarray(ttc.tt_contract_batched(xk, stacks, spec,
                                              interpret=True))
    np.testing.assert_array_equal(part, full[:, :k])


@pytest.mark.parametrize("spec,mode,want", [
    (tt.PAPER_TONN_SPEC, "interpret", "kron"),
    (tt.PAPER_TONN_SPEC, "pallas", "kron"),
    (tt.PAPER_TONN_SPEC, "ref", "ref"),
    (tt.auto_factorize(1024, 1024, L=4, max_rank=4), "interpret", "dense"),
    # an interior rank 1, but 64 columns do not tile a 128-lane vreg
    (tt.TTSpec((8, 8), (8, 8), (1, 1, 1)), "interpret", "dense"),
    (tt.auto_factorize(4096, 4096, L=4, max_rank=8), "interpret", "ref"),
], ids=["paper-interpret", "paper-pallas", "paper-ref", "rank4-1024",
        "untiled-split", "lm-sized"])
def test_tt_path_picks_body_from_spec(spec, mode, want):
    assert ops.tt_path(spec, mode) == want


def test_tt_linear_batched_dispatch_ref_equals_interpret():
    spec = tt.auto_factorize(32, 32, L=2, max_rank=4)
    stacks = [jnp.stack([c, 2.0 * c])
              for c in tt.tt_init(jax.random.PRNGKey(0), spec)]
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 32))
    y_ref = ops.tt_linear_batched(x, stacks, spec, mode="ref")
    y_int = ops.tt_linear_batched(x, stacks, spec, mode="interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_int),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("spec,want", [
    (tt.PAPER_TONN_SPEC, "interpret"),     # W is 4 MiB
    (tt.auto_factorize(4096, 4096, L=4, max_rank=8), "ref"),   # W is 64 MiB
], ids=["paper-tonn", "lm-sized"])
def test_tt_impl_takes_kernel_only_when_w_fits_vmem(spec, want):
    assert ops.tt_impl(spec, "interpret") == want
    assert ops.tt_impl(spec, "ref") == "ref"


# ------------------------------------ tt_contract_stencil (layer-1 prologue)

STENCIL_SPECS = [tt.PAPER_TONN_SPEC,                    # the Kronecker body
                 tt.auto_factorize(1024, 1024, L=4, max_rank=4)]   # dense


def _stencil_factors(spec, P, B, A, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), P)
    stacks = tuple(jnp.stack([tt.tt_init(k, spec)[i] for k in keys])
                   for i in range(spec.L))
    z0 = jax.random.normal(jax.random.PRNGKey(seed + 1), (P, B, spec.in_dim))
    hcols = 1e-2 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                     (P, A, spec.in_dim))
    return z0, hcols, stacks


@pytest.mark.parametrize("A", [21, 3])
@pytest.mark.parametrize("B", [100, 37])
@pytest.mark.parametrize("P", [1, 3])
@pytest.mark.parametrize("spec", STENCIL_SPECS, ids=["kron", "dense"])
def test_tt_contract_stencil_matches_rows_through_batched(spec, P, B, A):
    """The prologue launch (rows ``sin(z0 ± hcols)`` built in the kernel,
    B padded to whole sublanes a slot) equals the rows built in XLA
    (concat, ``fast_sin``, reshape) through ``tt_contract_batched``.  Not
    to the bit on the CPU: XLA:CPU contracts ``fast_sin``'s multiply-adds
    into FMAs differently inside and outside the kernel (≈ 2 ulp of a
    sine), which moves y by at most 4.8e-7 here; 1e-6 holds."""
    from repro.core import fastmath
    from repro.kernels import tt_contract as ttc
    z0, hcols, stacks = _stencil_factors(spec, P, B, A)
    y = np.asarray(ttc.tt_contract_stencil(z0, hcols, stacks, spec,
                                           interpret=True))
    bp = -(-B // 8) * 8
    assert y.shape == (P, (2 * A + 1) * bp, spec.out_dim)
    y = y.reshape(P, 2 * A + 1, bp, spec.out_dim)
    z = jnp.concatenate([z0[:, None], z0[:, None] + hcols[:, :, None],
                         z0[:, None] - hcols[:, :, None]], axis=1)
    a = fastmath.fast_sin(z).reshape(P, (2 * A + 1) * B, spec.in_dim)
    want = np.asarray(ttc.tt_contract_batched(a, stacks, spec,
                                              interpret=True))
    np.testing.assert_allclose(y[:, :, :B],
                               want.reshape(P, 2 * A + 1, B, spec.out_dim),
                               atol=1e-6, rtol=0)
    assert np.isfinite(y).all()               # the pad rows too


@pytest.mark.parametrize("spec", STENCIL_SPECS, ids=["kron", "dense"])
def test_tt_contract_stencil_rows_do_not_depend_on_stack_size(spec):
    """Entry p of an 11-entry launch equals the 1-entry launch on its
    cores, bit for bit: perturbation sharding evaluates slices of the
    stack (DESIGN.md §Distributed)."""
    from repro.kernels import tt_contract as ttc
    P, B, A = 11, 37, 3
    z0, hcols, stacks = _stencil_factors(spec, P, B, A)
    full = np.asarray(ttc.tt_contract_stencil(z0, hcols, stacks, spec,
                                              interpret=True))
    for p in (0, 6, 10):
        one = ttc.tt_contract_stencil(z0[p:p + 1], hcols[p:p + 1],
                                      tuple(s[p:p + 1] for s in stacks),
                                      spec, interpret=True)
        np.testing.assert_array_equal(np.asarray(one)[0], full[p])


@pytest.mark.parametrize("mode,kernel_mode,quant,want", [
    ("tonn", "pallas", None, True),
    ("tonn", "interpret", None, True),
    ("tt", "pallas", None, True),
    ("tt", "interpret", None, True),
    ("tonn", "ref", None, False),
    ("tt", "ref", None, False),
    ("tonn", "pallas", "int8", False),
    ("tt", "interpret", "int8", False),
    ("onn", "pallas", None, False),
    ("dense", "pallas", None, False),
])
def test_stencil_prologue_is_taken_by_tt_layers_on_the_kernel(
        mode, kernel_mode, quant, want, monkeypatch):
    """Which configurations build layer 1's stencil rows inside the
    kernel: tt and tonn with the paper spec on the unquantized kernel;
    not onn or dense (per-entry matmuls), the quantized kernel or ref."""
    from repro.configs.hjb_pinn import pinn_config
    from repro.core import pinn
    from repro.kernels import quant as quant_lib
    monkeypatch.setenv("REPRO_KERNEL_MODE", kernel_mode)
    q = (quant_lib.QuantConfig(enabled=True, dtype=quant) if quant
         else quant_lib.QuantConfig())
    model = pinn.TensorPinn(pinn_config("hjb-20d", mode, quant=q))
    if mode in ("tt", "tonn"):
        assert model.specs[1] == tt.PAPER_TONN_SPEC
    assert model.stencil_prologue() is want


def _deep_qr_layout(ports: int = 40):
    """A Givens-QR (Reck) layout: 2·ports − 3 levels, not rectangular."""
    u = np.linalg.qr(np.random.RandomState(3).randn(ports, ports))[0]
    return photonic.decompose_orthogonal(u)


@pytest.mark.parametrize("layout,want,path", [
    (photonic.rectangular_layout(16), "interpret", "onehot"),   # tonn core
    # an onn-sized rectangular mesh: too deep to unroll, rolled instead
    (photonic.rectangular_layout(ops.MESH_KERNEL_MAX_LEVELS + 4),
     "interpret", "rect"),
    # a deep mesh of another layout: the jnp path
    (_deep_qr_layout(ops.MESH_KERNEL_MAX_LEVELS // 2 + 8)[0], "ref", "ref"),
], ids=["tonn-core", "onn-rect", "deep-qr"])
def test_mesh_impl_takes_kernel_only_for_shallow_meshes(layout, want, path):
    """The kernel takes shallow meshes (one-hot body) and deep rectangular
    ones (``mesh_rect``); other deep layouts take the jnp path."""
    assert ops.mesh_impl(layout, "interpret") == want
    assert ops.mesh_path(layout, "interpret") == path
    assert ops.mesh_impl(layout, "ref") == "ref"
    assert ops.mesh_path(layout, "ref") == "ref"


# ------------------------------------------------- mesh_apply_stacked (ZO)

MESH_CASES = [
    # (ports, S, batch, shared_x, transpose)
    (8, 4, 16, True, False),     # a TT-core-sized mesh, shared identity feed
    (8, 4, 16, False, True),     # per-perturbation activations, Uᵀ
    (16, 11, 33, True, False),   # N=10 SPSA stack + base, unaligned batch
    (5, 3, 7, True, True),       # odd ports (unpaired wires every level)
]


@pytest.mark.parametrize("ports,S,batch,shared_x,transpose", MESH_CASES)
def test_mesh_apply_stacked_kernel_matches_ref(ports, S, batch, shared_x,
                                               transpose):
    """Pallas kernel (interpret) vs the jnp gather reference: the one-hot
    permutation matmul keeps the chain f32-identical."""
    lay = photonic.rectangular_layout(ports)
    key = jax.random.PRNGKey(0)
    phs = jax.random.normal(key, (S,) + lay.phase_shape())
    d = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (ports,)))
    d = jnp.where(d == 0, 1.0, d)
    shape = (batch, ports) if shared_x else (S, batch, ports)
    x = jax.random.normal(jax.random.fold_in(key, 2), shape)
    y_ref = photonic.mesh_apply_stacked(lay, phs, d, x, transpose=transpose)
    y_k = ops.mesh_apply_stacked(lay, phs, d, x, transpose=transpose,
                                 mode="interpret")
    assert y_k.shape == (S, batch, ports)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_ref))


def test_mesh_apply_stacked_kernel_qr_layout_and_stacked_diag():
    """Kernel path on a Givens-QR (ragged-level) layout with a stacked diag."""
    u = np.linalg.qr(np.random.RandomState(1).randn(8, 8))[0]
    lay, ph, d = photonic.decompose_orthogonal(u)
    S = 3
    phs = jnp.stack([ph, 1.1 * ph, 0.9 * ph])
    ds = jnp.stack([d] * S)
    x = jax.random.normal(jax.random.PRNGKey(2), (9, 8))
    y_ref = photonic.mesh_apply_stacked(lay, phs, ds, x)
    y_k = ops.mesh_apply_stacked(lay, phs, ds, x, mode="interpret")
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_ref))


def test_mesh_apply_stacked_deep_mesh_falls_back_to_ref():
    """Levels above MESH_KERNEL_MAX_LEVELS in a layout that is not
    rectangular must silently take the jnp path in every mode — no
    unrollable kernel is built."""
    lay, ph, d = _deep_qr_layout(ops.MESH_KERNEL_MAX_LEVELS // 2 + 8)
    ports = lay.ports
    assert lay.levels > ops.MESH_KERNEL_MAX_LEVELS
    phs = jnp.stack([ph, 0.9 * ph])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, ports))
    y_i = ops.mesh_apply_stacked(lay, phs, d, x, mode="interpret")
    y_r = ops.mesh_apply_stacked(lay, phs, d, x, mode="ref")
    np.testing.assert_array_equal(np.asarray(y_i), np.asarray(y_r))


RECT_CASES = [
    # (ports, S, batch, shared_x, transpose)
    (64, 3, 64, True, True),      # densify: Vᵀ on a shared identity feed
    (64, 3, 21, False, False),    # U on per-entry rows, off the row chunk
    (32, 11, 40, True, False),    # N=10 SPSA stack + base, shared rows
    (33, 2, 9, False, True),      # odd ports: padded wires, odd level count
]


@pytest.mark.parametrize("ports,S,batch,shared_x,transpose", RECT_CASES)
def test_mesh_rect_kernel_matches_ref(ports, S, batch, shared_x, transpose):
    """The deep-mesh body, forced at a small width in interpret mode,
    against the jnp gather path: the same per-level arithmetic in the same
    order, so f32-identical; and entry s of the stack is the unstacked
    apply of entry s."""
    from repro.kernels import mesh_apply
    lay = photonic.rectangular_layout(ports)
    assert mesh_apply.is_rectangular(lay)
    key = jax.random.PRNGKey(ports)
    phs = jax.random.normal(key, (S,) + lay.phase_shape())
    d = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (ports,)))
    d = jnp.where(d == 0, 1.0, d)
    shape = (batch, ports) if shared_x else (S, batch, ports)
    x = jax.random.normal(jax.random.fold_in(key, 2), shape)
    y_ref = photonic.mesh_apply_stacked(lay, phs, d, x, transpose=transpose)
    y_k = mesh_apply.mesh_apply_rect_pallas(lay, phs, d, x,
                                            transpose=transpose,
                                            interpret=True)
    assert y_k.shape == (S, batch, ports)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_ref))
    s = S - 1
    y_s = mesh_apply.mesh_apply_rect_pallas(
        lay, phs[s:], d, x if shared_x else x[s:], transpose=transpose,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(y_s[0]), np.asarray(y_k[s]))


# ------------------------------------------------------------ flash attention

FA_CASES = [
    # (B, H, KH, Sq, Sk, D, causal, window)
    (1, 4, 4, 128, 128, 64, True, None),     # MHA causal
    (2, 8, 2, 256, 256, 64, True, None),     # GQA
    (1, 8, 8, 200, 200, 32, True, None),     # unaligned seq
    (2, 4, 2, 256, 256, 64, True, 100),      # sliding window
    (1, 4, 2, 32, 256, 64, True, None),      # chunked prefill (Sq < Sk)
    (1, 4, 1, 1, 300, 64, True, None),       # single-query decode
    (1, 4, 4, 128, 128, 64, False, None),    # bidirectional (encoder)
]


@pytest.mark.parametrize("B,H,KH,Sq,Sk,D,causal,window", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KH, Sq, Sk, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype=dtype)
    k = jax.random.normal(ks[1], (B, KH, Sk, D), dtype=dtype)
    v = jax.random.normal(ks[2], (B, KH, Sk, D), dtype=dtype)
    o_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
    o_k = ops.attention(q, k, v, causal=causal, window=window, mode="interpret")
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_size_invariance():
    """Output must not depend on the (bq, bk) tiling."""
    from repro.kernels.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 192, 32))
    k = jax.random.normal(ks[1], (1, 2, 192, 32))
    v = jax.random.normal(ks[2], (1, 2, 192, 32))
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_attention_rows_are_convex_combinations():
    """Property: each output row lies in the convex hull of V rows →
    max |out| <= max |v| (softmax weights sum to 1)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    k = jax.random.normal(ks[1], (1, 2, 64, 16))
    v = jax.random.normal(ks[2], (1, 2, 64, 16))
    o = ops.attention(q, k, v, causal=True, mode="interpret")
    assert float(jnp.max(jnp.abs(o))) <= float(jnp.max(jnp.abs(v))) + 1e-5
