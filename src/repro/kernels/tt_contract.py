"""Fused TT-linear Pallas kernels — the TONN compute primitive.

The paper's photonic TONN-1 design (Fig. 2) multiplies an input by ALL
TT-cores in one optical pass: intermediates never leave the chip.  The TPU
analogue (DESIGN.md §2): HBM carries the activations and the tiny TT-cores,
and everything in between stays in VMEM.

TPU layout.  The per-core chain of ``tt_matvec`` contracts dims of r·n_k ≈ 8
and rotates the feature index at every step: a relayout of sub-tile minor
dims that Mosaic refuses and that the 128×128 MXU could not use anyway.  So
each kernel rebuilds the dense ``W = W(cores)`` (M × N) in a VMEM scratch,
with 2-D matmuls only, once per core set, and then streams the batch tiles
through ONE MXU matmul ``y = x @ Wᵀ`` each:

  * core k arrives as a flat row ``g (1, |G_k|)`` in its natural
    (r, m, n, r') order;
  * for each rank pair (a, b) the slab ``Z[μ, j] = G_k[a, μ, ν_k(j), b]``
    (m_k × N) is ``(g ⊙ mask_ab) @ C_k``, where the static one-hot ``C_k``
    sends a core entry to every input column whose k-th digit is its n index;
  * ``E_ab = R_k @ Z`` repeats the slab over the output rows (``R_k`` one-hot
    on the k-th output digit), and the rank sum ``W = Σ_paths ⊙_k E_k``
    contracts the chain elementwise, one block of output rows at a time.

Every matmul runs at ``Precision.HIGHEST``: the one-hot products are then
exact, and the FD residual, which amplifies rounding in u by 1/h²
(DESIGN.md §Perf), sees f32 arithmetic as on the jnp oracle.

HBM traffic per call is the x tiles, the y tiles, the cores and the one-hot
tables; the tables are read once per call, whatever the batch.

``tt_contract_batched`` adds a leading perturbation axis P: grid
``(P, batch-tiles)``, one core set per ``p`` and W rebuilt at the first batch
tile of every ``p``, so an entire ZO loss sweep (all N+1 perturbed models) is
ONE launch.  x may be shared across P (its index map ignores p) or carry its
own P axis.  ``tt_contract`` is the P = 1 case.  ``fits_vmem`` says whether a
spec's W and tables fit the kernel's VMEM budget; ``repro.kernels.ops`` sends
larger specs to the jnp chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tt as tt_lib
from repro.kernels import quant as quant_lib

__all__ = ["tt_contract", "tt_contract_batched",
           "tt_contract_batched_quant", "fits_vmem"]

_HIGHEST = jax.lax.Precision.HIGHEST
# x + y tiles, double-buffered, per batch row budget
_TILE_BUDGET_BYTES = 8 * 2**20
# W scratch + one-hot tables (double-buffered) + build temporaries
_VMEM_BUDGET_BYTES = 48 * 2**20
_VMEM_LIMIT_BYTES = 100 * 2**20
# output rows of W built per block (bounds the build temporaries)
_W_ROW_BLOCK = 256


def _dot(a: jax.Array, b: jax.Array, trans_b: bool = False) -> jax.Array:
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _digit(modes: tuple, k: int) -> np.ndarray:
    """k-th mixed-radix digit (most significant first) of every index."""
    idx = np.arange(int(np.prod(modes)))
    return (idx // int(np.prod(modes[k + 1:]))) % modes[k]


@functools.lru_cache(maxsize=None)
def _tables(spec: tt_lib.TTSpec, flat_lens: tuple) -> tuple:
    """Static one-hot tables per core: ``R (M, m)``, ``C (F, N)`` and
    ``masks (r·r', m, F)``, with F the (possibly padded) flat core length."""
    out = []
    for k, shape in enumerate(spec.core_shapes):
        r, m, n, rn = shape
        size = int(np.prod(shape))
        a, mu, nu, b = np.unravel_index(np.arange(size), shape)
        rows = (_digit(spec.out_modes, k)[:, None]
                == np.arange(m)[None]).astype(np.float32)
        cols = np.zeros((flat_lens[k], spec.in_dim), np.float32)
        cols[:size] = nu[:, None] == _digit(spec.in_modes, k)[None]
        masks = np.zeros((r * rn, m, flat_lens[k]), np.float32)
        masks[a * rn + b, mu, np.arange(size)] = 1.0
        out.append((rows, cols, masks))
    return tuple(out)


def _table_bytes(spec: tt_lib.TTSpec, flat_lens: tuple) -> int:
    lanes = lambda d: -(-d // 128) * 128
    total = 0
    for (r, m, _, rn), f in zip(spec.core_shapes, flat_lens):
        total += spec.out_dim * lanes(m) + f * lanes(spec.in_dim) \
            + r * rn * 8 * lanes(f)
    return 4 * total


def fits_vmem(spec: tt_lib.TTSpec) -> bool:
    """True iff W, the one-hot tables and the build temporaries fit VMEM."""
    flat = tuple(int(np.prod(s)) for s in spec.core_shapes)
    w_bytes = 4 * spec.out_dim * spec.in_dim
    rank = max(spec.ranks)
    build = 4 * min(spec.out_dim, _W_ROW_BLOCK) * spec.in_dim * (2 * rank + 2)
    return w_bytes + 2 * _table_bytes(spec, flat) + build \
        <= _VMEM_BUDGET_BYTES


def _row_block(m_dim: int) -> int:
    if m_dim <= _W_ROW_BLOCK:
        return m_dim
    for tm in range(_W_ROW_BLOCK, 7, -8):
        if m_dim % tm == 0:
            return tm
    return m_dim


def _batch_tile(rows: int, spec: tt_lib.TTSpec) -> int:
    """Balanced batch tile: as many tiles as the budget needs, each a
    multiple of 16 rows (bf16 tiling), or the whole batch if it fits."""
    per_row = 2 * 4 * (spec.in_dim + spec.out_dim)
    cap = max(16, (_TILE_BUDGET_BYTES // per_row) // 16 * 16)
    if rows <= cap:
        return rows
    n_tiles = -(-rows // cap)
    return -(-(-(-rows // n_tiles)) // 16) * 16


def _build_w(spec: tt_lib.TTSpec, cores: list, tables: list, w_ref) -> None:
    """W (M × N) from the flat core rows, into the VMEM scratch."""
    slabs = []
    for k, (r, m, _, rn) in enumerate(spec.core_shapes):
        _, cols_ref, masks_ref = tables[k]
        g = jnp.broadcast_to(cores[k], (m, cores[k].shape[-1]))
        cols = cols_ref[...]
        slabs.append([[_dot(g * masks_ref[a * rn + b], cols)
                       for b in range(rn)] for a in range(r)])
    tm = _row_block(spec.out_dim)
    for r0 in range(0, spec.out_dim, tm):
        acc = None                       # one (tm, N) block per open rank
        for k, (r, _, _, rn) in enumerate(spec.core_shapes):
            rows = tables[k][0][r0:r0 + tm, :]
            e = [[_dot(rows, slabs[k][a][b]) for b in range(rn)]
                 for a in range(r)]
            if acc is None:
                acc = e[0]
                continue
            nxt = []
            for b in range(rn):
                t = acc[0] * e[0][b]
                for a in range(1, r):
                    t = t + acc[a] * e[a][b]
                nxt.append(t)
            acc = nxt
        w_ref[r0:r0 + tm, :] = acc[0]


def _kernel(spec: tt_lib.TTSpec, shared_x: bool, quantized: bool, *refs):
    L = spec.L
    x_ref, refs = refs[0], refs[1:]
    if quantized:
        q_refs, s_refs, e_refs = refs[:L], refs[L:2 * L], refs[2 * L:3 * L]
        refs = refs[3 * L:]
    else:
        c_refs, refs = refs[:L], refs[L:]
    tables = [refs[3 * k:3 * k + 3] for k in range(L)]
    o_ref, w_ref = refs[3 * L:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        if quantized:
            # dequantize in VMEM: each code times its block's f32 scale,
            # the scale spread over its block by a one-hot matmul (exact)
            cores = [q_refs[k][0].astype(jnp.float32)
                     * _dot(s_refs[k][0], e_refs[k][...]) for k in range(L)]
        else:
            cores = [c[0].astype(jnp.float32) for c in c_refs]
        _build_w(spec, cores, tables, w_ref)

    x = x_ref[...] if shared_x else x_ref[0]
    y = _dot(x.astype(jnp.float32), w_ref[...], trans_b=True)
    o_ref[0] = y.astype(o_ref.dtype)


def _split_batch_axes(x: jax.Array, P: int, spec: tt_lib.TTSpec,
                      shared_x: bool | None):
    """Resolve the ``shared_x`` flag and flatten extra batch axes.

    ``shared_x=None`` keeps the legacy inference — 2-D x is shared, any
    higher rank is per-perturbation with a leading P axis.  An explicit
    flag disambiguates multi-axis inputs (e.g. a shared coefficients ×
    points grid ``(C, B, N)`` where C happens to equal P).  Returns
    ``(xf, batch_shape, shared)`` with xf rank 2 (shared) or 3 (per-P).
    """
    if shared_x is None:
        shared_x = x.ndim == 2
    if shared_x:
        batch_shape = x.shape[:-1]
        return x.reshape(-1, spec.in_dim), batch_shape, True
    if x.shape[0] != P:
        raise ValueError(f"x leading axis {x.shape[0]} != core stack P={P}")
    batch_shape = x.shape[1:-1]
    return x.reshape(P, -1, spec.in_dim), batch_shape, False


def _launch(x: jax.Array, core_args: list, core_specs: list,
            spec: tt_lib.TTSpec, P: int, flat_lens: tuple, shared_x: bool,
            quantized: bool, interpret: bool) -> jax.Array:
    """One ``pallas_call`` over the ``(P, batch-tiles)`` grid."""
    B = x.shape[-2]
    bt = _batch_tile(B, spec)
    const = lambda a: pl.BlockSpec(a.shape, lambda p, i: (0,) * a.ndim)
    tables = [jnp.asarray(t) for tab in _tables(spec, flat_lens) for t in tab]
    if shared_x:
        x_spec = pl.BlockSpec((bt, spec.in_dim), lambda p, i: (i, 0))
    else:
        x_spec = pl.BlockSpec((1, bt, spec.in_dim), lambda p, i: (p, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, spec, shared_x, quantized),
        grid=(P, pl.cdiv(B, bt)),
        in_specs=[x_spec] + core_specs + [const(t) for t in tables],
        out_specs=pl.BlockSpec((1, bt, spec.out_dim), lambda p, i: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, B, spec.out_dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((spec.out_dim, spec.in_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="tt_contract_quant" if quantized else "tt_contract",
    )(x, *core_args, *tables)


def _row_spec(length: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, length), lambda p, i: (p, 0, 0))


@functools.partial(jax.jit, static_argnames=("spec", "interpret", "shared_x"))
def tt_contract_batched(x: jax.Array, cores: tuple, spec: tt_lib.TTSpec,
                        interpret: bool = False,
                        shared_x: bool | None = None) -> jax.Array:
    """``y[p] = x[p] @ W(cores[p])^T`` for P stacked core-sets, one launch.

    cores: tuple of ``(P, r, m, n, r')`` arrays — one TT-core stack per chain
    position, leading axis = SPSA perturbation index.
    x: ``(B, N)`` shared across all P (e.g. the collocation stencil feeding
    layer 1 of every perturbed model) or ``(P, B, N)`` per-perturbation
    activations.  Returns ``(P, B, M)``.

    Extra batch axes are allowed on either flavor — ``(C, B, N)`` shared
    (a coefficients × points grid evaluated under every perturbation) or
    ``(P, C, B, N)`` per-perturbation — and flattened for the launch, with
    the output reshaped back to ``(P, *batch_axes, M)``.  ``shared_x``
    disambiguates when inference from rank alone is ambiguous (None keeps
    the legacy rule: rank 2 = shared, otherwise per-P).
    """
    if not cores:
        raise ValueError("need at least one core stack")
    P = cores[0].shape[0]
    x, batch_shape, shared_x = _split_batch_axes(x, P, spec, shared_x)
    flat_lens = tuple(int(np.prod(s)) for s in spec.core_shapes)
    flat = [c.reshape(P, 1, -1) for c in cores]
    y = _launch(x, flat, [_row_spec(f) for f in flat_lens], spec, P,
                flat_lens, shared_x, False, interpret)
    return y.reshape((P,) + batch_shape + (spec.out_dim,))


def tt_contract(x: jax.Array, cores: tuple, spec: tt_lib.TTSpec,
                interpret: bool = False) -> jax.Array:
    """y = x @ W(cores)^T, fused in VMEM.  x: (..., N) → (..., M)."""
    stacked = tuple(c[None] for c in cores)
    y = tt_contract_batched(x.reshape(-1, spec.in_dim), stacked, spec,
                            interpret=interpret, shared_x=True)
    return y.reshape(x.shape[:-1] + (spec.out_dim,))


@functools.partial(jax.jit,
                   static_argnames=("spec", "quant", "interpret", "shared_x"))
def tt_contract_batched_quant(x: jax.Array, cores: tuple,
                              spec: tt_lib.TTSpec,
                              quant: quant_lib.QuantConfig,
                              interpret: bool = False,
                              shared_x: bool | None = None) -> jax.Array:
    """``tt_contract_batched`` with block-scaled int8/fp8-e4m3 cores.

    Each of the P core variants is quantized independently
    (``quantize_blockwise`` per stack row → ``(P, padded)`` narrow codes +
    ``(P, n_blocks)`` f32 scales), shipped to VMEM in the narrow dtype,
    and dequantized in-kernel before W is built — so HBM weight traffic
    is ~1.125 B/param (block=32) and the dequantized cores equal
    ``kernels.ref.tt_contract_batched_quant_ref``'s bit for bit (same
    quantizer, same f32 product).  Extra batch axes and the ``shared_x``
    flag behave as in ``tt_contract_batched``.
    """
    if not quant.weights:
        raise ValueError(f"weight quantization not enabled in {quant}")
    if not cores:
        raise ValueError("need at least one core stack")
    P = cores[0].shape[0]
    x, batch_shape, shared_x = _split_batch_axes(x, P, spec, shared_x)

    quantize = jax.vmap(lambda c: quant_lib.quantize_blockwise(c, quant))
    qs, ss, spreads = [], [], []
    for c in cores:
        q, s = quantize(c)                 # (P, padded_k), (P, n_blocks_k)
        qs.append(q.reshape(P, 1, -1))
        ss.append(s.reshape(P, 1, -1))
        n_blocks, padded = s.shape[1], q.shape[1]
        spreads.append(jnp.asarray(
            np.arange(padded)[None] // quant.block
            == np.arange(n_blocks)[:, None], jnp.float32))
    flat_lens = tuple(q.shape[-1] for q in qs)
    core_specs = [_row_spec(q.shape[-1]) for q in qs] \
        + [_row_spec(s.shape[-1]) for s in ss] \
        + [pl.BlockSpec(e.shape, lambda p, i: (0, 0)) for e in spreads]
    y = _launch(x, qs + ss + spreads, core_specs, spec, P, flat_lens,
                shared_x, True, interpret)
    return y.reshape((P,) + batch_shape + (spec.out_dim,))
