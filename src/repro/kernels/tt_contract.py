"""Fused TT-linear Pallas kernels — the TONN compute primitive.

The paper's photonic TONN-1 design (Fig. 2) multiplies an input by ALL
TT-cores in one optical pass: intermediates never leave the chip.  The TPU
analogue (DESIGN.md §2): HBM carries the activations and the tiny TT-cores,
and everything in between stays in VMEM.

TPU layout.  The per-core chain of ``tt_matvec`` contracts dims of r·n_k ≈ 8
and rotates the feature index at every step: a relayout of sub-tile minor
dims that Mosaic refuses and that the 128×128 MXU could not use anyway.  So
the kernel builds dense matrices from the cores in VMEM, with 2-D matmuls
only, once per core set, and streams the batch tiles through them.  The
build is the same for every matrix it makes:

  * core k arrives as a flat row ``g (1, |G_k|)`` in its natural
    (r, m, n, r') order;
  * for each rank pair (a, b) the slab ``Z[μ, j] = G_k[a, μ, ν_k(j), b]``
    is ``(g ⊙ mask_ab) @ C_k``, where the static one-hot ``C_k`` sends a
    core entry to every built column whose input index has k-th digit ν;
  * ``E_ab = R_k @ Z`` repeats the slab over the built rows (``R_k`` one-hot
    on the k-th digit of each row's output index), and the rank sum
    ``Σ_paths ⊙_k E_k`` contracts the chain elementwise, one block of rows
    at a time.

Which rows and columns are built, in which order, is a static choice of
the tables; the kernel takes one of two bodies, by the spec alone
(``kron_factors``; ``repro.kernels.ops.tt_path`` names the one taken):

  * Kronecker body.  Ranks with an interior 1 at k (the paper's [1,2,1,2,1]
    at k = 2) make ``W = W_L ⊗ W_R``, W_L from cores [:k] (ML × NL) and W_R
    from cores [k:] (MR × NR), so ``y[b, iL·MR+iR] = Σ W_L[iL,jL] ·
    Σ W_R[iR,jR] · x[b, jL·NR+jR]``: NL·MR·NR + ML·NL·MR MACs a row instead
    of M·N (64 Ki against 1 Mi at 1024 × 1024).  Taken when M and N are
    multiples of 128 and MR, NR divide 128, so each 128-lane block holds
    whole groups.  Stage A contracts the minor index on the MXU: every
    lane-aligned block of x times ``I_a ⊗ W_R`` (a groups of NR lanes,
    128 × 128 for the paper).  Stage B contracts the major index, which
    mixes lane groups, without a relayout: for output block ob and input
    block ib, ``y_ob += Σ_d c[ob,ib,d] ⊙ roll_d(t_ib)``, f32 multiply-adds on
    the VPU, where ``roll_d`` rotates the block by d groups of MR lanes and
    the coefficient vector ``c[ob,ib,d]`` holds on each lane the W_L entry
    that pairs that lane's output group with the input group the roll
    brought there.  c is made from W_L with the same rolls, so it does not
    depend on the rotation's direction.  The scratch holds ``I_a ⊗ W_R``,
    W_L spread over t's lanes, c, and the stage-A and stage-B tiles, not
    W.
  * Dense body, for every other spec: ``W`` (M × N) is built into a VMEM
    scratch and each batch tile takes ONE MXU matmul ``y = x @ Wᵀ``.

Every matmul runs at ``Precision.HIGHEST``: the one-hot products are then
exact, and the FD residual, which amplifies rounding in u by 1/h²
(DESIGN.md §Perf), sees f32 arithmetic as on the jnp oracle.  Each row's
result depends on that row alone, whatever the tile or the stack size.

Both bodies read their input through ``x_tile(lanes)``, one tile of rows
on the given lanes, from one of two sources:

  * x in HBM (``tt_contract_batched``): a ``(tile, N)`` block per grid
    step.  HBM traffic per call is the x tiles, the y tiles, the cores and
    the one-hot tables; the tables are read once per call, whatever the
    batch.
  * the rank-1 FD stencil (``tt_contract_stencil``): the rows
    ``sin(z0 ± hcols[i])`` are built in VMEM from ``z0 (P, B, N)`` and the
    shifts ``hcols (P, A, N)``, both one block per ``p``, resident across
    its row tiles; a tile is whole slots of ``Bp = roundup(B, 8)`` rows.
    HBM traffic per call is z0 and the shifts once per ``p``, the y tiles,
    the cores and the tables: the ``(2A+1)·B`` input rows (194 MB at the
    paper's sizes) are never read.

``tt_contract_batched`` adds a leading perturbation axis P: grid
``(P, row-tiles)``, one core set per ``p`` and its matrices rebuilt at
the first row tile of every ``p``, so an entire ZO loss sweep (all N+1
perturbed models) is ONE launch.  x may be shared across P (its index map
ignores p) or carry its own P axis.  ``tt_contract`` is the P = 1 case.
``fits_vmem`` says whether a spec's W and tables fit the kernel's VMEM
budget; ``repro.kernels.ops`` sends larger specs to the jnp chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fastmath
from repro.core import tt as tt_lib
from repro.kernels import quant as quant_lib

__all__ = ["tt_contract", "tt_contract_batched", "tt_contract_stencil",
           "tt_contract_batched_quant", "fits_vmem", "kron_split",
           "split_spec", "kron_factors"]

_HIGHEST = jax.lax.Precision.HIGHEST
# x + y tiles, double-buffered, per batch row budget
_TILE_BUDGET_BYTES = 8 * 2**20
# W scratch + one-hot tables (double-buffered) + build temporaries
_VMEM_BUDGET_BYTES = 48 * 2**20
_VMEM_LIMIT_BYTES = 100 * 2**20
# output rows of W built per block (bounds the build temporaries)
_W_ROW_BLOCK = 256
_LANES = 128
# tile rows per step of the Kronecker body's VPU stage (a multiple of 8,
# the sublane tiling): its accumulators and rolled inputs fit the vector
# registers (on a TPU v5e, 32 and 64 rows ran the paper's layer 1 equally
# fast, 16 rows about 15 % slower)
_KRON_ROW_CHUNK = 32


def _dot(a: jax.Array, b: jax.Array, trans_b: bool = False) -> jax.Array:
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _digit(modes: tuple, k: int) -> np.ndarray:
    """k-th mixed-radix digit (most significant first) of every index."""
    idx = np.arange(int(np.prod(modes)))
    return (idx // int(np.prod(modes[k + 1:]))) % modes[k]


def kron_split(spec: tt_lib.TTSpec) -> int | None:
    """Most balanced interior index k with r_k == 1 (else None): there
    ``W = W_L ⊗ W_R``, with W_L from cores [:k] and W_R from cores [k:]."""
    best = None
    for k in range(1, spec.L):
        if spec.ranks[k] == 1:
            bal = abs(int(np.prod(spec.in_modes[:k]))
                      - int(np.prod(spec.in_modes[k:])))
            if best is None or bal < best[1]:
                best = (k, bal)
    return None if best is None else best[0]


def split_spec(spec: tt_lib.TTSpec, k: int) -> tuple:
    """The specs of W_L (cores [:k]) and W_R (cores [k:]) at a rank-1 k."""
    return (tt_lib.TTSpec(spec.out_modes[:k], spec.in_modes[:k],
                          tuple(spec.ranks[:k + 1])),
            tt_lib.TTSpec(spec.out_modes[k:], spec.in_modes[k:],
                          tuple(spec.ranks[k:])))


@functools.lru_cache(maxsize=None)
def kron_factors(spec: tt_lib.TTSpec) -> tuple | None:
    """``(W_L spec, W_R spec)`` when the kernel contracts through the
    Kronecker split, else None (the dense body).  Needs an interior rank 1
    and factors that tile a 128-lane vreg: M and N multiples of 128, MR and
    NR dividing 128, and whole stage-A blocks of ``128 / min(MR, NR)``
    groups."""
    k = kron_split(spec)
    if k is None:
        return None
    left, right = split_spec(spec, k)
    mr, nr = right.out_dim, right.in_dim
    if (spec.out_dim % _LANES or spec.in_dim % _LANES or _LANES % mr
            or _LANES % nr or left.in_dim % (_LANES // min(mr, nr))):
        return None
    return left, right


def _kron_geometry(spec: tt_lib.TTSpec) -> tuple:
    """``(left, right, g, a)``: the factor specs, W_R's output groups per
    128-lane block of t and y, and its groups per stage-A block."""
    left, right = kron_factors(spec)
    return (left, right, _LANES // right.out_dim,
            _LANES // min(right.out_dim, right.in_dim))


@functools.lru_cache(maxsize=None)
def _tables(spec: tt_lib.TTSpec, flat_lens: tuple, rows: tuple | None = None,
            cols: tuple | None = None) -> tuple:
    """Static one-hot tables per core: ``R (rows, m)``, ``C (F, cols)`` and
    ``masks (r·r', m, F)``, with F the (possibly padded) flat core length.
    ``rows``/``cols`` give the output/input index of each built row/column
    (default: W's own, all of them in order)."""
    rows = np.arange(spec.out_dim) if rows is None else np.asarray(rows)
    cols = np.arange(spec.in_dim) if cols is None else np.asarray(cols)
    out = []
    for k, shape in enumerate(spec.core_shapes):
        r, m, n, rn = shape
        size = int(np.prod(shape))
        a, mu, nu, b = np.unravel_index(np.arange(size), shape)
        row_t = (_digit(spec.out_modes, k)[rows][:, None]
                 == np.arange(m)[None]).astype(np.float32)
        col_t = np.zeros((flat_lens[k], len(cols)), np.float32)
        col_t[:size] = nu[:, None] == _digit(spec.in_modes, k)[cols][None]
        masks = np.zeros((r * rn, m, flat_lens[k]), np.float32)
        masks[a * rn + b, mu, np.arange(size)] = 1.0
        out.append((row_t, col_t, masks))
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _kron_tables(spec: tt_lib.TTSpec, flat_lens: tuple) -> tuple:
    """Tables of the Kronecker body: W_L's cores build ``H (ML, NL·MR)``,
    row ``s·NBout + ob`` holding W_L's row ``g·ob + s`` spread over the
    lanes of t's layout (column ``ib·128 + l`` holds W_L's column
    ``g·ib + l // MR``); W_R's cores build ``W_R`` tiled a × a times
    (``a·MR × a·NR``), masked to ``I_a ⊗ W_R`` in the kernel."""
    left, right, g, a = _kron_geometry(spec)
    k, mr, nr = left.L, right.out_dim, right.in_dim
    nb_out = spec.out_dim // _LANES
    lanes = np.arange(left.in_dim * mr)
    h_rows = [g * (r % nb_out) + r // nb_out for r in range(left.out_dim)]
    h_cols = g * (lanes // _LANES) + (lanes % _LANES) // mr
    return (_tables(left, flat_lens[:k], tuple(h_rows),
                    tuple(h_cols.tolist()))
            + _tables(right, flat_lens[k:],
                      tuple((np.arange(a * mr) % mr).tolist()),
                      tuple((np.arange(a * nr) % nr).tolist())))


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


def _tile_cap(spec: tt_lib.TTSpec) -> int:
    """Most rows a tile may hold: its x and y blocks, double-buffered, fill
    ``_TILE_BUDGET_BYTES``."""
    per_row = 2 * 4 * (spec.in_dim + spec.out_dim)
    return max(16, (_TILE_BUDGET_BYTES // per_row) // 16 * 16)


def _batch_tile(rows: int, spec: tt_lib.TTSpec) -> int:
    """Balanced batch tile: as many tiles as the budget needs, each a
    multiple of 16 rows (bf16 tiling), or the whole batch if it fits."""
    cap = _tile_cap(spec)
    if rows <= cap:
        return rows
    n_tiles = -(-rows // cap)
    return -(-(-(-rows // n_tiles)) // 16) * 16


def _w_blocks(spec: tt_lib.TTSpec, cores: list, tables: list):
    """Yield ``(r0, block)``: the built matrix (rows and columns as the
    tables give them) from the flat core rows, one block of rows at a
    time."""
    slabs = []
    for k, (r, m, _, rn) in enumerate(spec.core_shapes):
        _, cols_ref, masks_ref = tables[k]
        g = jnp.broadcast_to(cores[k], (m, cores[k].shape[-1]))
        cols = cols_ref[...]
        slabs.append([[_dot(g * masks_ref[a * rn + b], cols)
                       for b in range(rn)] for a in range(r)])
    n_rows = tables[0][0].shape[0]
    tm = _row_block(n_rows)
    for r0 in range(0, n_rows, tm):
        acc = None                       # one (tm, cols) block per open rank
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
        yield r0, acc[0]


def _build_matrix(spec: tt_lib.TTSpec, cores: list, tables: list):
    return jnp.concatenate([blk for _, blk in _w_blocks(spec, cores, tables)],
                           axis=0)


def _dense_body(spec, cores, tables, x_tile, o_ref, w_ref) -> None:
    @pl.when(pl.program_id(1) == 0)
    def _():
        for r0, blk in _w_blocks(spec, cores(), tables):
            w_ref[r0:r0 + blk.shape[0], :] = blk

    y = _dot(x_tile(slice(None)).astype(jnp.float32), w_ref[...],
             trans_b=True)
    o_ref[0] = y.astype(o_ref.dtype)


def _kron_body(spec, cores, tables, x_tile, o_ref, er_ref, h_ref, c_ref,
               t_ref, y_ref) -> None:
    """Stage A on the MXU, stage B on the VPU (module docstring).  Stage B
    runs over whole chunks of rows, the last one partly past the tile into
    the scratch's padding, one (nb_out, chunk, 128) product per (ib, d), so
    the kernel stays small to trace and lower.  ``c_ref[ib·g + d, ob]``
    holds the lane vector c[ob, ib, d]."""
    left, right, g, a = _kron_geometry(spec)
    k, mr, nr = left.L, right.out_dim, right.in_dim
    nb_in, nb_out = left.in_dim * mr // _LANES, spec.out_dim // _LANES
    shift = lambda d: (_LANES - mr * d) % _LANES
    roll = lambda v, d: v if d == 0 else pltpu.roll(v, shift(d), 1)
    block = lambda i: pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)

    @pl.when(pl.program_id(1) == 0)
    def _():
        cs = cores()
        tiled = _build_matrix(right, cs[k:], tables[k:])
        rr = jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 0)
        cc = jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 1)
        diag = None
        for s in range(a):
            blk = ((rr >= s * mr) & (rr < (s + 1) * mr)
                   & (cc >= s * nr) & (cc < (s + 1) * nr))
            diag = blk if diag is None else diag | blk
        er_ref[...] = jnp.where(diag, tiled, 0.0)
        h_ref[...] = _build_matrix(left, cs[:k], tables[:k])
        lane = jax.lax.broadcasted_iota(jnp.int32, (nb_out, _LANES), 1)

        def coefficients(ib, carry):
            hb = h_ref[:, block(ib)]
            for d in range(g):
                hr = roll(hb, d)
                c = hr[:nb_out]
                for s in range(1, g):
                    c = jnp.where(lane >= s * mr,
                                  hr[s * nb_out:(s + 1) * nb_out], c)
                c_ref[ib * g + d] = c[:, None, :]
            return carry

        jax.lax.fori_loop(0, nb_in, coefficients, 0)

    er = er_ref[...]
    wa, wo = a * nr, a * mr
    bt = o_ref.shape[1]
    for blk in range(left.in_dim // a):
        xb = x_tile(slice(blk * wa, (blk + 1) * wa)).astype(jnp.float32)
        t_ref[:bt, blk * wo:(blk + 1) * wo] = _dot(xb, er, trans_b=True)

    chunk = _KRON_ROW_CHUNK

    def stage_b(i, carry):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        acc = None                          # (nb_out, chunk, 128)
        for ib in range(nb_in):
            tb = t_ref[rows, ib * _LANES:(ib + 1) * _LANES]
            for d in range(g):
                term = c_ref[ib * g + d] * roll(tb, d)[None]
                acc = term if acc is None else acc + term
        y_ref[:, rows, :] = acc
        return carry

    jax.lax.fori_loop(0, t_ref.shape[0] // chunk, stage_b, 0)
    for ob in range(nb_out):
        o_ref[0, :, ob * _LANES:(ob + 1) * _LANES] = \
            y_ref[ob, :bt].astype(o_ref.dtype)


def _kernel(spec: tt_lib.TTSpec, x_tile_of, n_x: int, quantized: bool,
            *refs):
    L = spec.L
    x_refs, refs = refs[:n_x], refs[n_x:]
    if quantized:
        q_refs, s_refs, e_refs = refs[:L], refs[L:2 * L], refs[2 * L:3 * L]
        refs = refs[3 * L:]
    else:
        c_refs, refs = refs[:L], refs[L:]
    tables = [refs[3 * k:3 * k + 3] for k in range(L)]
    o_ref, scratch = refs[3 * L], refs[3 * L + 1:]

    def cores():
        if quantized:
            # dequantize in VMEM: each code times its block's f32 scale,
            # the scale spread over its block by a one-hot matmul (exact)
            return [q_refs[k][0].astype(jnp.float32)
                    * _dot(s_refs[k][0], e_refs[k][...]) for k in range(L)]
        return [c[0].astype(jnp.float32) for c in c_refs]

    body = _kron_body if kron_factors(spec) else _dense_body
    body(spec, cores, tables, x_tile_of(*x_refs), o_ref, *scratch)


def _x_source(x: jax.Array, spec: tt_lib.TTSpec, shared_x: bool) -> tuple:
    """``(operands, block specs, x_tile_of, rows, tile)`` of an input read
    from HBM: one ``(tile, N)`` block of x per grid step."""
    rows = x.shape[-2]
    bt = _batch_tile(rows, spec)
    if shared_x:
        blk = pl.BlockSpec((bt, spec.in_dim), lambda p, i: (i, 0))
        x_tile_of = lambda x_ref: lambda lanes: x_ref[:, lanes]
    else:
        blk = pl.BlockSpec((1, bt, spec.in_dim), lambda p, i: (p, i, 0))
        x_tile_of = lambda x_ref: lambda lanes: x_ref[0, :, lanes]
    return (x,), (blk,), x_tile_of, rows, bt


def _stencil_source(z0: jax.Array, shifts: jax.Array,
                    spec: tt_lib.TTSpec) -> tuple:
    """``_x_source`` for rows built in VMEM: slot s of ``Bp`` rows is
    ``sin(z0[p] + shifts[p, s])``.  A tile is whole slots, and its last
    slots past the end repeat the final one (their rows are not written)."""
    _, bp, width = z0.shape
    n_slots = shifts.shape[1]
    per_tile = min(n_slots, max(1, _tile_cap(spec) // bp))
    per_tile = -(-n_slots // -(-n_slots // per_tile))     # balanced tiles

    def x_tile_of(z0_ref, shift_ref):
        def x_tile(lanes):
            z = z0_ref[0, :, lanes]
            first = pl.program_id(1) * per_tile
            rows = [z + shift_ref[0, jnp.minimum(first + t, n_slots - 1), :,
                                  lanes] for t in range(per_tile)]
            return fastmath.fast_sin(jnp.concatenate(rows, axis=0))
        return x_tile

    return ((z0, shifts),
            (pl.BlockSpec((1, bp, width), lambda p, i: (p, 0, 0)),
             pl.BlockSpec((1, n_slots, 1, width), lambda p, i: (p, 0, 0, 0))),
            x_tile_of, n_slots * bp, per_tile * bp)


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


def _launch(source: tuple, core_args: list, core_specs: list,
            spec: tt_lib.TTSpec, P: int, flat_lens: tuple, quantized: bool,
            interpret: bool) -> jax.Array:
    """One ``pallas_call`` over the ``(P, row-tiles)`` grid; ``source`` is
    ``_x_source``'s or ``_stencil_source``'s."""
    x_args, x_specs, x_tile_of, n_rows, bt = source
    const = lambda a: pl.BlockSpec(a.shape, lambda p, i: (0,) * a.ndim)
    if kron_factors(spec):
        left, right, _, a = _kron_geometry(spec)
        mr, nr = right.out_dim, right.in_dim
        rows = -(-bt // _KRON_ROW_CHUNK) * _KRON_ROW_CHUNK
        nb_out = spec.out_dim // _LANES
        # I_a ⊗ W_R, W_L spread over t's lanes, the stage-B coefficient
        # vectors, and the stage-A and stage-B tiles padded to whole chunks
        scratch = [pltpu.VMEM((a * mr, a * nr), jnp.float32),
                   pltpu.VMEM((left.out_dim, left.in_dim * mr), jnp.float32),
                   pltpu.VMEM((left.in_dim, nb_out, 1, _LANES), jnp.float32),
                   pltpu.VMEM((rows, left.in_dim * mr), jnp.float32),
                   pltpu.VMEM((nb_out, rows, _LANES), jnp.float32)]
        tabs = _kron_tables(spec, flat_lens)
    else:
        scratch = [pltpu.VMEM((spec.out_dim, spec.in_dim), jnp.float32)]
        tabs = _tables(spec, flat_lens)
    tables = [jnp.asarray(t) for tab in tabs for t in tab]
    return pl.pallas_call(
        functools.partial(_kernel, spec, x_tile_of, len(x_args), quantized),
        grid=(P, pl.cdiv(n_rows, bt)),
        in_specs=list(x_specs) + core_specs + [const(t) for t in tables],
        out_specs=pl.BlockSpec((1, bt, spec.out_dim), lambda p, i: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, n_rows, spec.out_dim),
                                       x_args[0].dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="tt_contract_quant" if quantized else "tt_contract",
    )(*x_args, *core_args, *tables)


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
    y = _launch(_x_source(x, spec, shared_x), flat,
                [_row_spec(f) for f in flat_lens], spec, P, flat_lens, False,
                interpret)
    return y.reshape((P,) + batch_shape + (spec.out_dim,))


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def tt_contract_stencil(z0: jax.Array, hcols: jax.Array, cores: tuple,
                        spec: tt_lib.TTSpec,
                        interpret: bool = False) -> jax.Array:
    """``tt_contract_batched`` on the rank-1 FD stencil's rows, built in
    VMEM: the layer after a linear one whose input the stencil shifts by
    ±h·e_i, so its pre-activations are ``z0 ± hcols[i]``.

    z0: ``(P, B, N)`` pre-activations at the B points; hcols: ``(P, A, N)``,
    the A shifts ``h·W e_i``; cores: ``(P, r, m, n, r')`` stacks.  Slot 0
    of a point is ``sin(z0)``, slot i in 1..A ``sin(z0 + hcols[i-1])``,
    slot A+i ``sin(z0 − hcols[i-1])`` (``fastmath.fast_sin``); each slot
    holds ``Bp = roundup(B, 8)`` rows, the last ``Bp − B`` from zero rows
    of z0.  Returns ``(P, (2A+1)·Bp, M)``, slot-major; rows ``s·Bp + b``
    with b < B are the stencil's, the others are finite and read by
    nobody.  The ``(P, (2A+1)·B, N)`` activations never exist in HBM.
    """
    P, B, _ = z0.shape
    bp = -(-B // 8) * 8
    z0 = jnp.pad(z0, ((0, 0), (0, bp - B), (0, 0)))
    # z0 + (−hcols) is z0 − hcols to the bit; slot 0 adds a zero row
    shifts = jnp.concatenate([jnp.zeros_like(hcols[:, :1]), hcols, -hcols],
                             axis=1)[:, :, None]
    flat_lens = tuple(int(np.prod(s)) for s in spec.core_shapes)
    flat = [c.reshape(P, 1, -1) for c in cores]
    return _launch(_stencil_source(z0, shifts, spec), flat,
                   [_row_spec(f) for f in flat_lens], spec, P, flat_lens,
                   False, interpret)


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
    y = _launch(_x_source(x, spec, shared_x), qs + ss + spreads, core_specs,
                spec, P, flat_lens, True, interpret)
    return y.reshape((P,) + batch_shape + (spec.out_dim,))
