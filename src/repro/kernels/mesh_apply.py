"""Batched MZI-mesh application Pallas kernels — the photonic compute
primitive of the phase-domain ZO hot path (DESIGN.md §Photonic).  Two
bodies, chosen from the layout alone (``repro.kernels.ops.mesh_path``):
the one-hot body below for shallow meshes (the TONN core meshes), and the
``mesh_rect`` body (``mesh_apply_rect_pallas``, further down) for deep
rectangular (Clements) meshes such as ONN's 1024-port layers.

A ZO sweep in ``onn``/``tonn`` mode applies N+1 SPSA-perturbed meshes that
share ONE static layout.  The gather formulation (``repro.core.photonic``:
per level ``y[w] = C[c,w]·x[w] + S[c,w]·x[perm[c,w]]``) turns the level
chain into (gather, FMA) pairs with no scatter; this kernel runs that chain
for one (perturbation, batch-tile) program with the tile resident in VMEM:

  * grid ``(S, batch-tiles)`` — one stacked phase set per ``s`` step, the
    input tile shared across ``s`` when the feed is common (identity feed
    of a densification, collocation batch of layer 1: its BlockSpec index
    map ignores ``s``, so the input is never duplicated S× in HBM);
  * the per-wire trig tables ``C, S (S, levels, ports)`` are precomputed
    OUTSIDE the kernel in one vectorized pass (tiny: the paper's core
    meshes have ≤ ~10² entries per level);
  * the static wire permutation enters as a stack of one-hot matrices
    ``(levels, ports, ports)`` so the in-kernel gather is an MXU matmul —
    exact for one-hot f32 operands, keeping the kernel f32-identical to
    the jnp gather path;
  * the level chain is a static Python loop (fully unrolled — levels ==
    ports for the rectangular layout, small for the TT-core meshes this
    body exists for; ``repro.kernels.ops`` takes it up to
    ``MESH_KERNEL_MAX_LEVELS`` levels and ``MESH_KERNEL_MAX_ONEHOT_BYTES``
    of one-hot tables).

VMEM budget per program: ``bt·P`` x-tile + ``2·L·P`` trig + ``L·P²``
permutation + ``bt·P`` out — a few hundred KB at mesh sizes worth
compiling for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import photonic as ph_lib

__all__ = ["mesh_apply_stacked_pallas", "mesh_perm_onehot",
           "is_rectangular", "mesh_apply_rect_pallas"]


def mesh_perm_onehot(layout: ph_lib.MeshLayout) -> np.ndarray:
    """One-hot gather matrices ``M (levels, P, P)`` with
    ``M[c, perm[c, w], w] = 1`` so ``x @ M[c] == x[:, perm[c]]`` exactly
    (each output column selects a single input).  Memoized on the layout."""
    cached = getattr(layout, "_perm_onehot", None)
    if cached is not None:
        return cached
    perm, _, _ = ph_lib.mesh_gather_plan(layout)
    L, P = perm.shape
    onehot = np.zeros((L, P, P), dtype=np.float32)
    onehot[np.arange(L)[:, None], perm, np.arange(P)[None, :]] = 1.0
    object.__setattr__(layout, "_perm_onehot", onehot)
    return onehot


def _kernel(levels: int, transpose: bool, shared_x: bool, *refs):
    x_ref, cos_ref, sin_ref, perm_ref, diag_ref, o_ref = refs
    x = x_ref[...] if shared_x else x_ref[0]
    x = x.astype(jnp.float32)
    d = diag_ref[0]                          # (1, P)
    if not transpose:
        x = x * d
    for c in range(levels):                  # static unroll over the chain
        # HIGHEST keeps the one-hot gather exact on the MXU
        xg = jax.lax.dot_general(x, perm_ref[c], (((1,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        x = cos_ref[0, c:c + 1, :] * x + sin_ref[0, c:c + 1, :] * xg
    if transpose:
        x = x * d
    o_ref[0] = x.astype(o_ref.dtype)


def _batch_tile(rows: int, ports: int, levels: int,
                vmem_budget_bytes: int = 4 * 2**20) -> int:
    """Balanced batch tile whose resident set (x + out tiles; the trig and
    permutation tables are batch-independent) fits the VMEM budget: the
    whole batch if it fits, else equal tiles of a multiple of 8 rows."""
    fixed = (2 * levels * ports + levels * ports * ports) * 4
    per_row = 2 * ports * 4
    cap = max(8, min(2048, (vmem_budget_bytes - fixed) // per_row) // 8 * 8)
    if rows <= cap:
        return rows
    n_tiles = -(-rows // cap)
    return -(-(-(-rows // n_tiles)) // 8) * 8


def mesh_apply_stacked_pallas(layout: ph_lib.MeshLayout, phases: jax.Array,
                              diag: jax.Array, x: jax.Array,
                              transpose: bool = False,
                              interpret: bool = False) -> jax.Array:
    """Kernel-backed ``photonic.mesh_apply_stacked``: phases
    ``(S, levels, slots)``, diag ``(P,)`` or ``(S, P)``, x ``(B, P)``
    shared or ``(S, B, P)`` → ``(S, B, P)``."""
    S = phases.shape[0]
    Pw = layout.ports
    levels = layout.levels
    shared_x = x.ndim == 2
    if not shared_x and x.shape[0] != S:
        raise ValueError(f"x leading axis {x.shape[0]} != phase stack S={S}")
    B = x.shape[-2]

    cos, sin = ph_lib.mesh_gather_tables(layout, phases, transpose)
    onehot = mesh_perm_onehot(layout)
    if transpose:
        onehot = np.ascontiguousarray(onehot[::-1])
        # tables are already level-reversed/negated by mesh_gather_tables
    # (S, 1, P): every block's last two dims are whole array dims
    diag3 = jnp.broadcast_to(diag, (S, Pw)).reshape(S, 1, Pw)

    bt = _batch_tile(B, Pw, levels)
    if shared_x:
        in_specs = [pl.BlockSpec((bt, Pw), lambda s, i: (i, 0))]
    else:
        in_specs = [pl.BlockSpec((1, bt, Pw), lambda s, i: (s, i, 0))]
    in_specs += [
        pl.BlockSpec((1, levels, Pw), lambda s, i: (s, 0, 0)),   # cos
        pl.BlockSpec((1, levels, Pw), lambda s, i: (s, 0, 0)),   # sin
        pl.BlockSpec((levels, Pw, Pw), lambda s, i: (0, 0, 0)),  # perm
        pl.BlockSpec((1, 1, Pw), lambda s, i: (s, 0, 0)),        # diag
    ]
    return pl.pallas_call(
        functools.partial(_kernel, levels, transpose, shared_x),
        grid=(S, pl.cdiv(B, bt)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bt, Pw), lambda s, i: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, B, Pw), x.dtype),
        interpret=interpret,
        name="mesh_apply_stacked",
    )(x, cos, sin, jnp.asarray(onehot), diag3)


# ---------------------------------------------------------------------------
# Deep rectangular meshes: the ``mesh_rect`` body
# ---------------------------------------------------------------------------
#
# A rectangular (Clements) level pairs wire ``a`` with ``a + 1`` for every
# ``a`` of the level's parity, so no gather is needed once the wires are
# split into their even and odd halves, ``E[k] = x[2k]`` and
# ``O[k] = x[2k+1]`` (on lanes, E then O, each in the strided order of
# ``_to_lanes``):
#
#   parity 0 pairs (2k, 2k+1) = (E[k], O[k]):
#       E ← C_E·E + S_E·O,              O ← C_O·O + S_O·E
#   parity 1 pairs (2k+1, 2k+2) = (O[k], E[k+1]):
#       E ← C_E·E + S_E·roll(O, +1),    O ← C_O·O + S_O·roll(E, −1)
#
# with the per-wire tables of ``photonic.mesh_gather_tables`` (C = 1 and
# S = 0 on unpaired wires, so what a roll wraps round is multiplied by 0),
# built here in the split order by slicing the phases (``_rect_tables``):
# slot k of a level holds pair k, so no gather is needed there either.
# That is ``y[w] = C·x[w] + S·x[partner]``, the jnp path's arithmetic in
# its order.  Levels alternate parity, so the level loop runs over pairs of
# levels with static parities; the tables stream from HBM in blocks of
# levels along the grid's last axis while the output tile stays in VMEM,
# and each chunk of rows is carried through a block's levels at once.

_LANES = 128
# rows carried through a block's levels at once: more rows share each
# level's table loads (11 × 1024-port meshes on 1,024 rows, TPU v5e:
# 14.9 ms at 64 rows, 13.4 ms at 128)
_ROW_CHUNK = 128
_LEVEL_BLOCK = 128      # levels of trig tables per grid step
_ROW_TILE = 512         # rows of the output tile resident in VMEM


def is_rectangular(layout: ph_lib.MeshLayout) -> bool:
    """True when level ``c`` pairs exactly the wires ``(a, a + 1)`` with
    ``a ≡ c (mod 2)`` (``photonic.rectangular_layout``).  Memoized."""
    cached = getattr(layout, "_is_rect", None)
    if cached is not None:
        return cached
    perm, _, _ = ph_lib.mesh_gather_plan(layout)
    L, P = perm.shape
    w = np.arange(P)
    ok = True
    for c in range(L):
        first = (w - c % 2) % 2 == 0            # first lane of a pair
        want = np.where(first, w + 1, w - 1)
        want = np.where((want < 0) | (want >= P) | (w < c % 2), w, want)
        if not np.array_equal(perm[c], want):
            ok = False
            break
    object.__setattr__(layout, "_is_rect", ok)
    return ok


def _next(v: jax.Array, half: int) -> jax.Array:
    """v[k+1] at k (cyclic) in the strided order of ``_to_lanes``: the lane
    tiles move down by one and only the first one rolls."""
    head = pltpu.roll(v[:, :_LANES], _LANES - 1, 1)
    if half == _LANES:
        return head
    return jnp.concatenate([v[:, _LANES:], head], axis=1)


def _prev(v: jax.Array, half: int) -> jax.Array:
    """v[k-1] at k (cyclic) in the strided order of ``_to_lanes``."""
    tail = pltpu.roll(v[:, half - _LANES:], 1, 1)
    if half == _LANES:
        return tail
    return jnp.concatenate([tail, v[:, :half - _LANES]], axis=1)


def _rect_kernel(half: int, chunk: int, first_parity: int, transpose: bool,
                 shared_x: bool, *refs):
    x_ref, cos_ref, sin_ref, diag_ref, o_ref = refs
    lvl = pl.program_id(2)
    d = diag_ref[0]                                      # (1, Pp)

    @pl.when(lvl == 0)
    def _():
        x = (x_ref[...] if shared_x else x_ref[0]).astype(jnp.float32)
        o_ref[0] = x if transpose else x * d

    def level(e, o, t, parity):
        row = pl.ds(t, 1)
        ce, co = cos_ref[0, row, :half], cos_ref[0, row, half:]   # (1, half)
        se, so = sin_ref[0, row, :half], sin_ref[0, row, half:]
        if parity == 0:
            pe, po = o, e
        else:
            pe, po = _prev(o, half), _next(e, half)      # O[k-1], E[k+1]
        return ce * e + se * pe, co * o + so * po

    def level_pair(j, carry):
        e, o = carry
        t = pl.multiple_of(2 * j, 2)
        e, o = level(e, o, t, first_parity)
        return level(e, o, t + 1, 1 - first_parity)

    def rows(r, carry):
        rs = pl.ds(pl.multiple_of(r * chunk, 8), chunk)
        e, o = o_ref[0, rs, :half], o_ref[0, rs, half:]
        e, o = jax.lax.fori_loop(0, cos_ref.shape[1] // 2, level_pair, (e, o))
        o_ref[0, rs, :half] = e
        o_ref[0, rs, half:] = o
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // chunk, rows, 0)

    if transpose:
        @pl.when(lvl == pl.num_programs(2) - 1)
        def _():
            o_ref[0] = o_ref[0] * d


def _rect_geometry(layout: ph_lib.MeshLayout, rows: int) -> dict:
    """Padded sizes of the ``mesh_rect`` call: wires to whole lane tiles of
    each half, levels to whole blocks, rows to whole chunks and tiles."""
    half = -(-layout.ports // (2 * _LANES)) * _LANES
    lb = min(_LEVEL_BLOCK, -(-layout.levels // 2) * 2)
    levels = -(-layout.levels // lb) * lb
    chunk = min(_ROW_CHUNK, -(-rows // 8) * 8)
    chunked = -(-rows // chunk) * chunk
    n_tiles = -(-chunked // _ROW_TILE)
    bt = -(-chunked // (n_tiles * chunk)) * chunk
    return {"half": half, "level_block": lb, "levels": levels,
            "chunk": chunk, "row_tile": bt, "rows": bt * n_tiles}


def _rect_tables(layout: ph_lib.MeshLayout, phases: jax.Array,
                 transpose: bool, half: int, levels: int) -> tuple:
    """``photonic.mesh_gather_tables`` of a rectangular layout in the split
    wire order, ``(S, levels, 2·half)`` each (``_to_lanes``), the levels padded
    to ``levels`` with identity levels.  Pair k of a parity-0 level is
    (E[k], O[k]), of a parity-1 level (O[k], E[k+1]); its slot is k."""
    L, P = layout.levels, layout.ports
    ph = phases[..., :half]
    ph = jnp.pad(ph, [(0, 0), (0, 0), (0, half - ph.shape[-1])])
    k = np.arange(half)[None, :]
    parity = (np.arange(L) % 2)[:, None]
    pairs = (P - parity) // 2                               # (L, 1)
    paired = k < pairs                                      # slot k in use
    cos = jnp.where(paired, jnp.cos(ph), 1.0)
    sin = jnp.where(paired, jnp.sin(ph), 0.0)
    shift = lambda t, fill: jnp.pad(t[..., :-1], [(0, 0), (0, 0), (1, 0)],
                                    constant_values=fill)
    # parity 0: E first lane (−sin), O second (+sin), both of slot k;
    # parity 1: O[k] first lane of slot k, E[k] second lane of slot k−1
    odd = parity == 1
    cos_e = jnp.where(odd, shift(cos, 1.0), cos)
    sin_e = jnp.where(odd, shift(sin, 0.0), -sin)
    sin_o = jnp.where(odd, -sin, sin)
    cos_t = _to_lanes(jnp.stack([cos_e, cos], axis=-2), half)
    sin_t = _to_lanes(jnp.stack([sin_e, sin_o], axis=-2), half)
    if transpose:
        cos_t, sin_t = jnp.flip(cos_t, axis=-2), -jnp.flip(sin_t, axis=-2)
    pad = [(0, 0), (0, levels - L), (0, 0)]
    return (jnp.pad(cos_t, pad, constant_values=1.0),
            jnp.pad(sin_t, pad))


def _to_lanes(a: jax.Array, half: int) -> jax.Array:
    """(..., 2, half) halves in pair order k → (..., 2·half) lanes, E then
    O, each in the strided order that puts k = n·l + j (n = half / 128 lane
    tiles) at lane l of tile j: then k + 1 is the same lane of the next
    tile, and a shift by one wire rolls one tile only (``_next``)."""
    n = half // _LANES
    lead = a.shape[:-2]
    a = a.reshape(lead + (2, _LANES, n))
    return jnp.swapaxes(a, -1, -2).reshape(lead + (2 * half,))


def _split_halves(a: jax.Array, ports: int, half: int, fill: float):
    """Wires on the last axis → even wires then odd wires (``_to_lanes``),
    padded to ``2·half`` wires with ``fill``."""
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 2 * half - ports)],
                constant_values=fill)
    a = a.reshape(a.shape[:-1] + (half, 2))
    return _to_lanes(jnp.swapaxes(a, -1, -2), half)


def _join_halves(y: jax.Array, half: int) -> jax.Array:
    """Inverse of ``_split_halves`` before its padding: wire 2k + b."""
    lead = y.shape[:-1]
    y = y.reshape(lead + (2, half // _LANES, _LANES))
    return jnp.swapaxes(y, -1, -3).reshape(lead + (2 * half,))


def mesh_apply_rect_pallas(layout: ph_lib.MeshLayout, phases: jax.Array,
                           diag: jax.Array, x: jax.Array,
                           transpose: bool = False,
                           interpret: bool = False) -> jax.Array:
    """``photonic.mesh_apply_stacked`` for a rectangular layout of any
    depth (``is_rectangular``): phases ``(S, levels, slots)``, diag ``(P,)``
    or ``(S, P)``, x ``(B, P)`` shared or ``(S, B, P)`` → ``(S, B, P)``.
    Grid ``(S, row tiles, level blocks)``; a shared feed (the identity of a
    densification) is read once per row tile, whatever S."""
    if not is_rectangular(layout):
        raise ValueError("mesh_rect takes rectangular layouts only")
    S = phases.shape[0]
    Pw, L = layout.ports, layout.levels
    shared_x = x.ndim == 2
    if not shared_x and x.shape[0] != S:
        raise ValueError(f"x leading axis {x.shape[0]} != phase stack S={S}")
    B = x.shape[-2]
    g = _rect_geometry(layout, B)
    half, lb, bt = g["half"], g["level_block"], g["row_tile"]
    Pp = 2 * half

    cos, sin = _rect_tables(layout, phases, transpose, half, g["levels"])
    diag3 = _split_halves(jnp.broadcast_to(diag, (S, Pw)), Pw, half,
                          1.0).reshape(S, 1, Pp)
    xs = _split_halves(x, Pw, half, 0.0)
    xs = jnp.pad(xs, [(0, 0)] * (x.ndim - 2) + [(0, g["rows"] - B), (0, 0)])
    # application order runs the levels backwards under ``transpose``
    first_parity = (L - 1) % 2 if transpose else 0

    if shared_x:
        x_spec = pl.BlockSpec((bt, Pp), lambda s, i, l: (i, 0))
    else:
        x_spec = pl.BlockSpec((1, bt, Pp), lambda s, i, l: (s, i, 0))
    table = pl.BlockSpec((1, lb, Pp), lambda s, i, l: (s, l, 0))
    y = pl.pallas_call(
        functools.partial(_rect_kernel, half, g["chunk"], first_parity,
                          transpose, shared_x),
        grid=(S, g["rows"] // bt, g["levels"] // lb),
        in_specs=[x_spec, table, table,
                  pl.BlockSpec((1, 1, Pp), lambda s, i, l: (s, 0, 0))],
        out_specs=pl.BlockSpec((1, bt, Pp), lambda s, i, l: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, g["rows"], Pp), jnp.float32),
        interpret=interpret,
        name="mesh_rect",
    )(xs, cos, sin, diag3)
    return _join_halves(y, half)[:, :B, :Pw].astype(x.dtype)
