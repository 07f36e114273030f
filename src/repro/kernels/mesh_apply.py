"""Batched MZI-mesh application Pallas kernel — the photonic compute
primitive of the phase-domain ZO hot path (DESIGN.md §Photonic).

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
    kernel exists for; ``repro.kernels.ops`` falls back to the jnp path
    above ``MESH_KERNEL_MAX_LEVELS``).

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

from repro.core import photonic as ph_lib

__all__ = ["mesh_apply_stacked_pallas", "mesh_perm_onehot"]


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
