"""Tensor-train (TT) decomposition and contraction — the paper's §2.1.

A weight matrix ``W ∈ R^{M×N}`` with ``M = Π m_k``, ``N = Π n_k`` is folded
into a ``2L``-way tensor and parameterized by TT-cores

    G_k ∈ R^{r_{k-1} × m_k × n_k × r_k},   r_0 = r_L = 1,

so that ``W[(i_1..i_L),(j_1..j_L)] ≈ Π_k G_k[i_k, j_k]`` (Eq. (1) of the
paper).  This reduces parameter count from ``Π m_k n_k`` to
``Σ r_{k-1} m_k n_k r_k``.

This module provides:
  * ``TTSpec`` — static description of a TT-factorized matrix,
  * ``tt_matvec`` — the contraction chain ``y = x @ W(G)ᵀ`` that never
    materializes ``W`` (each step is a small matmul; this is the compute
    primitive the Pallas kernel in ``repro.kernels.tt_contract`` fuses),
  * ``tt_to_full`` — densification oracle (tests / small models),
  * ``tt_svd`` — TT-SVD decomposition of an existing matrix (Oseledets 2011),
  * ``auto_factorize`` — balanced integer factorization of layer dims, so any
    Linear in the LM architectures can be flipped to TT with one flag.

Index convention: row index of W = output (M), column = input (N).  A TT
"linear layer" computes ``y = x W^T`` with ``x: (..., N)`` → ``y: (..., M)``
to match the usual ``y = x @ W.T`` of an (out,in) weight.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TTSpec",
    "auto_factorize",
    "tt_matvec",
    "tt_matvec_stacked",
    "tt_to_full",
    "tt_svd",
    "tt_init",
    "tt_num_params",
]


@dataclasses.dataclass(frozen=True)
class TTSpec:
    """Static shape description of one TT-factorized (out_dim × in_dim) matrix."""

    out_modes: tuple  # (m_1, ..., m_L)
    in_modes: tuple   # (n_1, ..., n_L)
    ranks: tuple      # (r_0, r_1, ..., r_L) with r_0 = r_L = 1

    def __post_init__(self):
        if len(self.out_modes) != len(self.in_modes):
            raise ValueError("out_modes and in_modes must have equal length")
        if len(self.ranks) != len(self.out_modes) + 1:
            raise ValueError("ranks must have length L+1")
        if self.ranks[0] != 1 or self.ranks[-1] != 1:
            raise ValueError("TT boundary ranks must be 1")

    @property
    def L(self) -> int:
        return len(self.out_modes)

    @property
    def out_dim(self) -> int:
        return int(np.prod(self.out_modes))

    @property
    def in_dim(self) -> int:
        return int(np.prod(self.in_modes))

    @property
    def core_shapes(self) -> tuple:
        return tuple(
            (self.ranks[k], self.out_modes[k], self.in_modes[k], self.ranks[k + 1])
            for k in range(self.L)
        )

    @property
    def num_params(self) -> int:
        return int(sum(np.prod(s) for s in self.core_shapes))

    def contraction_flops(self, batch: int) -> int:
        """MACs of the tt_matvec chain for a given flattened batch size."""
        flops = 0
        m_prefix = 1
        n_suffix = self.in_dim
        for k in range(self.L):
            n_suffix //= self.in_modes[k]
            # (B*m_prefix, r_{k-1}*n_k) @ (r_{k-1}*n_k, m_k*r_k), batched over n_suffix
            flops += (
                batch
                * m_prefix
                * n_suffix
                * (self.ranks[k] * self.in_modes[k])
                * (self.out_modes[k] * self.ranks[k + 1])
            )
            m_prefix *= self.out_modes[k]
        return 2 * flops  # multiply-add


def _balanced_factorization(n: int, parts: int) -> list:
    """Factor ``n`` into ``parts`` integer factors, as balanced as possible.

    Greedy: repeatedly split the largest remaining factor by its smallest
    prime divisor, then merge back to exactly ``parts`` factors.
    """
    # prime factorization
    primes = []
    x = n
    d = 2
    while d * d <= x:
        while x % d == 0:
            primes.append(d)
            x //= d
        d += 1
    if x > 1:
        primes.append(x)
    if len(primes) < parts:
        primes += [1] * (parts - len(primes))
    # greedily multiply primes (largest first) into the currently-smallest bin
    primes.sort(reverse=True)
    bins = [1] * parts
    for p in primes:
        bins[int(np.argmin(bins))] *= p
    bins.sort(reverse=True)
    return bins


def auto_factorize(out_dim: int, in_dim: int, L: int = 4, max_rank: int = 16) -> TTSpec:
    """Build a TTSpec for an arbitrary (out_dim × in_dim) Linear.

    Uses balanced factorizations of both dims and a constant internal rank
    capped by ``max_rank`` (the paper uses ranks [1,2,1,2,1] for its
    1024×1024 layers; LM-scale layers use larger ranks).
    """
    out_modes = tuple(_balanced_factorization(out_dim, L))
    in_modes = tuple(_balanced_factorization(in_dim, L))
    ranks = [1]
    for k in range(1, L):
        # rank can never usefully exceed the full unfolding rank
        left = int(np.prod([out_modes[i] * in_modes[i] for i in range(k)]))
        right = int(np.prod([out_modes[i] * in_modes[i] for i in range(k, L)]))
        ranks.append(min(max_rank, left, right))
    ranks.append(1)
    return TTSpec(out_modes=out_modes, in_modes=in_modes, ranks=tuple(ranks))


def tt_init(key, spec: TTSpec, dtype=jnp.float32, scale: float | None = None) -> list:
    """Initialize TT-cores so the implied dense W has ~Glorot variance.

    Var(W_ij) = Π_k Var(G_k slice product) — for zero-mean independent cores,
    Var(W) = Π Var(G_k) · Π r_k (sum over rank paths).  We want
    Var(W) = 2/(fan_in+fan_out); solve per-core std.
    """
    target_var = scale if scale is not None else 2.0 / (spec.in_dim + spec.out_dim)
    # Var(W_ij) = Π_k var_k * (Π_{k=1..L-1} r_k)   (number of rank paths)
    n_paths = float(np.prod(spec.ranks[1:-1])) if spec.L > 1 else 1.0
    per_core_var = (target_var / n_paths) ** (1.0 / spec.L)
    keys = jax.random.split(key, spec.L)
    cores = []
    for k, shape in enumerate(spec.core_shapes):
        cores.append(
            (jax.random.normal(keys[k], shape, dtype=jnp.float32)
             * math.sqrt(per_core_var)).astype(dtype)
        )
    return cores


def tt_matvec(cores: Sequence[jax.Array], x: jax.Array,
              spec: TTSpec) -> jax.Array:
    """Compute ``y = x @ W(cores)^T`` without materializing ``W``.

    x: (..., N) → y: (..., M).  Invariant maintained over the chain:

        A_{k}: (B, m_1..m_k, r_k, n_{k+1}..n_L)

    each step contracts ``(r_{k-1}, n_k)`` with core ``G_k`` as one matmul
    of shape (B·M_<k, r·n_k) @ (r·n_k, m_k·r') batched over N_>k, at
    HIGHEST precision: the FD residual amplifies rounding in the output by
    1/h² (DESIGN.md §Perf), and TPU's DEFAULT rounds f32 operands to bf16.
    """
    batch_shape = x.shape[:-1]
    B = int(np.prod(batch_shape)) if batch_shape else 1
    n_suffix = spec.in_dim
    m_prefix = 1
    a = x.reshape(B, 1, spec.in_dim)  # (B, r0=1 · M_<1=1, N)
    for k in range(spec.L):
        r, m_k, n_k, r_next = spec.core_shapes[k]
        n_suffix //= n_k
        # a: (B*m_prefix, r * n_k, n_suffix)
        a = a.reshape(B * m_prefix, r * n_k, n_suffix)
        g = jnp.transpose(cores[k], (0, 2, 1, 3)).reshape(r * n_k, m_k * r_next)
        # (B·m_prefix, n_suffix, r·n_k) @ (r·n_k, m_k·r') -> (B·m_prefix, n_suffix, m_k·r')
        a = jnp.einsum("abc,bd->acd", a, g,
                       precision=jax.lax.Precision.HIGHEST)
        # reorder so produced m_k joins the m-prefix and r' precedes the n-suffix:
        a = a.reshape(B * m_prefix, n_suffix, m_k, r_next)
        a = jnp.transpose(a, (0, 2, 3, 1))  # (B·m_prefix, m_k, r', n_suffix)
        m_prefix *= m_k
    y = a.reshape(B, spec.out_dim)
    return y.reshape(*batch_shape, spec.out_dim)


def tt_matvec_stacked(cores: Sequence[jax.Array], x: jax.Array,
                      spec: TTSpec) -> jax.Array:
    """``tt_matvec`` over a leading stack axis P on the cores (the unfused
    oracle for ``repro.kernels.tt_contract.tt_contract_batched``).

    cores: each ``(P, r, m, n, r')``.  x: ``(B, N)`` shared across the stack
    or ``(P, B, N)`` per-stack-entry.  Returns ``(P, B, M)``.

    Deliberately a vmap of ``tt_matvec`` — the per-entry computation graph
    is identical to the sequential chain, so stacked and serial ZO sweeps
    agree bitwise (the FD residual squares second differences, amplifying
    any f32 reassociation by 1/h²; see DESIGN.md §Perf).  On TPU the
    stacked contraction kernel (``kernels/tt_contract.py``) takes its place.
    """
    if x.ndim == 2:
        # a shared x is broadcast, not closed over: every entry then runs
        # its own batched GEMM, so entry p's result does not depend on the
        # stack size (XLA:CPU folds an unbatched operand's stack axis into
        # the GEMM width, which changes the rounding with P)
        x = jnp.broadcast_to(x, (cores[0].shape[0],) + x.shape)
    return jax.vmap(lambda c, xx: tt_matvec(c, xx, spec))(list(cores), x)


def tt_to_full(cores: Sequence[jax.Array], spec: TTSpec) -> jax.Array:
    """Densify TT-cores into the full (out_dim, in_dim) matrix (oracle)."""
    # t: (m_1..m_k, n_1..n_k interleaved as (m,n) pairs, r_k)
    t = cores[0]  # (1, m1, n1, r1)
    t = t.reshape(spec.out_modes[0], spec.in_modes[0], spec.ranks[1])
    for k in range(1, spec.L):
        g = cores[k]  # (r_k, m, n, r')
        t = jnp.tensordot(t, g, axes=[[-1], [0]])  # (..., m_k, n_k, r')
    # t: (m1, n1, m2, n2, ..., mL, nL)
    t = t.reshape([d for k in range(spec.L)
                   for d in (spec.out_modes[k], spec.in_modes[k])])
    perm = list(range(0, 2 * spec.L, 2)) + list(range(1, 2 * spec.L, 2))
    t = jnp.transpose(t, perm)
    return t.reshape(spec.out_dim, spec.in_dim)


def tt_svd(w: np.ndarray, spec: TTSpec) -> list:
    """TT-SVD (Oseledets 2011): decompose a dense (M, N) matrix into TT-cores
    with the ranks given by ``spec`` (truncated SVD at each unfolding)."""
    M, N = w.shape
    if M != spec.out_dim or N != spec.in_dim:
        raise ValueError(f"shape mismatch: {w.shape} vs spec {spec.out_dim}x{spec.in_dim}")
    # reshape into (m1, ..., mL, n1, ..., nL) then interleave to (m1, n1, m2, n2, ...)
    t = np.asarray(w, dtype=np.float64).reshape(tuple(spec.out_modes) + tuple(spec.in_modes))
    L = spec.L
    perm = []
    for k in range(L):
        perm += [k, L + k]
    t = np.transpose(t, perm)  # (m1, n1, m2, n2, ...)
    cores = []
    r_prev = 1
    for k in range(L - 1):
        m_k, n_k = spec.out_modes[k], spec.in_modes[k]
        t = t.reshape(r_prev * m_k * n_k, -1)
        u, s, vt = np.linalg.svd(t, full_matrices=False)
        r_k = min(spec.ranks[k + 1], s.shape[0])
        u, s, vt = u[:, :r_k], s[:r_k], vt[:r_k]
        cores.append(u.reshape(r_prev, m_k, n_k, r_k))
        t = (s[:, None] * vt)
        r_prev = r_k
    m_L, n_L = spec.out_modes[-1], spec.in_modes[-1]
    cores.append(t.reshape(r_prev, m_L, n_L, 1))
    # pad ranks up to the spec if the data was lower-rank than requested
    padded = []
    for k, c in enumerate(cores):
        tgt = (spec.ranks[k], spec.out_modes[k], spec.in_modes[k], spec.ranks[k + 1])
        pad = [(0, tgt[i] - c.shape[i]) for i in range(4)]
        padded.append(np.pad(c, pad))
    return [jnp.asarray(c, dtype=jnp.float32) for c in padded]


def tt_num_params(spec: TTSpec) -> int:
    return spec.num_params


#: The paper's §4.2 factorization: 1024×1024 = [4,8,4,8]·[8,4,8,4],
#: TT-ranks [1,2,1,2,1] → 256 parameters per layer.
PAPER_TONN_SPEC = TTSpec(out_modes=(4, 8, 4, 8), in_modes=(8, 4, 8, 4),
                         ranks=(1, 2, 1, 2, 1))


def hjb_layer_spec(out_dim: int, in_dim: int, L: int = 4,
                   max_rank: int = 2) -> TTSpec:
    """TT spec for an HJB-PINN layer: the paper's exact factorization for the
    1024×1024 case, balanced auto-factorization otherwise."""
    if out_dim == in_dim == 1024 and L == 4 and max_rank == 2:
        return PAPER_TONN_SPEC
    return auto_factorize(out_dim, in_dim, L=L, max_rank=max_rank)
