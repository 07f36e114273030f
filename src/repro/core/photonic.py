"""Photonic MZI-mesh simulator — the paper's hardware substrate (§2.1, §4.1).

The paper implements a weight matrix ``W = U Σ V*`` where the unitaries are
meshes of 2×2 MZI rotators, each rotator ``R(φ)`` realized by one MZI (two
phase shifters + two 50/50 splitters).  Trainable parameters are the phases
``Φ``; hardware imperfections act on the phases:

    Φ_eff = Ω (Γ ⊙ Φ) + Φ_b
      Γ   ~ N(γ, σ_γ²)   per-shifter gamma-coefficient drift (fabrication)
      Ω                  thermal crosstalk between ADJACENT MZIs (banded mix)
      Φ_b ~ U(0, 2π)·β   phase bias from manufacturing error

(the paper's objective Φ* = argmin L(W(ΩΓΦ + Φ_b))).

Everything here is real-valued (the paper's rotators are 2-D rotations).  A
mesh is a leveled sequence of disjoint Givens rotations; we schedule an
arbitrary rotation list into levels (columns) greedily, so both the
rectangular (Clements-style) from-scratch layout and the QR/Reck
decomposition of an existing matrix share one apply path:

  * ``rectangular_layout(P)``          — P columns of alternating pairs,
                                         P(P-1)/2 MZIs (from-scratch training)
  * ``decompose_orthogonal(U)``        — Givens-QR nulling → (layout, phases,
                                         diag) s.t. mesh == U (maps off-chip-
                                         trained weights onto hardware)
  * ``mesh_apply(layout, phases, d, x)``  — y = U x in the precomputed
                                         GATHER form: each level is a static
                                         wire pairing, so both rotation lanes
                                         are gathered, rotated, and written
                                         back scatter-free (DESIGN.md
                                         §Photonic)
  * ``mesh_apply_scan``                — the seed's scatter-per-level
                                         ``lax.scan`` formulation, kept as
                                         the sequential photonic-realism
                                         reference (agrees with the gather
                                         form to f32 rounding)
  * ``mesh_apply_stacked`` /
    ``mesh_matrix_stacked``            — the gather form with a leading
                                         SPSA-perturbation axis on the
                                         phases: ONE batched program
                                         evaluates all perturbed meshes of a
                                         ZO sweep against a shared layout
  * ``PhotonicMatrix``                 — W = U Σ Vᵀ wrapper with param
                                         init / from_dense / apply / to_dense
                                         (+ ``apply_stacked`` /
                                         ``to_dense_stacked`` riding the
                                         kernel dispatcher)
  * ``NoiseModel``                     — sample + apply the three imperfections

Trainable vs. buffer split: the params dict of a ``PhotonicMatrix`` holds
the trainable phases/sigma AND the fixed ±1 ``diag_u``/``diag_v`` buffers
(``PHOTONIC_BUFFER_KEYS``) that pin the mesh to its orthogonal
decomposition.  ZO training must never perturb or update the buffers —
``repro.core.zoo`` takes a trainable-mask pytree
(``TensorPinn.trainable_mask``) that zeroes their ξ entries.

Design notes (TPU adaptation, see DESIGN.md §2): the mesh is *simulated* —
for BP baselines we differentiate through the level chain; for the paper's
proposed on-chip ZO training only forward applications are used, matching
the "inference-only" property of the real chip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MeshLayout",
    "rectangular_layout",
    "schedule_ops",
    "decompose_orthogonal",
    "mesh_gather_plan",
    "mesh_gather_tables",
    "mesh_apply",
    "mesh_apply_scan",
    "mesh_apply_stacked",
    "mesh_matrix",
    "mesh_matrix_stacked",
    "NoiseModel",
    "PhotonicMatrix",
    "PHOTONIC_BUFFER_KEYS",
    "mzi_count_matrix",
]

# fixed ±1 diagonal buffers of a PhotonicMatrix params dict: part of the
# orthogonal decomposition, NOT trainable — ZO perturbations/updates must
# skip them (zoo.sample_perturbation's mask; TensorPinn.trainable_mask)
PHOTONIC_BUFFER_KEYS = ("diag_u", "diag_v")


# ---------------------------------------------------------------------------
# Mesh layout & scheduling
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """Leveled mesh: level ``c`` applies rotations on wire pairs
    ``(idx_a[c,k], idx_b[c,k])`` for every unmasked slot ``k``.
    Padded slots point at the scratch wire ``P`` (see mesh_apply)."""

    ports: int
    idx_a: np.ndarray  # (levels, slots) int32
    idx_b: np.ndarray  # (levels, slots) int32
    mask: np.ndarray   # (levels, slots) bool

    @property
    def levels(self) -> int:
        return self.idx_a.shape[0]

    @property
    def slots(self) -> int:
        return self.idx_a.shape[1]

    @property
    def num_mzis(self) -> int:
        return int(self.mask.sum())

    def phase_shape(self) -> tuple:
        return (self.levels, self.slots)


def schedule_ops(ports: int, ops: Sequence[tuple]) -> MeshLayout:
    """Greedy level-schedule an ordered rotation list [(a, b), ...] into
    columns of disjoint pairs, preserving relative order on shared wires."""
    wire_level = np.full(ports, -1, dtype=np.int64)  # last level touching wire
    levels: list = []
    for (a, b) in ops:
        lvl = int(max(wire_level[a], wire_level[b])) + 1
        while len(levels) <= lvl:
            levels.append([])
        levels[lvl].append((a, b))
        wire_level[a] = lvl
        wire_level[b] = lvl
    n_levels = max(1, len(levels))
    slots = max(1, max((len(l) for l in levels), default=1))
    idx_a = np.full((n_levels, slots), ports, dtype=np.int32)  # pad -> scratch
    idx_b = np.full((n_levels, slots), ports, dtype=np.int32)
    mask = np.zeros((n_levels, slots), dtype=bool)
    for c, lvl in enumerate(levels):
        for k, (a, b) in enumerate(lvl):
            idx_a[c, k] = a
            idx_b[c, k] = b
            mask[c, k] = True
    return MeshLayout(ports=ports, idx_a=idx_a, idx_b=idx_b, mask=mask)


def rectangular_layout(ports: int) -> MeshLayout:
    """Clements-style rectangular arrangement: ``ports`` columns alternating
    even/odd pair offsets; exactly P(P-1)/2 MZIs."""
    ops = []
    for c in range(ports):
        off = c % 2
        for a in range(off, ports - 1, 2):
            ops.append((a, a + 1))
    layout = schedule_ops(ports, ops)
    assert layout.num_mzis == ports * (ports - 1) // 2, layout.num_mzis
    return layout


def decompose_orthogonal(u: np.ndarray) -> tuple:
    """Givens-QR (Reck-ordered) decomposition of a real orthogonal matrix.

    Returns ``(layout, phases, diag)`` with ``mesh_matrix(layout, phases,
    diag) == u`` (up to float error).  Nulling: G_K … G_1 U = D (diag ±1), so
    U = G_1ᵀ … G_Kᵀ D; application order is D first then Gᵀ in reverse.
    """
    u = np.asarray(u, dtype=np.float64)
    P = u.shape[0]
    assert u.shape == (P, P)
    r = u.copy()
    nulling: list = []  # (a, b, theta) in nulling order
    for c in range(P - 1):
        for row in range(P - 1, c, -1):
            a, b = row - 1, row
            x, y = r[a, c], r[b, c]
            if abs(y) < 1e-300:
                theta = 0.0
            else:
                theta = math.atan2(y, x)
            ca, sa = math.cos(theta), math.sin(theta)
            # G = [[ca, sa], [-sa, ca]] acting on rows (a, b) zeroes r[b, c]
            ra, rb = r[a].copy(), r[b].copy()
            r[a] = ca * ra + sa * rb
            r[b] = -sa * ra + ca * rb
            nulling.append((a, b, theta))
    diag = np.sign(np.diag(r)).astype(np.float64)
    diag[diag == 0] = 1.0
    # application order: reversed nulling, each Gᵀ = rotation by +theta applied
    # as mesh op R(phi) = [[cos, -sin], [sin, cos]]; Gᵀ = [[ca, -sa],[sa, ca]]
    ops = [(a, b) for (a, b, _) in reversed(nulling)]
    layout = schedule_ops(P, ops)
    phases = np.zeros(layout.phase_shape(), dtype=np.float64)
    # refill phases in the same traversal order schedule_ops used
    wire_level = np.full(P, -1, dtype=np.int64)
    counters = np.zeros(layout.levels, dtype=np.int64)
    for (a, b, theta) in reversed(nulling):
        lvl = int(max(wire_level[a], wire_level[b])) + 1
        k = counters[lvl]
        counters[lvl] += 1
        assert layout.idx_a[lvl, k] == a and layout.idx_b[lvl, k] == b
        phases[lvl, k] = theta
        wire_level[a] = lvl
        wire_level[b] = lvl
    return layout, jnp.asarray(phases, dtype=jnp.float32), jnp.asarray(diag, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Mesh application — precomputed gather/permutation form
# ---------------------------------------------------------------------------
#
# Each level of the mesh is a STATIC wire pairing, so instead of scattering
# rotated pairs back through a scratch lane (the seed's formulation, kept
# below as ``mesh_apply_scan``), every wire's output is a gather + FMA:
#
#     y[w] = C[c, w] · x[w] + S[c, w] · x[perm[c, w]]
#
# with per-wire coefficients C = cos(φ) (1 on unpaired wires) and
# S = ∓sin(φ) (−sin on the first lane of a pair, +sin on the second, 0 on
# unpaired wires).  The (perm, slot, sign) plan is precomputed once per
# layout (``mesh_gather_plan``), the whole trig table is evaluated in ONE
# vectorized pass (``mesh_gather_tables`` — the scan paid two tiny libm
# calls per level), and the form extends to a leading SPSA-perturbation
# axis on the phases for free (``mesh_apply_stacked``) — the batched mesh
# engine of the ZO hot path (DESIGN.md §Photonic).

def mesh_gather_plan(layout: MeshLayout) -> tuple:
    """Static per-level gather plan ``(perm, slot, sign)``, each
    ``(levels, ports)``:

      * ``perm[c, w]``  — the wire paired with ``w`` at level ``c``
                          (``w`` itself when unpaired),
      * ``slot[c, w]``  — the slot index of the MZI acting on ``w``
                          (0 on unpaired wires; masked by ``sign``),
      * ``sign[c, w]``  — −1 on the first lane of a pair, +1 on the
                          second, 0 on unpaired wires.

    Memoized on the (frozen) layout — plans are reused across traces.
    """
    plan = getattr(layout, "_gather_plan", None)
    if plan is not None:
        return plan
    P = layout.ports
    L, S = layout.idx_a.shape
    perm = np.tile(np.arange(P, dtype=np.int32), (L, 1))
    slot = np.zeros((L, P), dtype=np.int32)
    sign = np.zeros((L, P), dtype=np.float32)
    for c in range(L):
        for k in range(S):
            if not layout.mask[c, k]:
                continue
            a, b = int(layout.idx_a[c, k]), int(layout.idx_b[c, k])
            perm[c, a], perm[c, b] = b, a
            slot[c, a] = slot[c, b] = k
            sign[c, a], sign[c, b] = -1.0, 1.0
    plan = (perm, slot, sign)
    object.__setattr__(layout, "_gather_plan", plan)
    return plan


def mesh_gather_tables(layout: MeshLayout, phases: jax.Array,
                       transpose: bool = False) -> tuple:
    """Per-wire trig tables ``(C, S)``, each ``(..., levels, ports)`` for
    phases ``(..., levels, slots)`` — in APPLICATION order (``transpose``
    reverses the level axis and negates the sines).  One vectorized
    cos/sin pass over the whole gathered table."""
    perm, slot, sign = mesh_gather_plan(layout)
    idx = jnp.broadcast_to(jnp.asarray(slot),
                           phases.shape[:-1] + slot.shape[-1:])
    ph = jnp.take_along_axis(phases, idx, axis=-1)        # (..., L, P)
    paired = sign != 0.0
    cos = jnp.where(paired, jnp.cos(ph), 1.0)
    sin = jnp.asarray(sign) * jnp.sin(ph)                 # sign 0 → 0
    if transpose:
        cos = jnp.flip(cos, axis=-2)
        sin = -jnp.flip(sin, axis=-2)
    return cos, sin


def _mesh_apply_gather(layout: MeshLayout, phases: jax.Array, diag: jax.Array,
                       x: jax.Array, transpose: bool) -> jax.Array:
    """Shared gather-form core: ``x (..., B, P)``, ``phases (..., L, slots)``
    and ``diag (..., P)`` with broadcast-compatible leading (stack) dims."""
    perm, _, _ = mesh_gather_plan(layout)
    cos, sin = mesh_gather_tables(layout, phases, transpose)
    perm_seq = jnp.asarray(perm[::-1].copy() if transpose else perm)

    if not transpose:
        x = x * diag[..., None, :].astype(x.dtype)

    # scan over levels: move the level axis of the tables to the front
    cs = jnp.moveaxis(cos, -2, 0).astype(x.dtype)
    sn = jnp.moveaxis(sin, -2, 0).astype(x.dtype)

    def level(xc, inp):
        pm, c, s = inp                                  # (P,), (..., P) ×2
        xg = jnp.take(xc, pm, axis=-1)
        return c[..., None, :] * xc + s[..., None, :] * xg, None

    x, _ = jax.lax.scan(level, x, (perm_seq, cs, sn))

    if transpose:
        x = x * diag[..., None, :].astype(x.dtype)
    return x


def mesh_apply(layout: MeshLayout, phases: jax.Array, diag: jax.Array,
               x: jax.Array, transpose: bool = False) -> jax.Array:
    """Apply the mesh unitary ``U`` (or ``Uᵀ``) to ``x`` with trailing dim P.

    U x computed as: x ← D x, then levels 0..C-1 each applying disjoint
    rotations R(φ)=[[c,-s],[s,c]] on wire pairs.  ``transpose=True`` runs
    levels in reverse with negated angles and applies D last.

    Gather formulation — same per-level arithmetic as the seed's scatter
    scan (``mesh_apply_scan``), matching it to float32 rounding (≤ 1 ulp
    per level from XLA fusion choices); see DESIGN.md §Photonic.
    """
    P = layout.ports
    batch_shape = x.shape[:-1]
    xf = x.reshape(-1, P)
    y = _mesh_apply_gather(layout, phases, diag, xf, transpose)
    return y.reshape(*batch_shape, P)


def mesh_apply_stacked(layout: MeshLayout, phases: jax.Array, diag: jax.Array,
                       x: jax.Array, transpose: bool = False) -> jax.Array:
    """``mesh_apply`` with a leading stack axis on the phases — the batched
    mesh engine of the multi-perturbation ZO sweep.

    phases: ``(S, levels, slots)`` — one phase set per SPSA perturbation.
    diag:   ``(P,)`` shared buffer or ``(S, P)`` stacked (identical rows
            when the buffers are fixed, as ZO training guarantees).
    x:      ``(B, P)`` shared across the stack (e.g. the identity feed of a
            densification, or the collocation batch of layer 1) or
            ``(S, B, P)`` per-perturbation activations.
    Returns ``(S, B, P)``; entry ``s`` is f32-identical to
    ``mesh_apply(layout, phases[s], diag[s], x[s])``.

    This is the jnp reference; ``repro.kernels.ops.mesh_apply_stacked``
    dispatches to the Pallas kernel (grid over stack × batch tiles, level
    chain looped in-kernel) under ``REPRO_KERNEL_MODE``.
    """
    S = phases.shape[0]
    if x.ndim == 2:
        x = jnp.broadcast_to(x[None], (S,) + x.shape)
    if diag.ndim == 1:
        diag = jnp.broadcast_to(diag[None], (S, diag.shape[0]))
    return _mesh_apply_gather(layout, phases, diag, x, transpose)


def mesh_apply_scan(layout: MeshLayout, phases: jax.Array, diag: jax.Array,
                    x: jax.Array, transpose: bool = False) -> jax.Array:
    """The seed's scatter-per-level ``lax.scan`` formulation, kept as the
    sequential photonic-realism reference: one rotation column at a time,
    exactly like light traversing the physical mesh.  The gather form
    (``mesh_apply``) applies the same arithmetic and agrees to f32
    rounding; parity is asserted in tests/test_photonic_stacked.py."""
    P = layout.ports
    batch_shape = x.shape[:-1]
    xf = x.reshape(-1, P)
    # scratch wire at index P absorbs padded scatter slots
    xf = jnp.concatenate([xf, jnp.zeros_like(xf[:, :1])], axis=-1)

    idx_a = jnp.asarray(layout.idx_a)
    idx_b = jnp.asarray(layout.idx_b)
    mask = jnp.asarray(layout.mask)

    if not transpose:
        xf = xf.at[:, :P].multiply(diag[None, :].astype(xf.dtype))

    def level(carry, inp):
        xc = carry
        ia, ib, m, ph = inp
        if transpose:
            ph = -ph
        a = xc[:, ia]  # (B, slots)
        b = xc[:, ib]
        c = jnp.cos(ph).astype(xc.dtype)[None, :]
        s = jnp.sin(ph).astype(xc.dtype)[None, :]
        na = c * a - s * b
        nb = s * a + c * b
        mm = m[None, :]
        na = jnp.where(mm, na, a)
        nb = jnp.where(mm, nb, b)
        xc = xc.at[:, ia].set(na, mode="drop")
        xc = xc.at[:, ib].set(nb, mode="drop")
        return xc, None

    seq = (idx_a, idx_b, mask, phases)
    if transpose:
        seq = jax.tree.map(lambda t: jnp.flip(t, axis=0), seq)
    xf, _ = jax.lax.scan(level, xf, seq)

    if transpose:
        xf = xf.at[:, :P].multiply(diag[None, :].astype(xf.dtype))
    return xf[:, :P].reshape(*batch_shape, P)


def mesh_matrix(layout: MeshLayout, phases: jax.Array, diag: jax.Array) -> jax.Array:
    """Densify the mesh unitary: U = mesh_apply(I).  Column convention:
    mesh_apply computes U @ x, so U[:, j] = mesh_apply(e_j)."""
    eye = jnp.eye(layout.ports, dtype=jnp.float32)
    # mesh_apply treats trailing dim as the vector; feed rows of I, get Uᵀ rows
    ut = mesh_apply(layout, phases, diag, eye)  # row i = U e_i ... careful:
    # eye rows are basis vectors e_i (trailing dim = wire); result row i = U e_i
    return ut.T  # so column i of U


def mesh_matrix_stacked(layout: MeshLayout, phases: jax.Array,
                        diag: jax.Array) -> jax.Array:
    """Densify S stacked mesh unitaries in one batched pass, sharing the
    identity feed: ``(S, levels, slots)`` phases → ``(S, P, P)`` with
    ``out[s] == mesh_matrix(layout, phases[s], diag[s])``."""
    eye = jnp.eye(layout.ports, dtype=jnp.float32)
    ut = mesh_apply_stacked(layout, phases, diag, eye)    # (S, P, P)
    return jnp.swapaxes(ut, -1, -2)


# ---------------------------------------------------------------------------
# Noise / imperfection models
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NoiseModel:
    """Paper §4.1 hardware imperfections, applied in phase domain."""

    gamma_mean: float = 1.0     # γ nominal
    gamma_std: float = 0.002    # σ_γ fabrication drift
    crosstalk: float = 0.005    # κ: thermal coupling to adjacent MZIs (same level)
    phase_bias_scale: float = 1.0  # β·U(0,2π); 1.0 = paper's full bias
    enabled: bool = True

    def sample(self, key: jax.Array, phase_shape: tuple) -> dict:
        if not self.enabled:
            return {
                "gamma": jnp.ones(phase_shape, dtype=jnp.float32),
                "bias": jnp.zeros(phase_shape, dtype=jnp.float32),
            }
        k1, k2 = jax.random.split(key)
        gamma = self.gamma_mean + self.gamma_std * jax.random.normal(k1, phase_shape)
        bias = self.phase_bias_scale * jax.random.uniform(
            k2, phase_shape, minval=0.0, maxval=2.0 * math.pi)
        return {"gamma": gamma.astype(jnp.float32), "bias": bias.astype(jnp.float32)}

    def effective_phases(self, phases: jax.Array, noise: dict) -> jax.Array:
        """Φ_eff = Ω (Γ ⊙ Φ) + Φ_b.  Ω mixes adjacent slots within a level
        (nearest physical neighbours on chip).

        Rank-agnostic: ``phases`` may carry arbitrary leading axes (e.g. the
        SPSA perturbation stack of ``mesh_apply_stacked``) on top of the
        trailing ``(levels, slots)``; the noise leaves broadcast (one
        physical chip is shared by every perturbed model), and the
        crosstalk pad only ever touches the trailing slot axis.
        """
        if not self.enabled:
            return phases
        p = noise["gamma"] * phases
        if self.crosstalk > 0.0 and p.shape[-1] > 1:
            keep = [(0, 0)] * (p.ndim - 1)
            left = jnp.pad(p[..., 1:], keep + [(0, 1)])
            right = jnp.pad(p[..., :-1], keep + [(1, 0)])
            p = p + self.crosstalk * (left + right)
        return p + noise["bias"]


# ---------------------------------------------------------------------------
# Photonic matrix  W = U Σ Vᵀ
# ---------------------------------------------------------------------------

class PhotonicMatrix:
    """An (out_dim × in_dim) matrix realized as U(Φ_U) Σ Vᵀ(Φ_V).

    Static pieces (layouts) live on the object; trainable pieces are a params
    dict {"phases_u", "phases_v", "sigma"} plus fixed buffers {"diag_u",
    "diag_v"}.  ``apply`` computes y = W x for trailing-dim-``in_dim`` x.
    """

    def __init__(self, out_dim: int, in_dim: int):
        self.out_dim = out_dim
        self.in_dim = in_dim
        self.layout_u = rectangular_layout(out_dim)
        self.layout_v = rectangular_layout(in_dim)
        self.k = min(out_dim, in_dim)

    # -- param construction ------------------------------------------------
    def init(self, key: jax.Array, scale: float | None = None) -> dict:
        ku, kv, ks = jax.random.split(key, 3)
        std = scale if scale is not None else math.sqrt(
            2.0 / (self.in_dim + self.out_dim))
        # random phases give a Haar-ish orthogonal pair; sigma sets the scale
        return {
            "phases_u": 0.1 * jax.random.normal(ku, self.layout_u.phase_shape()),
            "phases_v": 0.1 * jax.random.normal(kv, self.layout_v.phase_shape()),
            "sigma": std * math.sqrt(float(self.k)) * jnp.abs(
                1.0 + 0.1 * jax.random.normal(ks, (self.k,))),
            "diag_u": jnp.ones((self.out_dim,), dtype=jnp.float32),
            "diag_v": jnp.ones((self.in_dim,), dtype=jnp.float32),
        }

    def from_dense(self, w: np.ndarray) -> dict:
        """Map a trained dense W onto hardware phases (the 'off-chip' path)."""
        w = np.asarray(w, dtype=np.float64)
        assert w.shape == (self.out_dim, self.in_dim)
        u, s, vt = np.linalg.svd(w, full_matrices=True)
        lu, pu, du = decompose_orthogonal(u)
        lv, pv, dv = decompose_orthogonal(vt.T)
        self.layout_u, self.layout_v = lu, lv
        return {
            "phases_u": pu, "phases_v": pv,
            "sigma": jnp.asarray(s[: self.k], dtype=jnp.float32),
            "diag_u": du, "diag_v": dv,
        }

    # -- forward -------------------------------------------------------------
    @staticmethod
    def _dac_phases(pu: jax.Array, pv: jax.Array, quant) -> tuple:
        """Snap the COMMANDED phases to the DAC grid (quant.phase_bits)
        before the hardware noise model acts: the DAC drives the shifter,
        then fabrication imperfections corrupt what it commanded —
        Φ_eff = Ω(Γ ⊙ Q(Φ)) + Φ_b.  No-op (exact passthrough) when phase
        quantization is off."""
        if quant is None or not quant.phases:
            return pu, pv
        from repro.kernels import quant as quant_lib
        return (quant_lib.quantize_phases(pu, quant.phase_bits),
                quant_lib.quantize_phases(pv, quant.phase_bits))

    def apply(self, params: dict, x: jax.Array,
              noise_model: NoiseModel | None = None,
              noise: dict | None = None, quant=None) -> jax.Array:
        pu, pv = self._dac_phases(params["phases_u"], params["phases_v"],
                                  quant)
        if noise_model is not None and noise is not None:
            pu = noise_model.effective_phases(pu, noise["u"])
            pv = noise_model.effective_phases(pv, noise["v"])
        # y = U Σ Vᵀ x
        z = mesh_apply(self.layout_v, pv, params["diag_v"], x, transpose=True)
        k = self.k
        sig = params["sigma"].astype(z.dtype)
        z = z[..., :k] * sig
        if self.out_dim > k:
            pad = jnp.zeros(z.shape[:-1] + (self.out_dim - k,), dtype=z.dtype)
            z = jnp.concatenate([z, pad], axis=-1)
        return mesh_apply(self.layout_u, pu, params["diag_u"], z)

    def apply_stacked(self, params: dict, x: jax.Array,
                      noise_model: NoiseModel | None = None,
                      noise: dict | None = None, quant=None) -> jax.Array:
        """``apply`` over a leading SPSA-perturbation axis S on the params
        (phases/sigma stacked; diag buffers ``(P,)`` shared or ``(S, P)``
        with identical rows): x ``(B, in)`` shared or ``(S, B, in)`` →
        ``(S, B, out)``.  Hardware noise is SHARED across the stack — one
        physical chip.  Routed through the kernel dispatcher
        (``repro.kernels.ops.mesh_apply_stacked``)."""
        from repro.kernels import ops
        pu, pv = self._dac_phases(params["phases_u"], params["phases_v"],
                                  quant)
        if noise_model is not None and noise is not None:
            pu = noise_model.effective_phases(pu, noise["u"])
            pv = noise_model.effective_phases(pv, noise["v"])
        z = ops.mesh_apply_stacked(self.layout_v, pv, params["diag_v"], x,
                                   transpose=True)
        k = self.k
        sig = params["sigma"].astype(z.dtype)                  # (S, k)
        z = z[..., :k] * sig[:, None, :]
        if self.out_dim > k:
            pad = jnp.zeros(z.shape[:-1] + (self.out_dim - k,), dtype=z.dtype)
            z = jnp.concatenate([z, pad], axis=-1)
        return ops.mesh_apply_stacked(self.layout_u, pu, params["diag_u"], z)

    def sample_noise(self, key: jax.Array, model: NoiseModel) -> dict:
        ku, kv = jax.random.split(key)
        return {"u": model.sample(ku, self.layout_u.phase_shape()),
                "v": model.sample(kv, self.layout_v.phase_shape())}

    def to_dense(self, params: dict, noise_model: NoiseModel | None = None,
                 noise: dict | None = None, quant=None) -> jax.Array:
        eye = jnp.eye(self.in_dim, dtype=jnp.float32)
        cols = self.apply(params, eye, noise_model, noise,
                          quant=quant)  # row j = W e_j
        return cols.T

    def to_dense_stacked(self, params: dict,
                         noise_model: NoiseModel | None = None,
                         noise: dict | None = None, quant=None) -> jax.Array:
        """Densify S stacked parameter sets in ONE batched pass sharing the
        identity feed: → ``(S, out, in)`` with entry ``s`` f32-identical to
        ``to_dense`` of the per-index params.  This is the TONN hot-path
        primitive: all N+1 SPSA-perturbed core meshes densify together."""
        eye = jnp.eye(self.in_dim, dtype=jnp.float32)
        cols = self.apply_stacked(params, eye, noise_model, noise,
                                  quant=quant)
        return jnp.swapaxes(cols, -1, -2)

    @property
    def num_mzis(self) -> int:
        return self.layout_u.num_mzis + self.layout_v.num_mzis


def mzi_count_matrix(out_dim: int, in_dim: int) -> int:
    """MZIs for an SVD-implemented (out×in) matrix: two square meshes."""
    return out_dim * (out_dim - 1) // 2 + in_dim * (in_dim - 1) // 2
