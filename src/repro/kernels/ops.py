"""Public jit'd entry points for the Pallas kernels with backend dispatch.

``KernelMode``:
  * "pallas"     — compiled Pallas (TPU target),
  * "interpret"  — Pallas interpret=True (CPU validation of the kernel body),
  * "ref"        — pure-jnp oracle (default on CPU; XLA fuses well enough for
                   correctness work and the dry-run only lowers HLO anyway).

Model code calls these wrappers and never touches pallas_call directly, so a
single env var (``REPRO_KERNEL_MODE``) flips the whole framework.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import photonic as _ph
from repro.core import tt as tt_lib
from repro.kernels import flash_attention as _fa
from repro.kernels import mesh_apply as _mesh
from repro.kernels import quant as _quant
from repro.kernels import ref as _ref
from repro.kernels import tt_contract as _ttc

__all__ = ["kernel_mode", "tt_impl", "tt_path", "mesh_impl", "mesh_path",
           "tt_linear", "tt_linear_batched", "tt_stencil_prologue",
           "tt_linear_stencil", "mesh_apply_stacked",
           "attention", "KERNEL_MODES"]

KERNEL_MODES = ("pallas", "interpret", "ref")

# the one-hot body unrolls its level chain: past this many levels it stops
# being worth compiling (onn-sized meshes: levels == ports, e.g. 1024)
MESH_KERNEL_MAX_LEVELS = 128
# the one-hot permutation stack (levels × P × P f32) must leave VMEM room
# for the batch tile; past this footprint the grid would degrade to tiny
# tiles re-streaming the table from HBM.  Deeper or wider rectangular
# meshes take the ``mesh_rect`` body; other layouts the jnp path.
MESH_KERNEL_MAX_ONEHOT_BYTES = 2 * 2**20


def kernel_mode() -> str:
    mode = os.environ.get("REPRO_KERNEL_MODE")
    if mode:
        if mode not in KERNEL_MODES:
            raise ValueError(
                f"unknown REPRO_KERNEL_MODE {mode!r}; "
                f"allowed values: {', '.join(KERNEL_MODES)}")
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def tt_impl(spec: tt_lib.TTSpec, mode: str | None = None) -> str:
    """Implementation the TT dispatchers take for ``spec``: the kernel mode,
    or "ref" when W and its tables do not fit the kernel's VMEM budget."""
    mode = mode or kernel_mode()
    return mode if mode == "ref" or _ttc.fits_vmem(spec) else "ref"


def tt_path(spec: tt_lib.TTSpec, mode: str | None = None) -> str:
    """Body the TT dispatchers take for ``spec``: "kron" (the kernel
    contracts through the interior rank-1 split ``W_L ⊗ W_R``), "dense"
    (the kernel builds W), or "ref" (the jnp chain).  Static per spec."""
    if tt_impl(spec, mode) == "ref":
        return "ref"
    return "kron" if _ttc.kron_factors(spec) else "dense"


def _onehot_fits(layout) -> bool:
    return (layout.levels <= MESH_KERNEL_MAX_LEVELS
            and 4 * layout.levels * layout.ports * layout.ports
            <= MESH_KERNEL_MAX_ONEHOT_BYTES)


def mesh_impl(layout, mode: str | None = None) -> str:
    """Implementation ``mesh_apply_stacked`` takes for ``layout``: the
    kernel mode, or "ref" for deep or wide meshes that are not
    rectangular."""
    mode = mode or kernel_mode()
    fits = _onehot_fits(layout) or _mesh.is_rectangular(layout)
    return mode if fits else "ref"


def mesh_path(layout, mode: str | None = None) -> str:
    """Body ``mesh_apply_stacked`` takes for ``layout``: "onehot" (levels
    unrolled, one-hot permutation matmuls), "rect" (deep rectangular
    meshes: wire pairs by lane rolls, levels looped), or "ref" (the jnp
    gather path).  Static per layout."""
    if mesh_impl(layout, mode) == "ref":
        return "ref"
    return "onehot" if _onehot_fits(layout) else "rect"


def _weight_quant(quant) -> bool:
    return quant is not None and quant.weights


def tt_linear(x: jax.Array, cores: Sequence[jax.Array], spec: tt_lib.TTSpec,
              mode: str | None = None, quant=None) -> jax.Array:
    mode = tt_impl(spec, mode)
    if _weight_quant(quant):
        if mode == "ref":
            return _ref.tt_contract_quant_ref(x, cores, spec, quant)
        # the single-chain hot path is serving-only and tiny; fake-quant
        # the cores (same quantizer the batched kernel dequantizes from
        # VMEM) and reuse the f32 kernel — math identical to the ref path
        cores = [_quant.fake_quant(c, quant) for c in cores]
        return _ttc.tt_contract(x, tuple(cores), spec,
                                interpret=(mode == "interpret"))
    if mode == "ref":
        return _ref.tt_contract_ref(x, cores, spec)
    return _ttc.tt_contract(x, tuple(cores), spec,
                            interpret=(mode == "interpret"))


def tt_linear_batched(x: jax.Array, cores: Sequence[jax.Array],
                      spec: tt_lib.TTSpec,
                      mode: str | None = None, quant=None,
                      shared_x: bool | None = None) -> jax.Array:
    """P stacked TT-linears in one program — the ZO multi-perturbation path.

    cores: each ``(P, r, m, n, r')``; x ``(B, N)`` shared or ``(P, B, N)``.
    Extra batch axes (e.g. a perturbations × coefficients × points input)
    are flattened for the launch and restored on the output; ``shared_x``
    disambiguates when rank inference is ambiguous (None = legacy rule:
    rank 2 shared, otherwise per-P with a leading P axis).
    With weight quantization on (``quant.weights``), ref mode fake-quants
    in pure jnp (the CPU oracle) and pallas/interpret dispatch to the
    narrow-dtype kernel that dequantizes block-scaled cores in VMEM —
    both see bit-identical weights and accumulate f32.  Specs whose dense W
    does not fit VMEM take the jnp chain (``tt_impl``); ``tt_path`` names
    the kernel body a spec takes.
    """
    mode = tt_impl(spec, mode)
    if _weight_quant(quant):
        if mode == "ref":
            return _ref.tt_contract_batched_quant_ref(x, cores, spec, quant,
                                                      shared_x=shared_x)
        return _ttc.tt_contract_batched_quant(
            x, tuple(cores), spec, quant, interpret=(mode == "interpret"),
            shared_x=shared_x)
    if mode == "ref":
        return _ref.tt_contract_batched_ref(x, cores, spec,
                                            shared_x=shared_x)
    return _ttc.tt_contract_batched(x, tuple(cores), spec,
                                    interpret=(mode == "interpret"),
                                    shared_x=shared_x)


def tt_stencil_prologue(spec: tt_lib.TTSpec, mode: str | None = None,
                        quant=None) -> bool:
    """True iff ``tt_linear_stencil`` can take ``spec``: the unquantized
    kernel (pallas or interpret), which builds the FD stencil's rows from
    their rank-1 factors in VMEM.  Static per spec and mode."""
    return tt_impl(spec, mode) != "ref" and not _weight_quant(quant)


def tt_linear_stencil(z0: jax.Array, hcols: jax.Array,
                      cores: Sequence[jax.Array], spec: tt_lib.TTSpec,
                      mode: str | None = None) -> jax.Array:
    """Layer 1 of the rank-1 FD stencil on P stacked core sets:
    ``tt_contract.tt_contract_stencil`` (rows ``sin(z0 ± hcols)`` built in
    the kernel, B padded to a multiple of 8 per slot).  Only where
    ``tt_stencil_prologue(spec, mode)``."""
    if not tt_stencil_prologue(spec, mode):
        raise ValueError(f"the stencil prologue needs the kernel; {spec} "
                         f"takes {tt_impl(spec, mode)!r}")
    return _ttc.tt_contract_stencil(z0, hcols, tuple(cores), spec,
                                    interpret=(tt_impl(spec, mode)
                                               == "interpret"))


def mesh_apply_stacked(layout, phases: jax.Array, diag: jax.Array,
                       x: jax.Array, transpose: bool = False,
                       mode: str | None = None, quant=None) -> jax.Array:
    """S stacked MZI-mesh applications in one program — the batched
    photonic engine of the phase-domain ZO path.

    phases ``(S, levels, slots)`` (one set per SPSA perturbation), diag
    ``(P,)`` shared buffer or ``(S, P)``, x ``(B, P)`` shared or
    ``(S, B, P)``; returns ``(S, B, P)``.  Dispatches by ``mesh_path``
    between the two Pallas bodies (one-hot for shallow meshes; ``mesh_rect``
    for deep or wide rectangular ones) and the jnp gather reference
    (``photonic.mesh_apply_stacked``), which deep or wide meshes of any
    other layout always take.

    ``quant`` with ``phase_bits`` set snaps the commanded phases to the
    uniform DAC grid before EITHER backend runs — the quantization is a
    property of the hardware being simulated, not of the kernel, so all
    modes see identical quantized phases.  (Callers going through
    ``PhotonicMatrix`` quantize before the noise model instead and pass
    quant=None here — idempotence makes the double hook safe anyway.)
    """
    path = mesh_path(layout, mode)
    if quant is not None and quant.phases:
        phases = _quant.quantize_phases(phases, quant.phase_bits)
    if path == "ref":
        return _ph.mesh_apply_stacked(layout, phases, diag, x, transpose)
    body = (_mesh.mesh_apply_stacked_pallas if path == "onehot"
            else _mesh.mesh_apply_rect_pallas)
    return body(layout, phases, diag, x, transpose=transpose,
                interpret=(mesh_impl(layout, mode) == "interpret"))


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, window: int | None = None,
              scale: float | None = None, mode: str | None = None) -> jax.Array:
    mode = mode or kernel_mode()
    if mode == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=(mode == "interpret"))
