"""Distributed BP-free ZO training: the SPSA sweep sharded over a device
mesh (DESIGN.md §Distributed — the wire protocol, the gradient-identity
contract across mesh layouts, and why parameter traffic is zero).

The paper's scaling claim is that zeroth-order training communicates only
*scalars*: every per-perturbation loss ``L(Φ + μ ξ_i)`` is a single number,
and with a shared PRNG seed each worker can regenerate every ξ_i locally.
This module turns that claim into an executable ``shard_map`` program over
an explicit two-axis ``Mesh``:

  * **perturbation sharding** (axis ``"pert"``) — each device evaluates its
    contiguous slice of the N+1 stacked losses (base loss rides along as
    perturbation 0, exactly like the fused single-device path) through the
    model's ``residual_losses_stacked``-style batched evaluator, scatters
    the slice into an (N+1)-vector, and ONE ``psum`` reconstructs the full
    loss vector everywhere.
  * **collocation-batch sharding** (axis ``"batch"``) — the global
    collocation batch is split over devices; each device evaluates its own
    batch shard and the per-shard mean losses are ``pmean``-reduced into the
    full-batch losses *before* the SPSA reconstruction, so the gradients
    every device materializes are identical across mesh layouts (up to f32
    reassociation of the batch mean — see the contract below).

Both axes compose (``shard="both"``).  Per step, the ONLY cross-device
traffic is the psum of the padded (N+1)-vector of f32 scalars plus the
pmean of each device's local loss slice — O(N) scalars, independent of the
model size.  Parameters, perturbations, and gradients never cross a device
boundary: every device regenerates the ξ stack from the shared step key and
contracts the psum-merged loss deltas against it locally
(``zoo.spsa_gradient_from_losses``).  ``measure_collective_bytes`` verifies
this from the compiled HLO — tests/test_distribution.py asserts the
measured bytes-on-wire against the O(N)-scalar bound.

Gradient-identity contract: for a fixed ``(params, key, xt)``, the gradient
returned by ``make_distributed_zo_step`` is identical across ALL mesh
layouts (1×1, P×1, 1×B, P×B) and equal to the single-device fused
``zoo.spsa_gradient`` within float32 tolerance.  Each loss L_i is computed
on exactly one device from bit-identical inputs (same regenerated ξ, same
collocation points), and two measured rules keep the evaluations themselves
bit-stable (XLA specializes degenerate shapes into differently-rounded
GEMMs): per-device perturbation slices are floored at 2 entries
(``pert_shard_size``), and per-device batch shards should hold ≥ 8
collocation points.  Within those bounds pure perturbation sharding is
BIT-identical to the single-device fused sweep, and batch sharding differs
only by the reassociated batch-mean reduction (~1e-7 relative on the losses
— no FD amplification, because the per-point residuals keep their bits).
``tests/test_distribution.py`` asserts this on 8 forced-host devices;
DESIGN.md §Distributed records the full contract.

Elastic resizing (``repro.runtime.elastic.ZOElasticController``): because
parameters are replicated — the protocol shards *work*, not state — a
device-count change is just "rebuild the step for the new mesh": the
perturbation slices re-resolve from the new axis size and a checkpoint
taken on any layout resumes on any other.

Typical use::

    mesh = make_zo_mesh("4x2")                 # 4-way pert × 2-way batch
    step = make_distributed_zo_step(mesh, batched_loss_fn, cfg)
    params, state, loss = step(params, state, xt, bc, lr)
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import zoo

__all__ = [
    "PERT_AXIS", "BATCH_AXIS", "ZOShardConfig",
    "make_zo_mesh", "pert_shard_size",
    "spsa_gradient_sharded", "zo_signsgd_step_sharded",
    "make_distributed_zo_step", "make_distributed_spsa_gradient",
    "local_replica", "measure_collective_bytes", "wire_bound_bytes",
]

PyTree = Any

PERT_AXIS = "pert"    # SPSA-perturbation sharding axis
BATCH_AXIS = "batch"  # collocation-batch sharding axis


@dataclasses.dataclass(frozen=True)
class ZOShardConfig:
    """Static layout of the distributed sweep (derived from the mesh).

    ``num_pert_shards``/``num_batch_shards`` are baked into the program as
    Python ints (slice sizes must be static under ``shard_map``); only the
    *which-slice* decision is traced via ``lax.axis_index``.
    """
    num_pert_shards: int = 1
    num_batch_shards: int = 1
    pert_axis: str = PERT_AXIS
    batch_axis: str = BATCH_AXIS

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "ZOShardConfig":
        return cls(num_pert_shards=int(mesh.shape[PERT_AXIS]),
                   num_batch_shards=int(mesh.shape[BATCH_AXIS]))


def pert_shard_size(n_total: int, n_shards: int) -> int:
    """Per-device slice of ``n_total`` stacked losses (ceil division: the
    stack is zero-padded up to ``per * n_shards`` so every device runs the
    same static-shape program).

    The slice is floored at 2: XLA specializes a unit leading batch dim
    into differently-tiled GEMMs, which breaks the bitwise gradient-identity
    contract across mesh layouts (measured: per ∈ {2..8} slices of the
    stacked PINN evaluator are bit-identical to the full-stack evaluation;
    per=1 drifts at the 1e-7 forward level, which the FD loss amplifies by
    1/h²).  The cost is at most one wasted padded entry per device on
    layouts where N+1 < 2·n_shards.
    """
    if n_shards <= 1:
        return n_total
    return max(2, -(-n_total // n_shards))


def make_zo_mesh(spec: str | None = None, shard: str | None = None,
                 devices=None) -> Mesh:
    """Explicit ZO mesh with axes ``("pert", "batch")``.

    ``spec`` is ``"PxB"`` (e.g. ``"4x2"``) or a bare device count assigned
    to the axis named by ``shard``; ``None`` puts all (given) devices on
    that axis.  ``shard`` defaults to ``"perturbation"``; with an explicit
    ``"PxB"`` spec it is redundant and only validated — a contradiction
    (e.g. ``shard="perturbation"`` with a batch axis > 1) raises instead of
    silently building a layout the caller did not ask for.
    ``shard="both"`` with no explicit spec picks the most balanced P×B
    factorization.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if shard is not None and shard not in ("perturbation", "batch", "both"):
        raise ValueError(f"unknown shard mode {shard!r}")
    if spec and "x" in spec:
        p, b = (int(v) for v in spec.split("x"))
        ok = {None: True, "perturbation": b == 1, "batch": p == 1,
              "both": True}[shard]
        if not ok:
            raise ValueError(
                f"mesh {spec} contradicts shard={shard!r} (a "
                f"{'batch' if shard == 'perturbation' else 'pert'} axis "
                f"> 1); use shard='both' for a 2-D layout")
    elif spec:
        p, b = (int(spec), 1) if shard != "batch" else (1, int(spec))
    elif shard in (None, "perturbation"):
        p, b = n, 1
    elif shard == "batch":
        p, b = 1, n
    else:  # both
        p = next(d for d in range(int(np.sqrt(n)), 0, -1) if n % d == 0)
        p, b = n // p, p
    if p * b > n:
        raise ValueError(f"mesh {p}x{b} needs {p * b} devices, have {n}")
    return Mesh(np.array(devices[:p * b]).reshape(p, b),
                (PERT_AXIS, BATCH_AXIS))


def _augmented_perturbations(key: jax.Array, params: PyTree, n: int,
                             n_pad: int,
                             trainable_mask: PyTree | None = None) -> tuple:
    """(xis, aug): the N sampled perturbations plus the padded evaluation
    stack [0, ξ_1..ξ_N, 0...] of length ``n_pad`` (entry 0 is the base loss;
    zero-padding re-evaluates the base — wasted only on non-divisible
    layouts, and masked out of the merged vector).  Buffer leaves
    (``trainable_mask`` False) carry zero ξ across the stack."""
    xis = zoo.sample_perturbations(key, params, n, trainable_mask)
    aug = jax.tree.map(
        lambda z: jnp.concatenate(
            [jnp.zeros_like(z[:1]), z,
             jnp.zeros((n_pad - n - 1,) + z.shape[1:], z.dtype)]),
        xis)
    return xis, aug


def spsa_gradient_sharded(batched_loss_fn: Callable[[PyTree, jax.Array], jax.Array],
                          params: PyTree, key: jax.Array, xt: jax.Array,
                          cfg: zoo.SPSAConfig, shard_cfg: ZOShardConfig,
                          trainable_mask: PyTree | None = None,
                          ) -> tuple:
    """Distributed Eq. (5) — runs INSIDE ``shard_map``. Returns (grad, base).

    ``batched_loss_fn(stacked_params, xt) -> (P,) losses`` evaluates a
    stacked parameter pytree on the device's (possibly batch-sharded) local
    collocation points; when batch-sharded it must reduce each loss as a
    MEAN over its batch axis so the cross-device ``pmean`` reconstructs the
    global-batch mean.

    Every device regenerates the full ξ stack from the shared ``key``
    (replicated compute, zero traffic), evaluates its ``axis_index`` slice
    of the padded [base, ξ_1..ξ_N] stack, and the loss vector is merged by
    one psum; the gradient is then reconstructed locally against the full
    stack, so all devices hold identical gradients.
    """
    if cfg.antithetic:
        raise NotImplementedError(
            "antithetic SPSA is not wired through the sharded path; "
            "use the single-device fused path (zoo.spsa_gradient)")
    n = cfg.num_samples
    npert, nbatch = shard_cfg.num_pert_shards, shard_cfg.num_batch_shards
    per = pert_shard_size(n + 1, npert)
    n_pad = per * npert
    xis, aug = _augmented_perturbations(key, params, n, n_pad, trainable_mask)

    if npert > 1:
        w = jax.lax.axis_index(shard_cfg.pert_axis)
        local = jax.tree.map(
            lambda z: jax.lax.dynamic_slice_in_dim(z, w * per, per, axis=0),
            aug)
    else:
        w, local = 0, aug
    lp = batched_loss_fn(
        jax.tree.map(lambda p, z: p + cfg.mu * z.astype(p.dtype),
                     params, local), xt)
    lp = lp.astype(jnp.float32)
    if nbatch > 1:
        # merge the batch shards FIRST: each device's slice becomes the
        # full-batch mean loss before the SPSA reconstruction sees it
        lp = jax.lax.pmean(lp, shard_cfg.batch_axis)
    if npert > 1:
        vec = jax.lax.dynamic_update_slice(
            jnp.zeros((n_pad,), jnp.float32), lp, (w * per,))
        vec = jax.lax.psum(vec, shard_cfg.pert_axis)
    else:
        vec = lp
    base = vec[0]
    grad = zoo.spsa_gradient_from_losses(params, key, vec[1:n + 1], base,
                                         cfg, xis=xis)
    return grad, base


def zo_signsgd_step_sharded(batched_loss_fn, params: PyTree,
                            state: zoo.ZOState, xt: jax.Array, lr,
                            cfg: zoo.SPSAConfig, shard_cfg: ZOShardConfig,
                            trainable_mask: PyTree | None = None,
                            ) -> tuple:
    """One distributed Eq. (6) update (inside shard_map).
    Returns (params, state, base_loss); all outputs replicated."""
    key, sub = jax.random.split(state.key)
    grad, base = spsa_gradient_sharded(batched_loss_fn, params, sub, xt,
                                       cfg, shard_cfg, trainable_mask)
    upd = jax.tree.map(jnp.sign, grad) if cfg.sign_update else grad
    new_params = jax.tree.map(lambda p, u: p - lr * u.astype(p.dtype),
                              params, upd)
    return new_params, zoo.ZOState(step=state.step + 1, key=key), base


def make_distributed_zo_step(mesh: Mesh, batched_loss_fn,
                             cfg: zoo.SPSAConfig, *, donate: bool = True,
                             trainable_mask: PyTree | None = None,
                             ) -> Callable:
    """Build the jitted distributed step for ``mesh``.

    ``batched_loss_fn(stacked_params, xt, bc) -> (P,) losses`` — e.g.
    ``lambda sp, xt, bc: pinn.residual_losses_stacked(model, sp, xt, bc=bc)``.

    Returns ``step(params, state, xt, bc, lr) -> (params, state, loss)``:
    params/state replicated in and out, ``xt`` split over the batch axis
    (its leading dim must be divisible by the batch-axis size), ``bc``
    replicated LEAF-WISE — a legacy ``(xb, ub)`` boundary pair or the
    composite-loss engine's ``{term_name: (x, target)}`` dict both thread
    through unchanged (the boundary/data terms are O(batch/4) and
    evaluated identically everywhere — see DESIGN.md §Distributed).  Rebuilding for a different
    mesh is the whole elastic-resize story: parameters are replicated, so
    nothing needs re-sharding (``runtime.elastic.ZOElasticController``).
    ``trainable_mask`` (replicated static structure) excludes fixed buffers
    — e.g. the photonic ±1 diags (``TensorPinn.trainable_mask``) — from
    the regenerated ξ stacks on every device, keeping them bit-identical.
    """
    shard_cfg = ZOShardConfig.from_mesh(mesh)

    def worker(params, state, xt, bc, lr):
        blf = lambda sp, x: batched_loss_fn(sp, x, bc)
        return zo_signsgd_step_sharded(blf, params, state, xt, lr,
                                       cfg, shard_cfg, trainable_mask)

    sharded = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(), P(), P(shard_cfg.batch_axis), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)

    def step(params, state, xt, bc, lr):
        if xt.shape[0] % shard_cfg.num_batch_shards:
            raise ValueError(
                f"global batch {xt.shape[0]} not divisible by the "
                f"{shard_cfg.num_batch_shards}-way batch axis")
        return sharded(params, state, xt, bc, lr)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def local_replica(tree: PyTree) -> PyTree:
    """The first local replica of a mesh-replicated pytree, as single-device
    arrays (no copy).  Code outside the ``shard_map`` (validation, per-term
    logging) evaluates on it: GSPMD cannot partition a Pallas kernel over
    the mesh, so a forward on the replicated arrays does not compile on
    TPU."""
    return jax.tree.map(lambda a: a.addressable_data(0), tree)


def wire_bound_bytes(num_samples: int, n_pert: int, slack: int = 4) -> int:
    """The O(N)-scalar per-device traffic budget of one distributed step:
    the psum of the zero-padded (N+1)-vector plus the pmean of the local
    slice, all f32, plus a few scalars of slack.  The single home of the
    bound that tests assert ``measure_collective_bytes`` against."""
    per = pert_shard_size(num_samples + 1, n_pert)
    return 4 * (per * n_pert + per + slack)


def make_distributed_spsa_gradient(mesh: Mesh, batched_loss_fn,
                                   cfg: zoo.SPSAConfig,
                                   trainable_mask: PyTree | None = None,
                                   ) -> Callable:
    """Gradient-only counterpart of ``make_distributed_zo_step``: a jitted
    ``(params, key, xt) -> (grad, base_loss)`` over the mesh.  This is what
    the gradient-identity tests compare against the single-device
    ``zoo.spsa_gradient`` — same ξ, same layout-invariant result."""
    shard_cfg = ZOShardConfig.from_mesh(mesh)
    sharded = jax.shard_map(
        lambda p, k, x: spsa_gradient_sharded(batched_loss_fn, p, k, x,
                                              cfg, shard_cfg, trainable_mask),
        mesh=mesh, in_specs=(P(), P(), P(shard_cfg.batch_axis)),
        out_specs=(P(), P()), check_vma=False)
    return jax.jit(sharded)


# ------------------------------------------------------- traffic measurement

_COLLECTIVE_RE = re.compile(
    r"=\s*([^=\n]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter"
    r"|collective-permute|all-to-all)(?:-start)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}


def measure_collective_bytes(fn: Callable, *args) -> dict:
    """Per-device bytes crossing the device boundary per call of ``fn``,
    measured from the compiled (optimized SPMD) HLO: every collective op's
    result size, summed (tuple-shaped combined collectives included; async
    start/done pairs counted once).  This is what the O(N)-scalar claim is
    asserted against — a parameter-sized transfer shows up here immediately.

    Returns ``{"bytes": int, "ops": [(op, shape, bytes), ...]}``.
    """
    lowered = fn.lower(*args) if hasattr(fn, "lower") \
        else jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    ops = []
    total = 0
    for m in _COLLECTIVE_RE.finditer(text):
        # async start/done pairs: the '-start' suffix sits outside the op
        # group, and '-done' ops never match (the regex requires '(' right
        # after the optional suffix), so each collective is counted once
        shapes, op = m.group(1), m.group(2)
        nbytes = 0
        for dtype, dims in _SHAPE_RE.findall(shapes):
            elems = int(np.prod([int(d) for d in dims.split(",") if d]
                                or [1]))
            nbytes += elems * _DTYPE_BYTES.get(dtype, 4)
        ops.append((op, shapes, nbytes))
        total += nbytes
    return {"bytes": total, "ops": ops}
