#!/usr/bin/env python3
"""Chip smoke test: the paper-width BP-free PINN trainer and the PDE serving
engine, run once through their normal entry points on a TPU.

    python chip_smoke.py                # one chip: train + serve phases
    python chip_smoke.py --four-chips   # four chips: distributed ZO only

One chip.  The train phase calls ``repro.launch.train.main`` with
``--arch tensor-pinn --pde hjb-20d`` at the paper's width (hidden 1024,
tonn, fabrication noise, fd_fast, N=10, batch 100) for a few ZO-signSGD
steps and checkpoints under ``--out``.  It then checks that

  * every TT layer and every core mesh takes the compiled Pallas kernel,
    and the compiled (P = N+1)-stacked loss holds the ``tt_contract`` and
    ``mesh_apply_stacked`` kernels;
  * the step-0 losses of all N+1 perturbed models through the kernels agree
    with the plain jnp path (``REPRO_KERNEL_MODE=ref`` at HIGHEST matmul
    precision) within the FD noise floor of DESIGN.md §Perf;
  * a slice of the stack, the size a device evaluates on four chips, gives
    the same losses as the full stack.

The serve phase loads that checkpoint with ``SolverRegistry.load_checkpoint``,
warms ``PdeServingEngine`` up, serves requests of mixed size through
``submit``/``run`` and checks the served u against a direct ``TensorPinn.u``
forward with no compile after the warm-up.

Four chips.  ``--four-chips`` runs only the perturbation-sharded ZO path:
the trainer with ``--shard perturbation --mesh 4x1`` for a few steps, then
the distributed SPSA gradient against the single-device fused gradient for
the same seed and ξ (the identity contract of DESIGN.md §Distributed), the
devices the result spans and the collectives in its compiled HLO.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU
(or without the repository's ``src/`` beside this file) the script exits
non-zero before printing it; a failed check raises and exits non-zero too.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import enable_compile_cache  # noqa: E402

PDE = "hjb-20d"
SEED = 0
BATCH = 100          # paper: 100 collocation points per step
N_SAMPLES = 10       # paper: N = 10 SPSA perturbations
MU = 0.01
TRAIN_STEPS = 3
# the FD residual squares (u₊ − 2u₀ + u₋)/h², so ~1e-7 forward rounding
# reaches the losses as ~1e-3..1e-2 relative (DESIGN.md §Perf)
FD_LOSS_RTOL = 1e-1
# mixed request sizes: single points, partial slots, a full slot, a
# request spanning several slots and one spanning engine steps
REQUEST_SIZES = (1, 7, 100, 256, 600, 2085)
SLOTS, SLOT_POINTS = 8, 256


def train_args(hidden: int | None, steps: int) -> list:
    args = ["--arch", "tensor-pinn", "--pde", PDE, "--pinn-mode", "tonn",
            "--pinn-noise", "--batch", str(BATCH),
            "--zo-samples", str(N_SAMPLES), "--seed", str(SEED),
            "--steps", str(steps), "--log-every", "1"]
    return args + (["--hidden", str(hidden)] if hidden else [])


@dataclasses.dataclass
class Setup:
    """The trainer's step-0 state, rebuilt as ``train_pinn`` builds it."""

    model: object
    params: dict
    noise: dict | None
    mask: dict
    xt: jax.Array
    tb: dict
    key: jax.Array          # the first step's SPSA key

    def loss_fn(self):
        from repro.core import pinn
        return lambda p: pinn.residual_loss(
            self.model, p, self.xt, self.noise, term_batches=self.tb)

    def batched_loss_fn(self):
        from repro.core import pinn
        return lambda sp, x: pinn.residual_losses_stacked(
            self.model, sp, x, self.noise, term_batches=self.tb)


def paper_setup(hidden: int | None = None) -> Setup:
    from repro.configs.hjb_pinn import pinn_config
    from repro.core import pinn, zoo
    from repro.data import pde_collocation_iterator, pde_term_batch_iterator
    cfg = pinn_config(PDE, "tonn", fused=True, noise=True,
                      **({"hidden": hidden} if hidden else {}))
    model = pinn.TensorPinn(cfg)
    key = jax.random.PRNGKey(SEED)
    params = model.init(key)
    noise = model.sample_noise(jax.random.fold_in(key, 99))
    xt = next(pde_collocation_iterator(BATCH, seed=SEED, pde=PDE))
    tb = next(pde_term_batch_iterator(max(BATCH // 4, 8), seed=SEED,
                                      problem=model.problem))
    _, sub = jax.random.split(zoo.ZOState.create(SEED + 1).key)
    return Setup(model, params, noise, model.trainable_mask(params), xt, tb,
                 sub)


def perturbed_stack(s: Setup) -> dict:
    """The N+1 parameter sets the first ZO step evaluates: base + μ·ξ_i."""
    from repro.core import zoo
    xis = zoo.sample_perturbations(s.key, s.params, N_SAMPLES, s.mask)
    return jax.tree.map(
        lambda p, z: p + MU * jnp.concatenate([jnp.zeros_like(z[:1]), z]),
        s.params, xis)


@contextlib.contextmanager
def kernel_mode(mode: str):
    old = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = mode
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_MODE"]
        else:
            os.environ["REPRO_KERNEL_MODE"] = old


def compiled_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels in compiled HLO, by kernel name."""
    return collections.Counter(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", hlo_text))


# ----------------------------------------------------------------- phases

def train_phase(out: Path, hidden: int | None, steps: int,
                extra: tuple = ()) -> Path:
    from repro.launch import train
    ckpt = out / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    t0 = time.perf_counter()
    train.main(train_args(hidden, steps) + ["--ckpt-dir", str(ckpt)]
               + list(extra))
    print(f"[smoke] train: {steps} ZO-signSGD steps in "
          f"{time.perf_counter() - t0:.3f} s wall (step 0 includes compile)")
    return ckpt


def kernel_phase(s: Setup, stack: dict):
    """Which implementation each kernel takes, and what the compiled
    stacked loss really holds; returns that compiled loss."""
    from repro.kernels import ops
    for i, spec in enumerate(s.model.specs):
        print(f"[smoke] kernel tt_contract layer {i} {spec.out_modes}x"
              f"{spec.in_modes} ranks {spec.ranks}: {ops.tt_impl(spec)} "
              f"({ops.tt_path(spec)} body)")
    meshes = {(lay.ports, lay.levels): (ops.mesh_impl(lay),
                                        ops.mesh_path(lay))
              for pms in s.model.photonic_cores for pm in pms
              for lay in (pm.layout_u, pm.layout_v)}
    for (ports, levels), (impl, path) in sorted(meshes.items()):
        print(f"[smoke] kernel mesh_apply_stacked ports {ports} levels "
              f"{levels}: {impl} ({path} body)")
    t0 = time.perf_counter()
    compiled = jax.jit(s.batched_loss_fn()).lower(stack, s.xt).compile()
    found = compiled_kernels(compiled.as_text())
    print(f"[smoke] compiled stacked loss (P={N_SAMPLES + 1}) in "
          f"{time.perf_counter() - t0:.3f} s; Pallas kernels: {dict(found)}")
    for name in ("tt_contract", "mesh_apply_stacked"):
        if not found[name]:
            raise SystemExit(f"chip_smoke: the stacked tonn loss holds no "
                             f"{name} Pallas kernel")
    return compiled


def loss_phase(s: Setup, stack: dict, compiled) -> None:
    """Step-0 losses through the kernels vs the plain jnp path."""
    fused = np.asarray(compiled(stack, s.xt))
    with kernel_mode("ref"), jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(s.batched_loss_fn())(stack, s.xt))
    rel = np.abs(fused - ref) / np.abs(ref)
    print(f"[smoke] step-0 loss: pallas {fused[0]:.7e} vs jnp reference "
          f"{ref[0]:.7e} (rel {rel[0]:.3e})")
    print(f"[smoke] all {len(fused)} perturbed losses: max rel diff "
          f"{rel.max():.3e} (FD floor rtol {FD_LOSS_RTOL:g})")
    if not np.all(np.isfinite(fused)):
        raise SystemExit("chip_smoke: non-finite loss on the kernel path")
    np.testing.assert_allclose(fused, ref, rtol=FD_LOSS_RTOL)
    # perturbation sharding evaluates slices of the stack (DESIGN.md
    # §Distributed): an entry's loss must not depend on the stack size
    from repro.parallel import zo_shard
    per = zo_shard.pert_shard_size(N_SAMPLES + 1, 4)
    part = np.asarray(jax.jit(s.batched_loss_fn())(
        jax.tree.map(lambda a: a[:per], stack), s.xt))
    print(f"[smoke] first {per} losses as their own stack vs in the "
          f"{len(fused)}-stack: max rel diff "
          f"{np.max(np.abs(part - fused[:per]) / np.abs(fused[:per])):.3e}, "
          f"bit-identical {int((part == fused[:per]).sum())}/{per}")
    np.testing.assert_allclose(part, fused[:per], rtol=1e-6)


def serve_phase(ckpt: Path, sizes: tuple = REQUEST_SIZES) -> None:
    from repro.serving import PdeServingEngine, PointRequest, SolverRegistry
    reg = SolverRegistry()
    solver = reg.load_checkpoint(PDE, ckpt)
    engine = PdeServingEngine(reg, slots=SLOTS, slot_points=SLOT_POINTS)
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    warm_compiles = engine.stats["compiles"]
    pts = [np.asarray(solver.problem.sample_collocation(
        jax.random.PRNGKey(1000 + i), n), np.float32)
        for i, n in enumerate(sizes)]
    t0 = time.perf_counter()
    reqs = [engine.submit(PointRequest(PDE, p)) for p in pts]
    engine.run()
    # a repeat of a served request: answered by the stencil cache
    reqs.append(engine.submit(PointRequest(PDE, pts[2])))
    serve_s = time.perf_counter() - t0
    stats = engine.serving_stats()
    lat_ms = np.asarray([r.latency_s for r in reqs]) * 1e3
    print(f"[smoke] serve: warm-up {warm_s:.3f} s ({warm_compiles} "
          f"program(s)); {len(reqs)} requests / "
          f"{sum(len(r.points) for r in reqs)} points in {serve_s:.3f} s; "
          f"latency p50 {np.percentile(lat_ms, 50):.3f} ms "
          f"p99 {np.percentile(lat_ms, 99):.3f} ms")
    print("[smoke] serving counters: " + json.dumps(
        {k: stats[k] for k in ("compiles", "steps", "program_runs",
                               "points_served", "points_padded",
                               "cache_hits", "cache_misses")}))
    if not all(r.done for r in reqs):
        raise SystemExit("chip_smoke: a request was left unserved")
    recompiles = stats["compiles"] - warm_compiles
    print(f"[smoke] recompiles after warm-up: {recompiles}")
    if recompiles:
        raise SystemExit("chip_smoke: the engine recompiled after warm-up")
    direct = np.asarray(jax.jit(
        lambda x: solver.model.u(solver.params, x, solver.noise))(
            jnp.asarray(np.concatenate(pts))))
    served = np.concatenate([r.out for r in reqs[:len(pts)]])
    diff = np.abs(served - direct)
    print(f"[smoke] served vs direct TensorPinn.u: max |diff| "
          f"{diff.max():.3e}, bit-identical {int((diff == 0).sum())}/"
          f"{diff.size}; repeat request equal: "
          f"{np.array_equal(reqs[-1].out, reqs[2].out)}")
    np.testing.assert_allclose(served, direct, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(reqs[-1].out, reqs[2].out)


def four_chip_phase(out: Path, hidden: int | None, steps: int) -> None:
    from repro.core import zoo
    from repro.parallel import zo_shard
    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke --four-chips: {len(jax.devices())} "
                         "device(s), need 4")
    train_phase(out, hidden, steps,
                extra=("--shard", "perturbation", "--mesh", "4x1"))
    s = paper_setup(hidden)
    scfg = zoo.SPSAConfig(num_samples=N_SAMPLES, mu=MU)
    mesh = zo_shard.make_zo_mesh("4x1", "perturbation")
    blf = s.batched_loss_fn()
    grad4 = zo_shard.make_distributed_spsa_gradient(mesh, blf, scfg, s.mask)
    t0 = time.perf_counter()
    g4, base4 = jax.block_until_ready(grad4(s.params, s.key, s.xt))
    t4 = time.perf_counter() - t0
    grad1 = jax.jit(lambda p: zoo.spsa_gradient(
        s.loss_fn(), p, s.key, scfg,
        batched_loss_fn=lambda sp: blf(sp, s.xt), trainable_mask=s.mask))
    t0 = time.perf_counter()
    g1, base1 = jax.block_until_ready(grad1(s.params))
    t1 = time.perf_counter() - t0
    leaves4, leaves1 = jax.tree.leaves(g4), jax.tree.leaves(g1)
    scale = max(float(jnp.max(jnp.abs(leaf))) for leaf in leaves1)
    worst = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(leaves4, leaves1))
    devices = {d for leaf in leaves4 for d in leaf.sharding.device_set}
    traffic = zo_shard.measure_collective_bytes(grad4, s.params, s.key, s.xt)
    bound = zo_shard.wire_bound_bytes(N_SAMPLES, 4)
    print(f"[smoke] 4-chip gradient spans {len(devices)} devices; "
          f"collectives {[(op, shape) for op, shape, _ in traffic['ops']]} "
          f"= {traffic['bytes']} B/step (bound {bound} B)")
    print(f"[smoke] 4-chip vs 1-chip fused gradient: max |diff| {worst:.3e} "
          f"(scale {scale:.3e}, rel {worst / scale:.3e}); base loss "
          f"{float(base4):.7e} vs {float(base1):.7e}; first-call wall "
          f"{t4:.3f} s vs {t1:.3f} s (compile included)")
    if len(devices) != 4:
        raise SystemExit("chip_smoke: the distributed gradient does not "
                         "span 4 devices")
    if not any(op == "all-reduce" for op, _, _ in traffic["ops"]):
        raise SystemExit("chip_smoke: no psum of the loss vector in the "
                         "compiled distributed gradient")
    if traffic["bytes"] > bound:
        raise SystemExit("chip_smoke: the distributed gradient moves more "
                         "than the O(N)-scalar bound")
    # identity contract (DESIGN.md §Distributed): the same ξ and the same
    # per-perturbation losses, so the gradients agree to loss-level f32
    # reassociation carried linearly through the SPSA reconstruction
    for a, b in zip(leaves4, leaves1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, rtol=1e-3)
    np.testing.assert_allclose(float(base4), float(base1), rtol=1e-4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the perturbation-sharded ZO phase on "
                         "four chips")
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for the checkpoint")
    args = ap.parse_args(argv)

    enable_compile_cache()
    from repro.kernels import ops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{dev.platform!r}); nothing is run off the chip")
    if ops.kernel_mode() != "pallas":
        raise SystemExit(f"chip_smoke: kernel mode {ops.kernel_mode()!r}, "
                         "not 'pallas'; unset REPRO_KERNEL_MODE")
    count = len(jax.devices())
    print(f"[smoke] device: platform {dev.platform}, kind {dev.device_kind},"
          f" count {count}; jax {jax.__version__}")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.four_chips:
        four_chip_phase(out, None, TRAIN_STEPS)
    else:
        ckpt = train_phase(out, None, TRAIN_STEPS)
        s = paper_setup()
        stack = perturbed_stack(s)
        loss_phase(s, stack, kernel_phase(s, stack))
        serve_phase(ckpt)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
