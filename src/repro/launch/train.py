"""End-to-end training launcher.

Runs any assigned architecture (``--arch``, optionally ``--reduced``) or the
paper's BP-free tensor PINN (``--arch hjb-pinn`` / ``tensor-pinn``) on any
registered PDE workload (``--pde``, see ``repro.pde``) with:

  * pjit/GSPMD sharding over an explicit mesh (``--mesh dxm``, default =
    all local devices on the data axis),
  * distributed BP-free ZO for the PINN archs (``--shard {perturbation,
    batch,both}`` + ``--mesh PxB``): the SPSA sweep sharded over a
    ('pert','batch') mesh with O(N)-scalar per-step traffic
    (``repro.parallel.zo_shard``, DESIGN.md §Distributed),
  * AdamW / Adafactor / BP-free ZO-signSGD (``--optimizer``),
  * deterministic restart-safe data pipeline,
  * fault-tolerant checkpointing (atomic, keep-k, optional async) + resume,
  * straggler watchdog,
  * optional sign-compressed gradient all-reduce across the ``pod`` axis.

Examples (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2.5-3b --reduced --steps 20 --batch 8 --seq 64
    PYTHONPATH=src python -m repro.launch.train \
        --arch hjb-pinn --pde heat-20d --reduced --steps 200 --batch 100
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, synthetic_lm_batch
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.optim import get_optimizer, sign_compress_grads
from repro.optim.optimizers import default_optimizer_for
from repro.optim.zo import zo_signsgd_trainer_step
from repro.parallel import sharding as shd
from repro.parallel.act import activation_sharding
from repro.runtime import StragglerWatchdog, enable_compile_cache


def build_train_step(cfg, optimizer, compress_pod_grads: bool = False):
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, cfg, batch))(params)
        if compress_pod_grads:
            grads = sign_compress_grads(grads)
        new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, loss
    return step


PINN_ARCHS = ("hjb-pinn", "tensor-pinn")


def _parse_coeff_ranges(text: str) -> dict:
    """``name=lo:hi[,name=lo:hi]`` → {name: (lo, hi)} for --coeff-range."""
    out = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            name, rng = part.split("=")
            lo, hi = (float(v) for v in rng.split(":"))
        except ValueError:
            raise SystemExit(
                f"--coeff-range: malformed entry {part!r} "
                "(expected name=lo:hi[,name=lo:hi])")
        out[name.strip()] = (lo, hi)
    if not out:
        raise SystemExit("--coeff-range: no ranges given")
    return out


def _parse_term_weights(entries) -> dict:
    """Repeated ``--term-weight NAME=W[,NAME=W]`` → {name: float}."""
    out = {}
    for text in entries:
        for part in text.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                name, w = part.split("=")
                out[name.strip()] = float(w)
            except ValueError:
                raise SystemExit(
                    f"--term-weight: malformed entry {part!r} "
                    "(expected NAME=W[,NAME=W])")
    if not out:
        raise SystemExit("--term-weight: no weights given")
    return out


def _apply_term_weights(args, problem) -> dict:
    """Resolve --term-weight/--bc-weight into ``set_term_weights``
    overrides on ``problem`` (the loss-term engine, DESIGN.md
    §Loss-terms).  --bc-weight is sugar for the problem's boundary-kind
    term(s) — helmholtz-2d's λ, ns-2d's "ic" — an explicit --term-weight
    for the same name wins.  Returns the applied overrides."""
    tw = _parse_term_weights(args.term_weight) if args.term_weight else {}
    if args.bc_weight is not None:
        b_names = [t.name for t in problem.loss_terms()
                   if t.kind == "boundary"]
        if not b_names:
            raise SystemExit(f"--bc-weight: PDE {problem.name!r} has no "
                             "boundary-kind loss term")
        for name in b_names:
            tw.setdefault(name, args.bc_weight)
    if tw:
        try:
            problem.set_term_weights(tw)
        except ValueError as e:
            raise SystemExit(f"--term-weight: {e}")
    return tw


def _conditioned_problem(args):
    """Resolve --pde plus any --coeff-range/--coeff-dist overrides into a
    problem instance (None → let the config/model resolve the name as
    before).  Overrides rebind ``coeff_spec`` on a fresh registry instance:
    ranges only drive sampling/normalization/validation, never the residual
    (which reads raw coefficient values off the input slots)."""
    if not (args.coeff_range or args.coeff_dist):
        return None
    from repro import pde as pde_lib
    problem = pde_lib.get_problem(args.pde)
    if problem.coeff_spec is None:
        raise SystemExit(
            f"--coeff-range/--coeff-dist need a coefficient-conditioned "
            f"PDE; {args.pde!r} is not (try one of "
            f"{[n for n in pde_lib.available() if pde_lib.get_problem(n).coeff_spec]})")
    ranges = _parse_coeff_ranges(args.coeff_range) if args.coeff_range else {}
    problem.coeff_spec = problem.coeff_spec.with_ranges(
        ranges, dist=args.coeff_dist)
    return problem


def train_pinn(args):
    """BP-free PINN training on a registered PDE workload (paper §3–§4).

    ZO-signSGD by default — the paper's on-chip, forward-only algorithm —
    through the fused multi-perturbation hot path (DESIGN.md §Perf) unless
    ``--sequential`` requests the photonic-realism one-mesh-at-a-time order.
    ``--optimizer adamw|sgd`` selects the off-chip BP baseline instead.
    """
    from repro.configs.hjb_pinn import pinn_config, pinn_reduced
    from repro.core import pinn, zoo
    from repro.data import pde_collocation_iterator, pde_term_batch_iterator

    build = pinn_reduced if args.reduced else pinn_config
    overrides = {"hidden": args.hidden} if args.hidden else {}
    if args.estimator:
        # estimator choice travels in the config, so config_to_meta below
        # writes it into the checkpoint meta for serving/resume
        overrides["deriv"] = args.estimator
    if args.spectral_points:
        overrides["spectral_points"] = args.spectral_points
    if args.quant or args.phase_bits:
        # quantization-aware ZO training: fake-quant inside the loss —
        # zoo/zo_shard and the wire protocol are untouched (DESIGN.md
        # §Quantization)
        from repro.kernels import quant as quant_lib
        overrides["quant"] = quant_lib.QuantConfig(
            enabled=True, dtype=args.quant, block=args.quant_block,
            phase_bits=args.phase_bits)
    cfg = build(pde=args.pde, mode=args.pinn_mode, fused=not args.sequential,
                noise=args.pinn_noise, **overrides)
    problem_override = _conditioned_problem(args)
    model = pinn.TensorPinn(cfg, problem=problem_override)
    problem = model.problem
    weight_overrides = _apply_term_weights(args, problem)
    if weight_overrides:
        print("[pinn] term weights: "
              + " ".join(f"{k}={v:g}"
                         for k, v in problem.term_weights().items()))
    print(f"[pinn] pde={problem.name} in_dim={problem.in_dim} "
          f"mode={cfg.mode} hidden={cfg.hidden} deriv={cfg.deriv} "
          f"fused={cfg.use_fused_kernel}"
          + (f" quant={cfg.quant.tag()}" if cfg.quant.enabled else ""))
    if problem.coeff_spec is not None:
        spec = problem.coeff_spec
        print("[pinn] conditioned on "
              + ", ".join(f"{n}∈[{lo:g}, {hi:g}]" for n, lo, hi
                          in zip(spec.names, spec.lo, spec.hi))
              + f" ({spec.dist}); net_in={problem.net_dim}")

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    hw_noise = model.sample_noise(jax.random.fold_in(key, 99))
    # partition trainable phases/weights from fixed buffers (photonic ±1
    # diags): ZO must neither perturb nor sign-update the buffers
    mask = model.trainable_mask(params)
    n_train = sum(int(np.prod(x.shape)) for x, t
                  in zip(jax.tree.leaves(params), jax.tree.leaves(mask)) if t)
    n_buf = sum(int(np.prod(x.shape)) for x, t
                in zip(jax.tree.leaves(params), jax.tree.leaves(mask)) if not t)
    print(f"[pinn] trainable params: {n_train} (+ {n_buf} fixed buffers)")
    val = problem.sample_collocation(jax.random.fold_in(key, 1234), 1000) \
        if problem.has_exact_solution else None

    mgr = None
    # self-describing checkpoints: the serving registry loads a trained
    # solver by name from this alone (arch + problem + the noise seed that
    # regenerates the fixed per-chip fabrication noise) — no config
    # side-channel (DESIGN.md §Serving)
    ckpt_meta = {"pinn": pinn.config_to_meta(cfg), "pde": problem.name,
                 "seed": args.seed}
    if problem.coeff_spec is not None:
        # the trained coefficient ranges travel with the checkpoint: serving
        # restores them to normalize inputs identically and to reject
        # queries outside the trained family (DESIGN.md §Parameterized)
        ckpt_meta["coeff_spec"] = problem.coeff_spec.to_meta()
    # the trained loss composition travels too: serving/validation rebuild
    # the SAME weighted loss from the checkpoint alone (DESIGN.md
    # §Loss-terms) — overrides applied, defaults recorded explicitly
    ckpt_meta["term_weights"] = problem.term_weights()
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3,
                                save_every=args.ckpt_every,
                                async_save=args.async_ckpt)
    watchdog = StragglerWatchdog(
        on_straggle=lambda s: print(f"[watchdog] straggler at step {s.step}: "
                                    f"{s.duration_s:.3f}s vs median "
                                    f"{s.median_s:.3f}s"))

    opt_name = args.optimizer or "zo-signsgd"
    lr0 = args.lr or 2e-3
    half_life = max(args.steps // 3, 1)

    if args.shard and opt_name != "zo-signsgd":
        raise SystemExit(f"--shard is distributed ZO only "
                         f"(got --optimizer {opt_name}); the BP baselines "
                         "use the GSPMD mesh path of the LM archs instead")

    one_replica = lambda tree: tree     # params as the forward below sees them
    # both branches share the step signature (params, aux, xt, tb, lr_t) →
    # (params, aux, loss) so one loop below owns watchdog/logging/checkpoints
    # (tb = the per-step term-batch dict from the composite-loss engine)
    if opt_name == "zo-signsgd" and args.shard:
        # distributed ZO: shard the SPSA sweep over an explicit mesh —
        # per-step traffic is O(N) scalars, params never move (DESIGN.md
        # §Distributed).  Requires the fused stacked evaluator.
        from repro.parallel import zo_shard
        if args.sequential:
            raise SystemExit("--shard needs the stacked evaluator; "
                             "drop --sequential")
        mesh = zo_shard.make_zo_mesh(args.mesh, args.shard)
        npert, nbatch = mesh.shape["pert"], mesh.shape["batch"]
        if args.batch % nbatch:
            raise SystemExit(f"--batch {args.batch} not divisible by the "
                             f"{nbatch}-way batch axis")
        print(f"[pinn] distributed ZO mesh pert={npert} batch={nbatch} "
              f"(shard={args.shard})")
        scfg = zoo.SPSAConfig(num_samples=args.zo_samples, mu=0.01)
        aux = zoo.ZOState.create(args.seed + 1)
        aux_name = "zo"
        step_fn = zo_shard.make_distributed_zo_step(
            mesh,
            # the replicated bc slot carries the term-batch dict pytree:
            # boundary/data rows are tiny and evaluated on every shard
            lambda sp, xt, tb: pinn.residual_losses_stacked(
                model, sp, xt, hw_noise, term_batches=tb),
            scfg, trainable_mask=mask)
        one_replica = zo_shard.local_replica
    elif opt_name == "zo-signsgd":
        scfg = zoo.SPSAConfig(num_samples=args.zo_samples, mu=0.01)
        aux = zoo.ZOState.create(args.seed + 1)
        aux_name = "zo"

        @partial(jax.jit, donate_argnums=(0, 1))
        def step_fn(params, aux, xt, tb, lr_t):
            lf = lambda p: pinn.residual_loss(model, p, xt, hw_noise,
                                              term_batches=tb)
            blf = (None if args.sequential else
                   lambda sp: pinn.residual_losses_stacked(
                       model, sp, xt, hw_noise, term_batches=tb))
            return zoo.zo_signsgd_step(lf, params, aux, lr=lr_t, cfg=scfg,
                                       batched_loss_fn=blf,
                                       trainable_mask=mask)
    else:
        # off-chip BP baseline on the ideal (or noisy) model
        opt = get_optimizer(opt_name, lr=args.lr)
        aux = opt.init(params)
        aux_name = "opt"

        @partial(jax.jit, donate_argnums=(0, 1))
        def step_fn(params, aux, xt, tb, lr_t):
            # lr_t unused: the BP optimizers carry their own schedule
            lf = lambda p: pinn.residual_loss(model, p, xt, hw_noise,
                                              term_batches=tb)
            loss, grads = jax.value_and_grad(lf)(params)
            # the fixed buffers get nonzero BP gradients (they scale wires
            # elementwise) — zero them so the baseline can't walk the ±1
            # diags off the orthogonal decomposition either
            grads = jax.tree.map(
                lambda g, t: g if t else jnp.zeros_like(g), grads, mask)
            new_params, new_aux = opt.update(grads, aux, params)
            return new_params, new_aux, loss

    start_step = 0
    if mgr and args.resume:
        try:
            restored, meta = mgr.restore_latest(
                {"params": params, aux_name: aux})
            params, aux = restored["params"], restored[aux_name]
            start_step = meta["step"]
            print(f"[resume] step {start_step}")
        except FileNotFoundError:
            pass
    if args.shard:
        # start replicated on the mesh, where the step leaves them, so the
        # step compiles once
        params, aux = jax.device_put((params, aux), NamedSharding(mesh, P()))

    # restart-safe counter-based streams (shared data pipeline): the
    # collocation batch on shard 0, the boundary/data term batches on
    # shard 1 of the same (seed, step) key space
    colloc = pde_collocation_iterator(args.batch, seed=args.seed,
                                      start_step=start_step, pde=args.pde,
                                      problem=problem_override,
                                      coeffs_per_step=args.coeffs_per_step)
    terms = pde_term_batch_iterator(max(args.batch // 4, 8), seed=args.seed,
                                    start_step=start_step, problem=problem)
    multi_term = len(problem.loss_terms()) > 1
    # the watchdog sees the interval between consecutive completed steps:
    # two steps stay in flight, and the loss of the step two behind is
    # waited for, so on an accelerator a step's duration is the device's
    # time per step (or the host's, where the host is the slower), never
    # the enqueue time of an asynchronous dispatch
    inflight = collections.deque()      # (step, loss) dispatched, not waited
    last_done = time.monotonic()

    def complete(done_step, done_loss):
        nonlocal last_done
        jax.block_until_ready(done_loss)
        now = time.monotonic()
        st = watchdog.end_step(done_step, duration_s=now - last_done)
        last_done = now
        return st

    for step in range(start_step, args.steps):
        xt = next(colloc)
        tb = next(terms)
        params, aux, loss = step_fn(params, aux, xt, tb,
                                    lr0 * 0.5 ** (step / half_life))
        inflight.append((step, loss))
        if len(inflight) > 2:
            complete(*inflight.popleft())
        log_now = step % args.log_every == 0
        save_now = mgr and mgr.should_save(step)
        if log_now or save_now:
            while inflight:             # the log line reads this step's loss
                st = complete(*inflight.popleft())
        if log_now:
            msg = f"step {step} loss {float(loss):.4e} ({st.duration_s:.2f}s)"
            if multi_term:
                pt = pinn.per_term_losses(model, one_replica(params), xt,
                                          hw_noise, term_batches=tb)
                msg += " [" + " ".join(f"{k}={float(v):.3e}"
                                       for k, v in pt.items()) + "]"
            if val is not None:
                mse = pinn.validation_mse(model, one_replica(params), val,
                                          hw_noise)
                msg += f" val MSE {float(mse):.4e}"
            print(msg)
        if save_now:
            mgr.save(step, {"params": params, aux_name: aux},
                     {"step": step, **ckpt_meta})
        if log_now or save_now:
            # nothing is in flight: the log line and the checkpoint are
            # host work of no step's, so the next step's interval starts here
            last_done = time.monotonic()
    while inflight:
        complete(*inflight.popleft())

    if mgr:
        mgr.save(args.steps, {"params": params, aux_name: aux},
                 {"step": args.steps, **ckpt_meta})
        mgr.wait()
    if val is not None:
        mse = pinn.validation_mse(model, one_replica(params), val, hw_noise)
        print(f"[pinn] final val MSE {float(mse):.4e}")
    print("[train] done")
    return params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default=None,
                    choices=[None, "adamw", "adafactor", "sgd", "zo-signsgd"])
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--mesh", default=None,
                    help="LM archs: DATAxMODEL (e.g. 4x2). PINN archs with "
                         "--shard: PERTxBATCH for the distributed ZO mesh")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--zo-vectorized", action="store_true",
                    help="batch the N SPSA loss evals in one program "
                         "(TPU/CPU fast path; a photonic chip is serial)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    # PINN-only flags (--arch hjb-pinn / tensor-pinn)
    ap.add_argument("--pde", default="hjb-20d",
                    help="registered PDE workload (repro.pde.available())")
    ap.add_argument("--pinn-mode", default="tonn",
                    choices=["dense", "onn", "tt", "tonn"])
    ap.add_argument("--hidden", type=int, default=None,
                    help="override the PINN hidden width")
    ap.add_argument("--zo-samples", type=int, default=10,
                    help="N SPSA perturbations per ZO step (paper: 10)")
    ap.add_argument("--estimator", default=None,
                    choices=[None, "fd", "fd_fast", "stein", "spectral",
                             "auto"],
                    help="derivative estimator override: central FD "
                         "(stacked / incremental-stencil), Gaussian Stein, "
                         "FFT-exact spectral line grids, or 'auto' = the "
                         "problem's own choice; default keeps the fused-"
                         "path fd_fast/fd selection")
    ap.add_argument("--spectral-points", type=int, default=None,
                    help="line-grid size M per active axis for "
                         "--estimator spectral (default: the problem's "
                         "spectral_points)")
    ap.add_argument("--sequential", action="store_true",
                    help="photonic-realism order: one perturbed mesh at a "
                         "time instead of the fused stacked program")
    ap.add_argument("--shard", default=None,
                    choices=["perturbation", "batch", "both"],
                    help="distributed ZO over a ('pert','batch') device "
                         "mesh: shard the SPSA sweep, the collocation "
                         "batch, or both (repro.parallel.zo_shard; O(N)-"
                         "scalar traffic per step)")
    ap.add_argument("--pinn-noise", action="store_true",
                    help="enable the fabrication-noise model (on-chip rows)")
    ap.add_argument("--quant", default=None,
                    choices=[None, "int8", "fp8_e4m3"],
                    help="quantization-aware training: block-scaled TT-core/"
                         "weight quantization (DESIGN.md §Quantization)")
    ap.add_argument("--quant-block", type=int, default=32,
                    help="absmax-scaling block size for --quant")
    ap.add_argument("--phase-bits", type=int, default=None,
                    help="DAC resolution: snap trainable MZI phases to the "
                         "uniform 2π/2^bits grid (hardware-faithful knob)")
    ap.add_argument("--coeff-range", default=None,
                    help="override the trained coefficient ranges of a "
                         "conditioned PDE: name=lo:hi[,name=lo:hi] "
                         "(e.g. kappa=0.5:2.0)")
    ap.add_argument("--coeff-dist", default=None,
                    choices=[None, "uniform", "loguniform"],
                    help="coefficient sampling distribution override")
    ap.add_argument("--coeffs-per-step", type=int, default=None,
                    help="grouped scenario sampling: C coefficient draws "
                         "per step tiled over the batch instead of "
                         "per-point iid")
    ap.add_argument("--term-weight", action="append", default=None,
                    metavar="NAME=W",
                    help="override a loss term's scale weight by name "
                         "(repeatable / comma-separated; names from the "
                         "problem's loss_terms(), e.g. ic=10 data=0.5); "
                         "recorded in checkpoint meta so serving rebuilds "
                         "the trained loss")
    ap.add_argument("--bc-weight", type=float, default=None,
                    help="sugar for the boundary-kind term's weight "
                         "(paper Eq. 4's λ — helmholtz-2d's boundary, "
                         "ns-2d's ic); an explicit --term-weight wins")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.arch in PINN_ARCHS:
        return train_pinn(args)
    if args.shard:
        raise SystemExit("--shard (distributed ZO mesh) is PINN-only; "
                         "LM archs shard via --mesh DATAxMODEL")

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))

    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))

    opt_name = args.optimizer or default_optimizer_for(args.arch)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed)

    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    report = shd.ShardingReport(fallbacks=[])
    pshard = shd.param_shardings(
        mesh, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           params), report)
    params = jax.tree.map(jax.device_put, params, pshard)

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3,
                                save_every=args.ckpt_every,
                                async_save=args.async_ckpt)

    watchdog = StragglerWatchdog(
        on_straggle=lambda s: print(f"[watchdog] straggler at step {s.step}: "
                                    f"{s.duration_s:.3f}s vs median "
                                    f"{s.median_s:.3f}s — early checkpoint"))

    with mesh, activation_sharding(mesh):
        if opt_name == "zo-signsgd":
            state = {"key": jax.random.PRNGKey(args.seed + 1)}
            if mgr and args.resume:
                try:
                    restored, meta = mgr.restore_latest(
                        {"params": params, "key": state["key"]})
                    params, state["key"] = restored["params"], restored["key"]
                    start_step = meta["step"]
                    print(f"[resume] step {start_step}")
                except FileNotFoundError:
                    pass

            # fully jitted step with donated params+key: the update buffers
            # are reused in place instead of a fresh N×param allocation/step
            @partial(jax.jit, donate_argnums=(0, 1))
            def zo_step(params, key, batch):
                lf = lambda p: api.loss_fn(p, cfg, batch)
                key, sub = jax.random.split(key)
                new_params, loss = zo_signsgd_trainer_step(
                    lf, params, sub, lr=args.lr or 1e-3,
                    vectorized=args.zo_vectorized)
                return new_params, key, loss

            for step in range(start_step, args.steps):
                batch = synthetic_lm_batch(data_cfg, step)
                watchdog.start_step()
                params, state["key"], loss = zo_step(params, state["key"], batch)
                st = watchdog.end_step(step)
                if step % args.log_every == 0:
                    print(f"step {step} loss {float(loss):.4f} "
                          f"({st.duration_s:.2f}s)")
                if mgr and mgr.should_save(step):
                    mgr.save(step, {"params": params, "key": state["key"]},
                             {"step": step})
        else:
            opt = get_optimizer(opt_name, lr=args.lr)
            opt_state = opt.init(params)
            if mgr and args.resume:
                try:
                    restored, meta = mgr.restore_latest(
                        {"params": params, "opt": opt_state})
                    params, opt_state = restored["params"], restored["opt"]
                    start_step = meta["step"]
                    print(f"[resume] step {start_step}")
                except FileNotFoundError:
                    pass
            step_fn = jax.jit(build_train_step(cfg, opt, args.compress_grads),
                              donate_argnums=(0, 1))
            for step in range(start_step, args.steps):
                batch = synthetic_lm_batch(data_cfg, step)
                watchdog.start_step()
                params, opt_state, loss = step_fn(params, opt_state, batch)
                st = watchdog.end_step(step)
                if step % args.log_every == 0:
                    print(f"step {step} loss {float(loss):.4f} "
                          f"({st.duration_s:.2f}s)")
                if mgr and mgr.should_save(step):
                    mgr.save(step, {"params": params, "opt": opt_state},
                             {"step": step})
        if mgr:
            mgr.save(args.steps, {"params": params} if opt_name == "zo-signsgd"
                     else {"params": params, "opt": opt_state},
                     {"step": args.steps})
            mgr.wait()
    print("[train] done")
    return params


if __name__ == "__main__":
    main()
