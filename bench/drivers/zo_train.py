"""Training cells: the paper's ZO-signSGD on the stacked BP-free PINN loss.

Set-up builds the compiled step with its state from the program's public
pieces, as ``repro.launch.train.train_pinn`` builds them: weights and
fabrication noise from ``--seed`` (one jitted call each),
``pde_collocation_iterator`` and ``pde_term_batch_iterator`` for the data,
``zoo.zo_signsgd_step`` over ``pinn.residual_losses_stacked`` with the
trainable mask, jitted with params and state donated.  The fabrication
noise enters the step as an argument, not as a constant baked into the
program, so one compiled step serves every seed from the persistent
cache.  It drives that step through the first ``check_steps`` steps (the first one
compiles) and hands the same step, state and streams to the window.

The window runs steps until ``--seconds`` have passed, at most
``max_inflight`` steps ahead of the device, then waits for the last one:
``zo_steps_per_s`` is the steps over all of that time.  With ``--trace 1``
the window is profiled instead, for ``trace_seconds``.

``correct`` compares the first steps with the plain reference of the
configuration, run once the window has closed.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial

import numpy as np

from harness import Spans, check, leaf_norm_gaps, log, make_model, \
    memory_peak_bytes, profiled, reference_module

# leaves whose reference gradient is below this share of the median leaf's
# move by round-off alone and are left out of the norm comparisons
GRAD_FLOOR = 1e-3


@dataclasses.dataclass
class Hooks:
    """Seams for the benchmark's own tests: the stacked loss the step is
    built over, and a wrapper around the built step."""

    loss_fn: object = None
    wrap_step: object = None


def build(cfg: dict, job: dict, seed: int, hooks: Hooks):
    import jax
    from repro.core import pinn, zoo
    from repro.data import pde_collocation_iterator, pde_term_batch_iterator

    model = make_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(key)
    noise = jax.jit(model.sample_noise)(jax.random.fold_in(key, 99))
    mask = model.trainable_mask(params)
    scfg = zoo.SPSAConfig(num_samples=job["zo_samples"], mu=job["mu"])
    aux = zoo.ZOState.create(seed + 1)
    loss_fn = hooks.loss_fn or pinn.residual_losses_stacked

    @partial(jax.jit, donate_argnums=(0, 1))
    def one_chip(params, aux, xt, tb, lr, noise):
        lf = lambda p: pinn.residual_loss(model, p, xt, noise,
                                          term_batches=tb)
        blf = lambda sp: loss_fn(model, sp, xt, noise, term_batches=tb)
        return zoo.zo_signsgd_step(lf, params, aux, lr=lr, cfg=scfg,
                                   batched_loss_fn=blf, trainable_mask=mask)

    step = lambda p, a, xt, tb, lr: one_chip(p, a, xt, tb, lr, noise)
    if hooks.wrap_step is not None:
        step = hooks.wrap_step(step)
    colloc = pde_collocation_iterator(job["batch"], seed=seed,
                                      pde=cfg["pde"])
    terms = pde_term_batch_iterator(max(job["batch"] // 4, 8), seed=seed,
                                    problem=model.problem)
    return step, params, aux, colloc, terms


def first_steps(step, params, aux, colloc, terms, lr: float, n: int):
    """The first ``n`` steps, through the window's own call and feed:
    (params, aux, losses, [params before, after step 1, after step n])."""
    import jax
    snaps = [jax.device_get(params)]
    losses = []
    for s in range(n):
        params, aux, loss = step(params, aux, next(colloc), next(terms), lr)
        losses.append(loss)
        if s == 0:
            snaps.append(jax.device_get(params))
    snaps.append(jax.device_get(params))
    return params, aux, [float(x) for x in losses], snaps


def run(cfg: dict, job: dict, limits: dict, seed: int, seconds: float,
        traced: bool, devices, t_start: float, hooks: Hooks | None = None
        ) -> dict:
    import jax
    hooks = hooks or Hooks()
    lr = job["lr"]
    step, params, aux, colloc, terms = build(cfg, job, seed, hooks)

    params, aux, losses, snaps = first_steps(step, params, aux, colloc,
                                             terms, lr, job["check_steps"])
    log(f"first {len(losses)} losses {losses}")

    spans = Spans(traced)
    inflight = collections.deque()
    state = {"params": params, "aux": aux, "loss": None}

    def one_step():
        with spans("data"):
            xt, tb = next(colloc), next(terms)
        with spans("dispatch"):
            state["params"], state["aux"], state["loss"] = step(
                state["params"], state["aux"], xt, tb, lr)
        inflight.append(state["loss"])
        if len(inflight) > job["max_inflight"]:
            with spans("sync"):
                inflight.popleft().block_until_ready()

    def loop(duration: float) -> tuple:
        n, t0 = 0, time.perf_counter()
        while True:
            one_step()
            n += 1
            if time.perf_counter() - t0 >= duration:
                break
        with spans("sync"):
            jax.block_until_ready(state["loss"])
        inflight.clear()
        return n, time.perf_counter() - t0

    setup_s = time.perf_counter() - t_start
    out = {"setup_s": setup_s,
           "layer_ctx": {"sets_per_device": job["zo_samples"] + 1,
                         "chips": len(devices)}}
    with spans.collections():
        if traced:
            loop(job["trace_warm_seconds"])
            with profiled() as prof:
                n, dt = loop(job["trace_seconds"])
            out["trace"] = prof["trace"]
            out["layer_ctx"]["steps_traced"] = n
        else:
            n, dt = loop(seconds)
            out["e2e"] = {"zo_steps_per_s": n / dt}
    log(f"window: {n} steps in {dt:.6f} s; {spans.gc_summary()}")
    last = float(state["loss"])
    out["attempted"], out["failed"] = n, (0 if np.isfinite(last) else n)
    out["memory_peak_bytes"] = memory_peak_bytes(devices)
    del state, params, aux, inflight
    out["checks"] = compare(cfg, job, limits, seed, losses, snaps)
    return out


def compare(cfg: dict, job: dict, limits: dict, seed: int, losses: list,
            snaps: list) -> dict:
    """The first steps against the plain reference, by the numbers that
    ``limits`` names: the first step's loss, and the first update (the
    sign-SGD optimizer's view of the first gradient) by the worst leaf's
    norm and by the share of its entries that move the other way.  The
    later steps drift apart by rounding (PERF.md §6) and are read only by
    ``bench/calibrate.py``."""
    ref = reference_module(cfg)
    t = time.perf_counter()
    r = ref.zo_signsgd_steps(cfg, job, seed, len(losses))
    log(f"reference: {len(losses)} steps in {time.perf_counter() - t:.3f} s;"
        f" losses {r['loss']}")
    return compare_readings(losses, snaps, r, limits)


def readings(losses: list, snaps: list, r: dict) -> dict:
    """Gaps between the program's first steps and the reference's r:
    the loss of each step; the first update, by gaps of leaf norms (worst
    and median counted leaf) and by the share of the counted entries whose
    sign differs from the reference's (the SPSA losses of all N + 1
    perturbed models set these signs); the change over all checked
    steps."""
    import jax
    leaves = lambda tree: [np.asarray(x, np.float64)
                           for x in jax.tree.leaves(tree)]
    gn = [float(np.linalg.norm(x)) for x in leaves(r["grad0"])]
    floor = GRAD_FLOOR * float(np.median(gn))
    counted = [n >= floor for n in gn]
    p0, p1, p3 = (leaves(s) for s in snaps)
    r0, r1, r3 = leaves(r["p0"]), leaves(r["p1"]), leaves(r["p_last"])
    out = {f"loss{k}_gap": abs(a - b) / abs(b)
           for k, (a, b) in enumerate(zip(losses, r["loss"]))}
    du = [b - a for a, b in zip(p0, p1)]
    dr = [b - a for a, b in zip(r0, r1)]
    upd = leaf_norm_gaps(du, dr, counted)
    chg = leaf_norm_gaps([b - a for a, b in zip(p0, p3)],
                         [b - a for a, b in zip(r0, r3)], counted)
    miss = sum(int(np.sum(np.sign(u) != np.sign(v)))
               for u, v, c in zip(du, dr, counted) if c)
    entries = sum(u.size for u, c in zip(du, counted) if c)
    out.update(update1_gap=max(upd), update1_gap_median=float(np.median(upd)),
               update1_sign_miss=miss / entries,
               change_gap=max(chg), change_gap_median=float(np.median(chg)),
               leaves_counted=int(sum(counted)))
    return out


def compare_readings(losses: list, snaps: list, r: dict,
                     limits: dict) -> dict:
    got = readings(losses, snaps, r)
    return {k: check(got[k], lim) for k, lim in limits.items()
            if k in got}
