"""Batched photonic mesh engine benchmark: the phase-domain ZO hot path
(tonn/onn with the fabrication-noise model ON — the paper's Table-1 on-chip
rows) through the stacked mesh engine vs the pre-PR vmap-fallback paths,
plus mesh-apply microbenchmarks and parity numbers (DESIGN.md §Photonic).

Arms per ZO-step row (N=10 SPSA samples unless overridden):

  * ``stacked``        — this PR: ONE batched gather-form mesh pass
    densifies all N+1 perturbed TONN core meshes
    (``PhotonicMatrix.to_dense_stacked``), onn's layer meshes densify the
    same way before dense products, and the fixed ±1 diag buffers are excluded from the
    SPSA probe (``TensorPinn.trainable_mask``).
  * ``vmap_fallback``  — the generic ``residual_losses_stacked`` fallback
    (``jax.vmap`` of the scalar loss — the ONLY pre-PR path for onn),
    compiled against the seed's scatter-per-level ``lax.scan`` mesh.
  * ``legacy_stacked`` (tonn only) — the pre-PR tonn hot path: a plain
    per-perturbation ``jax.vmap`` of the scalar densification through the
    scan mesh, feeding the stacked TT evaluator.

Where the win lands: the ZO step is mesh-bound when the TT-core unfoldings
are large (few, wide cores — ``tt_L=2``), and activation-bound at the
paper's 4-core factorization (where both arms move the same activation
bytes and the gap is the mesh+sine share).  The gate row (``--ci`` asserts
≥ 2×) is the mesh-dominated config; the paper-factorization row is
reported un-gated for honesty.

Parity (asserted on every row):

  * mesh-apply: the stacked gather engine vs a loop of the sequential
    photonic-realism scan path, at strict f32 forward tolerance;
  * u-stencils: the stacked evaluator vs the per-perturbation sequential
    scan-mesh path at strict f32 forward tolerance (losses then differ
    only by the documented 1/h² FD amplification — DESIGN.md §Perf);
  * one ZO step leaves every diag buffer bit-identical.

Emits ``BENCH_photonic_mesh.json`` (archived by CI).

    PYTHONPATH=src python benchmarks/photonic_mesh.py --ci
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp

from repro.core import photonic, pinn, zoo


def _time(fn, repeats: int = 3) -> float:
    """Median wall-time (ms); the callable must already be compiled."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e3


# ------------------------------------------------------ legacy (pre-PR) path

@contextlib.contextmanager
def scan_mesh():
    """Trace-time swap of the mesh engine back to the seed's scatter scan:
    compiling a jitted function inside this context bakes the pre-PR mesh
    into that program (photonic-realism arithmetic), so the fallback arms
    measure what the code actually did before this PR."""
    orig = photonic.mesh_apply
    photonic.mesh_apply = photonic.mesh_apply_scan
    try:
        yield
    finally:
        photonic.mesh_apply = orig


def legacy_prepare_stacked(model: pinn.TensorPinn, stacked: dict,
                           noise: dict | None) -> dict:
    """The pre-PR ``prepare_params_stacked``: a plain per-perturbation
    ``jax.vmap`` of the scalar densification.  Trace the caller inside
    ``scan_mesh()`` to bake in the seed's scatter mesh — together these
    reproduce the pre-PR tonn hot path with no re-implementation that
    could drift from ``PhotonicMatrix.apply``."""
    return jax.vmap(lambda p: model.prepare_params(p, noise)[0])(stacked)


# ------------------------------------------------------------ microbench

def bench_mesh_apply(ports: int, S: int, batch: int, repeats: int) -> dict:
    """Gather vs scan for one mesh; stacked engine vs vmap-of-scan for a
    perturbation stack — the raw primitive the ZO step is built from."""
    lay = photonic.rectangular_layout(ports)
    key = jax.random.PRNGKey(0)
    phs = jax.random.normal(key, (S,) + lay.phase_shape())
    d = jnp.ones((ports,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (batch, ports))

    gather = jax.jit(lambda: photonic.mesh_apply(lay, phs[0], d, x))
    scan = jax.jit(lambda: photonic.mesh_apply_scan(lay, phs[0], d, x))
    gather_ms, scan_ms = _time(gather, repeats), _time(scan, repeats)

    stacked = jax.jit(lambda: photonic.mesh_apply_stacked(lay, phs, d, x))
    vmapped = jax.jit(jax.vmap(
        lambda p: photonic.mesh_apply_scan(lay, p, d, x)))
    stacked_ms = _time(stacked, repeats)
    vmap_ms = _time(lambda: vmapped(phs), repeats)

    err = float(jnp.max(jnp.abs(stacked() - vmapped(phs))))
    return {
        "ports": ports, "stack": S, "batch": batch,
        "gather_ms": round(gather_ms, 3), "scan_ms": round(scan_ms, 3),
        "gather_speedup": round(scan_ms / gather_ms, 2),
        "stacked_ms": round(stacked_ms, 3), "vmap_scan_ms": round(vmap_ms, 3),
        "stacked_speedup": round(vmap_ms / stacked_ms, 2),
        "stacked_vs_scan_abs_err": err,
        "parity_ok": bool(err < 1e-5),
    }


# ---------------------------------------------------------- ZO step bench

def bench_zo_mode(mode: str, hidden: int, batch: int, num_samples: int,
                  tt_rank: int, tt_L: int, repeats: int, label: str,
                  gate: bool, seed: int = 0, pde: str = "hjb-20d") -> dict:
    nm = photonic.NoiseModel(enabled=True)
    cfg = pinn.PINNConfig(hidden=hidden, mode=mode, tt_rank=tt_rank,
                          tt_L=tt_L, deriv="fd_fast", pde=pde, noise=nm,
                          use_fused_kernel=True)
    model = pinn.TensorPinn(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    noise = model.sample_noise(jax.random.fold_in(key, 99))
    mask = model.trainable_mask(params)
    xt = model.problem.sample_collocation(jax.random.fold_in(key, 1), batch)
    scfg = zoo.SPSAConfig(num_samples=num_samples, mu=0.01)
    state = zoo.ZOState.create(seed + 1)
    lf = lambda p: pinn.residual_loss(model, p, xt, noise)

    def make_step(blf):
        return jax.jit(lambda p, s: zoo.zo_signsgd_step(
            lf, p, s, lr=1e-3, cfg=scfg, batched_loss_fn=blf,
            trainable_mask=mask))

    stacked_step = make_step(
        lambda sp: pinn.residual_losses_stacked(model, sp, xt, noise))
    fallback_step = make_step(jax.vmap(lf))
    legacy_step = None
    if mode == "tonn":
        legacy_step = make_step(
            lambda sp: pinn.residual_losses_stacked(
                model, legacy_prepare_stacked(model, sp, noise), xt, noise))

    with scan_mesh():  # bake the pre-PR mesh into the fallback programs
        jax.block_until_ready(fallback_step(params, state)[2])
        if legacy_step is not None:
            jax.block_until_ready(legacy_step(params, state)[2])
    stacked_ms = _time(lambda: stacked_step(params, state)[2], repeats)
    fallback_ms = _time(lambda: fallback_step(params, state)[2], repeats)
    legacy_ms = (None if legacy_step is None else
                 _time(lambda: legacy_step(params, state)[2], repeats))

    # ---- parity: stacked engine vs the sequential photonic-realism path
    xis = zoo.sample_perturbations(jax.random.fold_in(key, 2), params,
                                   num_samples, mask)
    sp = jax.tree.map(lambda p, z: p + scfg.mu * z, params, xis)
    h = model.fd_step
    prepared = model.prepare_params_stacked(sp, noise)
    u_stacked = model.fd_u_stencil_stacked(prepared, xt, h)
    seq_stencil = jax.jit(lambda p: model.fd_u_stencil(p, xt, h, noise))
    with scan_mesh():  # sequential reference = the scan-mesh realism path
        jax.block_until_ready(
            seq_stencil(jax.tree.map(lambda z: z[0], sp)))
    u_seq = jnp.stack([seq_stencil(jax.tree.map(lambda z: z[i], sp))
                       for i in range(num_samples)])
    u_rel = float(jnp.max(jnp.abs(u_stacked - u_seq)
                          / (jnp.abs(u_seq) + 1e-6)))

    seq_loss = jax.jit(lambda p: pinn.residual_loss(model, p, xt, noise))
    with scan_mesh():
        jax.block_until_ready(seq_loss(jax.tree.map(lambda z: z[0], sp)))
    l_seq = jnp.stack([seq_loss(jax.tree.map(lambda z: z[i], sp))
                       for i in range(num_samples)])
    l_stacked = pinn.residual_losses_stacked(model, sp, xt, noise)
    loss_rel = float(jnp.max(jnp.abs(l_stacked - l_seq))
                     / (float(jnp.max(jnp.abs(l_seq))) + 1e-12))

    # ---- buffer freeze: one step must keep every diag bit-identical
    p1, _, _ = stacked_step(params, state)
    diag_frozen = all(
        bool(jnp.all(a == b))
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(p1)[0])
        if any(isinstance(k, jax.tree_util.DictKey)
               and k.key in photonic.PHOTONIC_BUFFER_KEYS for k in pa))

    # u-stencils at strict f32 forward tolerance; the squared-second-
    # difference losses amplify that by 1/h² = 1e4 (DESIGN.md §Perf), and
    # small off-label configs sit nearer the bound than the paper config —
    # same rationale as the seed's 0.3 fd-vs-fd_fast tolerance
    parity_ok = bool(u_rel < 1e-4 and loss_rel < 0.3 and diag_frozen)
    return {
        "mode": mode, "label": label, "pde": pde, "hidden": hidden,
        "batch": batch, "num_samples": num_samples, "tt_rank": tt_rank,
        "tt_L": tt_L, "gate": gate,
        "stacked_ms": round(stacked_ms, 2),
        "vmap_fallback_ms": round(fallback_ms, 2),
        "speedup": round(fallback_ms / stacked_ms, 2),
        "legacy_stacked_ms": (None if legacy_ms is None
                              else round(legacy_ms, 2)),
        "legacy_speedup": (None if legacy_ms is None
                           else round(legacy_ms / stacked_ms, 2)),
        "u_max_rel_err": u_rel,
        "loss_max_rel_err": loss_rel,
        "diag_buffers_frozen": diag_frozen,
        "parity_ok": parity_ok,
    }


def run(num_samples: int = 10, repeats: int = 3, pde: str = "hjb-20d",
        full: bool = False) -> dict:
    mesh_rows = [
        bench_mesh_apply(ports=16, S=num_samples + 1, batch=256,
                         repeats=repeats),
        bench_mesh_apply(ports=64, S=num_samples + 1, batch=64,
                         repeats=repeats),
    ]
    zo_rows = [
        # gate row: wide TT-core unfoldings (tt_L=2 → 128-port meshes) make
        # the step mesh-bound — where the batched engine's win lands
        bench_zo_mode("tonn", hidden=512, batch=16, num_samples=num_samples,
                      tt_rank=4, tt_L=2, repeats=repeats,
                      label="mesh-dominated", gate=True, pde=pde),
        # the paper's 4-core factorization at CI scale: activation-bound,
        # reported un-gated (both arms move the same activation bytes)
        bench_zo_mode("tonn", hidden=64, batch=32, num_samples=num_samples,
                      tt_rank=2, tt_L=3, repeats=repeats,
                      label="paper-factorization", gate=False, pde=pde),
        bench_zo_mode("onn", hidden=64, batch=32, num_samples=num_samples,
                      tt_rank=2, tt_L=3, repeats=repeats,
                      label="svd-mesh", gate=True, pde=pde),
    ]
    if full:
        zo_rows.append(
            bench_zo_mode("tonn", hidden=1024, batch=100,
                          num_samples=num_samples, tt_rank=2, tt_L=4,
                          repeats=repeats, label="paper-scale", gate=False,
                          pde=pde))
    return {
        "config": {"num_samples": num_samples, "pde": pde, "noise": True,
                   "backend": jax.default_backend()},
        "mesh_apply": mesh_rows,
        "zo_step": zo_rows,
    }


def summarize(result: dict) -> list:
    """Rows for benchmarks/run.py's CSV."""
    out = []
    for r in result["mesh_apply"]:
        out.append({
            "name": f"photonic_mesh/apply-p{r['ports']}xS{r['stack']}",
            "us_per_call": round(r["stacked_ms"] * 1e3, 1),
            "derived": (f"stacked={r['stacked_speedup']}x vs vmap(scan) "
                        f"({r['vmap_scan_ms']}ms), gather="
                        f"{r['gather_speedup']}x vs scan"),
        })
    for r in result["zo_step"]:
        out.append({
            "name": f"photonic_mesh/zo-{r['mode']}-{r['label']}",
            "us_per_call": round(r["stacked_ms"] * 1e3, 1),
            "derived": (f"speedup={r['speedup']}x vs vmap-fallback "
                        f"({r['vmap_fallback_ms']}ms), "
                        f"u_err={r['u_max_rel_err']:.1e}, "
                        f"diag_frozen={r['diag_buffers_frozen']}"),
        })
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ci", action="store_true",
                    help="assert parity everywhere + the ≥2x gate rows")
    ap.add_argument("--full", action="store_true",
                    help="add the paper-scale tonn row (~minutes on CPU)")
    ap.add_argument("--num-samples", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--pde", default="hjb-20d")
    ap.add_argument("--out", default="BENCH_photonic_mesh.json")
    args = ap.parse_args()

    result = run(num_samples=args.num_samples, repeats=args.repeats,
                 pde=args.pde, full=args.full)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    for r in result["mesh_apply"] + result["zo_step"]:
        assert r["parity_ok"], f"photonic mesh parity failure: {r}"
    if args.ci:
        for r in result["zo_step"]:
            if r["gate"]:
                assert r["speedup"] >= 2.0, \
                    f"stacked ZO step below the 2x gate: {r}"
    print(f"[photonic_mesh] OK ({len(result['zo_step'])} ZO rows)")


if __name__ == "__main__":
    main()
