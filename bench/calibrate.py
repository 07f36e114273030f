#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--points N]

For each seed, in one process, and at the cell's own size:

  program   the timed path's first steps (training) or served u (serving)
            against the plain reference at the configuration's precision;
  control   the reference computed one precision lower (three-pass
            bfloat16 matmuls for float32 at HIGHEST) against the same;
  faults    (training) the reference with half of the batch left out, and
            the program's first update turned uphill (its sign flipped).
            A state left unchanged reads 1 on the update gaps and on the
            sign miss, and needs no run.

Prints one JSON line per seed and reading.  It runs on whatever backend
JAX finds: the readings that set the limits come from the chip;
``bench/test_control.py`` runs it on the CPU at a reduced size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(BENCH / "drivers")]

import harness  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def stack_losses(cfg: dict, job: dict, seed: int, ref) -> list:
    """The program's N + 1 losses of the first step (the stacked loss on
    the first step's perturbation stack): a diagnostic beside the
    reference's, to weigh the rounding in each loss against the SPSA
    differences L_i − L_0 that the sign update reads."""
    import jax
    from repro.core import pinn
    model = harness.make_model(cfg)
    params, noise = ref.seeded_start(cfg, seed)
    _, sub = jax.random.split(jax.random.PRNGKey(seed + 1))
    xis = ref.perturbations(sub, params, job["zo_samples"])
    stack = jax.tree.map(lambda p, *zs: jnp.stack(
        [p] + [p + job["mu"] * z for z in zs]), params, *xis)
    xt = ref.collocation(cfg, seed, 0, job["batch"])
    return [float(x) for x in jax.jit(
        lambda sp: pinn.residual_losses_stacked(model, sp, xt, noise))(stack)]


def train_readings(cfg: dict, job: dict, seed: int) -> dict:
    import jax
    import numpy as np
    import zo_train
    ref = harness.reference_module(cfg)
    step, params, aux, colloc, terms = zo_train.build(
        cfg, job, seed, zo_train.Hooks())
    n = job["check_steps"]
    _, _, losses, snaps = zo_train.first_steps(step, params, aux, colloc,
                                               terms, job["lr"], n)
    del step, params, aux
    base = ref.zo_signsgd_steps(cfg, job, seed, n)
    prog0 = np.asarray(stack_losses(cfg, job, seed, ref))
    ref0 = np.asarray(base["stack0"])

    def against(r):
        return zo_train.readings(r["loss"], [r["p0"], r["p1"], r["p_last"]],
                                 base)

    p0, p1, p_last = snaps
    uphill = [p0, jax.tree.map(lambda a, b: 2 * a - b, p0, p1), p_last]

    out = {"program": zo_train.readings(losses, snaps, base),
           "losses": {"program": losses, "reference": base["loss"]},
           "stack0": {"max_loss_gap": float(np.abs(prog0 - ref0).max()),
                      "spsa_deltas_abs_median": float(
                          np.median(np.abs(ref0[1:] - ref0[0]))),
                      "spsa_delta_gap_max": float(np.abs(
                          (prog0[1:] - prog0[0]) - (ref0[1:] - ref0[0])).max())},
           "control": against(ref.zo_signsgd_steps(cfg, job, seed, n,
                                                   precision="high")),
           "half_batch": against(ref.zo_signsgd_steps(cfg, job, seed, n,
                                                      half_batch=True)),
           "uphill": zo_train.readings(losses, uphill, base)}
    return out


def serve_readings(cfg: dict, mix: dict, seed: int, points: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import open_loop
    from repro.serving import PointRequest
    ref = harness.reference_module(cfg)
    engine = open_loop.build(cfg, mix, open_loop.Hooks())
    rng = np.random.default_rng([seed, 1])
    m = cfg["collocation_margin"]
    pts = rng.uniform(m, 1.0 - m, (points, cfg["space_dim"] + 1))
    reqs = [engine.submit(PointRequest(cfg["name"], p))
            for p in np.array_split(pts, max(1, points // 256))]
    engine.run()
    got = np.concatenate([r.out for r in reqs])
    del engine
    params, noise = ref.seeded_start(cfg, mix["weights_seed"])
    x = jnp.asarray(pts.astype(np.float32))
    u = {prec: jax.jit(lambda p, nz, q, prec=prec: ref.u_points(
        p, nz, q, cfg, prec)) for prec in ("highest", "high")}
    want = np.asarray(u["highest"](params, noise, x), np.float64)
    low = np.asarray(u["high"](params, noise, x), np.float64)
    gap = lambda a: {"u_gap_max": float(np.abs(a - want).max()),
                     "u_gap_mean": float(np.abs(a - want).mean())}
    return {"program": gap(got), "control": gap(low)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--points", type=int, default=65536)
    args = ap.parse_args(argv)
    files = harness.cell_files(args.workload)
    cfg, mix = files["config"], files["traffic"]
    harness.enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if mix["generator"] == "zo_train":
            r = train_readings(cfg, mix, seed)
        else:
            r = serve_readings(cfg, mix, seed, args.points)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
