"""PDE serving launcher: load trained solver checkpoints by name and
drive the slot-batched inference runtime (``repro.serving``).

Each ``--ckpt NAME=DIR`` loads a self-describing ``launch/train.py``
checkpoint into the registry; ``--synthetic N`` generates N mixed
variable-size requests against every loaded solver (a traffic smoke /
sizing tool — the measured benchmark is the chip cell
``tonn-hjb20d.serve-80``, ``bench/run.py``).

    PYTHONPATH=src python -m repro.launch.serve_pde \
        --ckpt heat=ckpts/heat-10d --ckpt hjb=ckpts/hjb-20d \
        --synthetic 64 --slots 8 --slot-points 256
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.runtime import enable_compile_cache
from repro.serving import PdeServingEngine, PointRequest, SolverRegistry


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", action="append", required=True,
                    metavar="NAME=DIR",
                    help="load checkpoint DIR as solver NAME (repeatable)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--slot-points", type=int, default=256)
    ap.add_argument("--synthetic", type=int, default=32,
                    help="number of synthetic requests to serve")
    ap.add_argument("--max-request-points", type=int, default=256)
    ap.add_argument("--cache-capacity", type=int, default=65536)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    reg = SolverRegistry()
    for spec in args.ckpt:
        name, _, directory = spec.partition("=")
        if not directory:
            raise SystemExit(f"--ckpt wants NAME=DIR, got {spec!r}")
        s = reg.load_checkpoint(name, directory)
        print(f"[serve_pde] loaded {name!r}: pde={s.problem.name} "
              f"mode={s.model.cfg.mode} step={s.step}")

    from repro.serving.cache import StencilCache
    engine = PdeServingEngine(reg, slots=args.slots,
                              slot_points=args.slot_points,
                              cache=StencilCache(args.cache_capacity))
    engine.warmup()
    print(f"[serve_pde] warm: {engine.stats['compiles']} compiled "
          f"program(s), pool {args.slots}x{args.slot_points}")

    # pre-generate the traffic so measured latency is serving, not
    # point-sampling
    rng = np.random.RandomState(args.seed)
    names = reg.names()
    traffic = []
    for i in range(args.synthetic):
        name = names[i % len(names)]
        n = int(rng.randint(1, args.max_request_points + 1))
        traffic.append((name, np.asarray(
            reg.get(name).problem.sample_collocation(
                jax.random.PRNGKey(args.seed * 10_000 + i), n),
            np.float32)))
    reqs = [engine.submit(PointRequest(name, pts)) for name, pts in traffic]
    engine.run()

    lat_ms = np.asarray([r.latency_s for r in reqs]) * 1e3
    print(f"[serve_pde] served {len(reqs)} requests / "
          f"{sum(len(r.points) for r in reqs)} points: "
          f"p50 {np.percentile(lat_ms, 50):.2f} ms, "
          f"p99 {np.percentile(lat_ms, 99):.2f} ms")
    stats = engine.serving_stats()
    cache = stats.get("cache", {})
    print(f"[serve_pde] programs: {stats['compiles']} compiled, "
          f"{stats['program_runs']} runs; stencil cache: "
          f"{stats['cache_hits']} hits / {stats['cache_misses']} misses "
          f"(hit rate {cache.get('hit_rate', 0.0):.1%}), "
          f"{stats['cache_evictions']} evictions")
    print(json.dumps(stats, indent=2))


if __name__ == "__main__":
    main()
