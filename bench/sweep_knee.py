#!/usr/bin/env python3
"""Find the serving knee once, by a sweep of offered rates on the chip.

    python3 bench/sweep_knee.py --workload tonn-hjb20d.serve-80 \
        --rates 200,400,800,1600 --seconds 8

One engine, one process: for each rate the cell's open loop offers its
mix for ``--seconds`` and stops at the close.  Per rate it prints the
offered and answered points per second, the p50 / p95 latency of the
requests answered, and the backlog at the close (points due but not yet
answered).  The knee is the highest rate at which the backlog does not
grow over the window; the cell's traffic file then states its rate as a
number (``rate_rps``, a fixed share of the knee).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(BENCH / "drivers")]

import harness  # noqa: E402


def main(argv=None) -> int:
    import numpy as np
    import open_loop
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma list")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    files = harness.cell_files(args.workload)
    cfg, mix = files["config"], dict(files["traffic"])
    harness.accelerator(files["cell"]["chips"])
    harness.enable_compile_cache()
    engine = open_loop.build(cfg, mix, open_loop.Hooks())
    rng = np.random.default_rng(args.seed)
    m = cfg["collocation_margin"]
    for rate in (float(r) for r in args.rates.split(",")):
        mix["rate_rps"] = rate
        due, sizes = open_loop.schedule(mix, args.seed, args.seconds)
        pts = rng.uniform(m, 1.0 - m, (int(sizes.sum()), cfg["space_dim"] + 1))
        loop = open_loop.OpenLoop(engine, cfg["name"],
                                  np.split(pts, np.cumsum(sizes)[:-1]), due,
                                  harness.Spans())
        res = loop.run(args.seconds, 0.0)
        lat = loop.latencies_s()
        done = np.isfinite(lat)
        offered = int(sizes.sum())
        print(json.dumps({
            "rate_rps": rate,
            "offered_points_per_s": offered / args.seconds,
            "answered_points_per_s":
                res["points_answered_in_window"] / args.seconds,
            "backlog_points_at_close":
                offered - res["points_answered_in_window"],
            "p50_ms": float(np.percentile(lat[done], 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "requests": len(due)}), flush=True)
        engine.queue.clear()
        engine.active = [None] * engine.slots
    return 0


if __name__ == "__main__":
    sys.exit(main())
