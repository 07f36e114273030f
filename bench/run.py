#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and limits are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up loads the program,
makes weights and inputs from ``--seed`` and warms every shape the window
uses (the first run in a checkout also compiles; later runs load the
programs from the persistent cache).  The window then runs for
``--seconds``; with ``--trace 1`` a short window is profiled instead and
the per-layer metrics are read from the trace, spans and counters.  Last
comes the comparison with the plain reference that decides ``correct``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``checks``, each compared number beside its limit.  Without a TPU,
with fewer chips than the cell asks for, or on a device kind missing from
``bench/peaks.json`` it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "drivers"))
sys.path.insert(0, str(BENCH / "metrics"))

import harness  # noqa: E402


def per_layer(bench: dict, cell: dict, ctx: dict) -> dict:
    """The per-layer metrics of this cell, each from its own reader; a
    reader that finds nothing to read leaves its metric out."""
    reports = {m["name"] for m in bench["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", []) and not (
                "workloads" not in m and m["moves"] in reports):
            continue
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(trace) -> dict:
    return {"device_ops": [[n, s] for n, s in trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps(10)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    files = harness.cell_files(args.workload)
    bench, cell, cfg = files["bench"], files["cell"], files["config"]
    mix = files["traffic"]
    devices, device, peak = harness.accelerator(cell["chips"])
    harness.enable_compile_cache()
    driver = harness.load_module(BENCH / "drivers" / f"{mix['generator']}.py")
    traced = bool(args.trace)
    res = driver.run(cfg, mix, files["checks"], args.seed, args.seconds,
                     traced, devices, T_START)

    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    result = {"correct": all(c["ok"] for c in res["checks"].values())
              and res["failed"] == 0,
              "attempted": res["attempted"], "failed": res["failed"]}
    if traced:
        trace = res["trace"]
        ctx = dict(res["layer_ctx"], trace=trace, config=cfg, mix=mix,
                   peak=peak)
        result["metrics"] = per_layer(bench, cell, ctx)
        device.update(busy_s=trace.mean_busy_s(), window_s=trace.window_s)
        result["device"] = device
        result["breakdown"] = breakdown(trace)
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res["e2e"].items()}
        metrics["setup_s"] = {"value": res["setup_s"], "unit": "s"}
        result["metrics"] = metrics
        result["device"] = device
    harness.log(f"setup_s {res['setup_s']!r}")
    harness.emit(result, res["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
