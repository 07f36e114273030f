"""Shared pieces of the benchmark: files found by name, the device check,
the traced window, the comparison helpers and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  bench/configs/<config>.json      sizes of the configuration as it is run
  bench/traffic/<traffic>.json     parameters of a traffic mix; its
                                   "generator" names the module in
                                   bench/drivers/ that runs it
  bench/checks/<workload>.json     limits of the comparison that decides
                                   ``correct`` in that cell
  bench/metrics/<metric>.py        reader of one per-layer metric:
                                   ``read(ctx) -> float | None``
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str | None = None):
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_files(workload: str) -> dict:
    """The cell's entries and files, by the names in BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT / configs[cell["config"]]["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "checks": load_json(BENCH / "checks" / f"{workload}.json"),
    }


def make_model(cfg: dict):
    """The program's ``TensorPinn`` for the configuration file, checked
    against the file's TT shapes."""
    import dataclasses
    from repro.configs.hjb_pinn import pinn_config
    from repro.core import photonic, pinn
    pcfg = pinn_config(cfg["pde"], cfg["mode"], fused=cfg["fused"],
                       noise=cfg["noise"]["enabled"], hidden=cfg["hidden"],
                       tt_rank=cfg["tt_rank"], tt_L=cfg["tt_L"],
                       fd_step=cfg["fd_step"])
    pcfg = dataclasses.replace(pcfg,
                               noise=photonic.NoiseModel(**cfg["noise"]))
    model = pinn.TensorPinn(pcfg)
    for spec in model.specs:
        if (list(spec.out_modes), list(spec.in_modes), list(spec.ranks)) != (
                cfg["tt_out_modes"], cfg["tt_in_modes"], cfg["tt_ranks"]):
            raise SystemExit(f"bench: the program builds TT layer {spec}, "
                             "not the configuration's")
    return model


def reference_module(cfg: dict):
    return load_module(ROOT / cfg["reference"])


# ------------------------------------------------------------------ device

def accelerator(chips: int) -> tuple:
    """(devices to use, device record, peaks) — or exit non-zero where JAX
    finds no TPU, fewer chips than the cell asks for, or a device kind
    missing from bench/peaks.json."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"bench: JAX finds no TPU (platform {d0.platform!r});"
                         " nothing is measured off the chip")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if d0.device_kind not in peaks:
        raise SystemExit(f"bench: device kind {d0.device_kind!r} is not in "
                         "bench/peaks.json")
    record = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    return devs[:chips], record, peaks[d0.device_kind]


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, at its fixed path inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says); every program
    is kept, so a second run of a cell compiles nothing."""
    import jax
    from repro.runtime import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ tracing

class Spans:
    """Host spans of the benchmark: ``TraceAnnotation``s in the profiler's
    trace when tracing, and summed host time per name always.  Inside
    ``collections()`` each garbage collection of Python's is a "gc" span
    too, and ``gc_longest_s`` keeps the longest."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.seconds: dict = {}
        self.counts: dict = {}
        self.gc_longest_s = 0.0

    def _add(self, name: str, seconds: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + 1

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        ctx = (jax.profiler.TraceAnnotation(name) if self.traced
               else contextlib.nullcontext())
        with ctx:
            yield
        self._add(name, time.perf_counter() - t)

    @contextlib.contextmanager
    def collections(self):
        import jax
        open_ = {}

        def on_gc(phase, info):
            if phase == "start":
                open_["t"] = time.perf_counter()
                if self.traced:
                    open_["span"] = jax.profiler.TraceAnnotation("gc")
                    open_["span"].__enter__()
            elif "t" in open_:
                if "span" in open_:
                    open_.pop("span").__exit__(None, None, None)
                dt = time.perf_counter() - open_.pop("t")
                self._add("gc", dt)
                self.gc_longest_s = max(self.gc_longest_s, dt)

        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)

    def gc_summary(self) -> str:
        return (f"{self.counts.get('gc', 0)} garbage collections, "
                f"{self.seconds.get('gc', 0.0) * 1e3:.3f} ms, longest "
                f"{self.gc_longest_s * 1e3:.3f} ms")


@contextlib.contextmanager
def profiled():
    """Profile the enclosed block; yields a dict that holds the reduced
    ``xtrace.Trace`` under "trace" once the block has closed.  The
    raw trace is written under TMPDIR and deleted after it is read."""
    import jax
    xtrace = load_module(BENCH / "xtrace.py")
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    out = {}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans suffice
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["trace"] = xtrace.load(xtrace.find_xplane(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


# ---------------------------------------------------------------- compare

def leaf_norm_gaps(prog: list, ref: list, counted: list) -> list:
    """Per counted leaf, |‖prog‖ − ‖ref‖| / max(‖ref leaf‖, median ‖ref
    leaf‖): a gap of norms, not the norm of a gap."""
    import numpy as np
    rn = [float(np.linalg.norm(r)) for r, c in zip(ref, counted) if c]
    pn = [float(np.linalg.norm(p)) for p, c in zip(prog, counted) if c]
    med = float(np.median(rn))
    return [abs(p - r) / max(r, med) for p, r in zip(pn, rn)]


def check(value: float, limit: float) -> dict:
    return {"value": value, "limit": limit,
            "ok": bool(math.isfinite(value) and value <= limit)}


# ------------------------------------------------------------------ output

def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers beside their limits as the last lines on
    stderr, then the result as the last line on stdout, with the checks
    under a key of their own that comes last."""
    shown = {k: {"value": v["value"], "limit": v["limit"]}
             for k, v in checks.items()}
    for k, v in shown.items():
        print(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = shown
    print(json.dumps(line, allow_nan=True))
    sys.stdout.flush()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)

