"""Serving cells: an open loop of point queries into ``PdeServingEngine``.

Set-up registers one solver in a ``SolverRegistry`` (weights and
fabrication noise from the mix's ``weights_seed``, one jitted call each:
the engine compiles its program with the solver's weights as constants,
so weights that changed with ``--seed`` would compile anew in every run),
builds the engine with the mix's slots and slot size, warms its one
program, and draws the whole schedule in bulk: request sizes and
inter-arrival gaps are a fixed multiset drawn from the mix's ``work_seed``
(so every run offers the same work), put in an order drawn from
``--seed``; every query point is fresh and uniform in the domain, from
``--seed``.

The window submits each request when it is due (Poisson arrivals at
``rate_rps``) and steps the engine whenever it holds work, all in this one
thread.  A request's latency runs from its due time to its completion, so
a stall counts against every request that waits behind it.  After the
window closes the loop keeps stepping, without new arrivals, until every
request is answered or ``grace_seconds`` have passed; a request still
unanswered then has failed and counts as infinitely late.

``correct`` compares every served u with the plain reference.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import Spans, check, log, make_model, memory_peak_bytes, \
    profiled, reference_module


@dataclasses.dataclass
class Hooks:
    """Seam for the benchmark's own tests: a wrapper around each compiled
    program the engine builds."""

    wrap_program: object = None


def schedule(mix: dict, seed: int, seconds: float) -> tuple:
    """(due times in s from the window's start, request sizes)."""
    n = int(np.ceil(mix["rate_rps"] * seconds))
    work = np.random.default_rng(mix["work_seed"])
    small, large = mix["small"], mix["large"]
    is_small = np.arange(n) < round(small["share"] * n)
    sizes = np.where(
        is_small,
        work.integers(small["lo"], small["hi"] + 1, n),
        np.exp(work.uniform(np.log(large["lo"]), np.log(large["hi"] + 1),
                            n)).astype(np.int64))
    gaps = work.exponential(1.0, n)
    order = np.random.default_rng(seed)
    sizes, gaps = order.permutation(sizes), order.permutation(gaps)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, sizes


def build(cfg: dict, mix: dict, hooks: Hooks):
    import jax
    from repro.serving import PdeServingEngine, SolverRegistry

    model = make_model(cfg)
    key = jax.random.PRNGKey(mix["weights_seed"])
    params = jax.jit(model.init)(key)
    noise = jax.jit(model.sample_noise)(jax.random.fold_in(key, 99))
    reg = SolverRegistry()
    reg.register(cfg["name"], model, params, hw_noise=noise)
    engine = PdeServingEngine(reg, slots=mix["slots"],
                              slot_points=mix["slot_points"])
    if hooks.wrap_program is not None:
        inner = engine._program
        engine._program = lambda *a, **k: hooks.wrap_program(inner(*a, **k))
    engine.warmup()
    return engine


class OpenLoop:
    """Drives one engine with one schedule; times from the due times."""

    def __init__(self, engine, solver: str, points: list, due, spans):
        self.engine, self.solver, self.points = engine, solver, points
        self.due, self.spans = due, spans
        self.reqs, self.late = [], []

    def run(self, seconds: float, grace: float) -> dict:
        from repro.serving import PointRequest
        eng, spans, due = self.engine, self.spans, self.due
        n, i = len(due), 0
        t0 = time.perf_counter()
        close, stop = t0 + seconds, t0 + seconds + grace
        self.t0 = t0
        served_at_close = None
        while True:
            now = time.perf_counter()
            if served_at_close is None and now >= close:
                served_at_close = self._answered(close)
            if i < n and t0 + due[i] <= now:
                with spans("submit"):
                    while i < n and t0 + due[i] <= now:
                        self.reqs.append(eng.submit(
                            PointRequest(self.solver, self.points[i])))
                        self.late.append(now - (t0 + due[i]))
                        i += 1
            if eng.queue or any(s is not None for s in eng.active):
                with spans("engine_step"):
                    eng.step()
            elif i < n:
                with spans("generator"):
                    wait = t0 + due[i] - time.perf_counter()
                    if wait > 2e-3:
                        time.sleep(wait - 1e-3)
            elif served_at_close is not None or now >= stop:
                break
            if now >= stop:
                break
        if served_at_close is None:
            served_at_close = self._answered(close)
        return {"points_answered_in_window": served_at_close}

    def _answered(self, close: float) -> int:
        return int(sum(len(r.points) for r in self.reqs
                       if r.done and r.t_done <= close))

    def latencies_s(self) -> np.ndarray:
        lat = np.full(len(self.due), np.inf)
        for k, r in enumerate(self.reqs):
            if r.done:
                lat[k] = r.t_done - (self.t0 + self.due[k])
        return lat


def run(cfg: dict, mix: dict, limits: dict, seed: int, seconds: float,
        traced: bool, devices, t_start: float, hooks: Hooks | None = None
        ) -> dict:
    hooks = hooks or Hooks()
    engine = build(cfg, mix, hooks)
    window = mix["trace_seconds"] if traced else seconds
    due, sizes = schedule(mix, seed, window)
    rng = np.random.default_rng([seed, 1])
    lo, hi = cfg["collocation_margin"], 1.0 - cfg["collocation_margin"]
    flat = rng.uniform(lo, hi, (int(sizes.sum()), cfg["space_dim"] + 1))
    points = np.split(flat, np.cumsum(sizes)[:-1])
    spans = Spans(traced)
    loop = OpenLoop(engine, cfg["name"], points, due, spans)
    setup_s = time.perf_counter() - t_start
    compiles = engine.stats["compiles"]
    with spans.collections():
        if traced:
            with profiled() as prof:
                res = loop.run(window, mix["grace_seconds"])
        else:
            res = loop.run(window, mix["grace_seconds"])
    lat = loop.latencies_s()
    failed = int(np.sum(~np.isfinite(lat)))
    late = np.asarray(loop.late)
    log(f"{len(due)} requests, {int(sizes.sum())} points; generator late "
        f"p50 {np.percentile(late, 50) * 1e3:.4f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.4f} ms, max "
        f"{late.max() * 1e3:.4f} ms; failed {failed}; compiles in window "
        f"{engine.stats['compiles'] - compiles}; {spans.gc_summary()}")
    out = {"setup_s": setup_s, "attempted": len(due), "failed": failed,
           "memory_peak_bytes": memory_peak_bytes(devices)}
    stats = engine.serving_stats()
    steps = spans.counts.get("engine_step", 0)
    out["layer_ctx"] = {
        "counters": stats,
        "engine_step_s": spans.seconds.get("engine_step", 0.0),
        "engine_steps": steps}
    log(f"latency p50 {np.percentile(lat, 50) * 1e3:.4f} ms, p95 "
        f"{np.percentile(lat, 95) * 1e3:.4f} ms")
    if traced:
        out["trace"] = prof["trace"]
    else:
        out["e2e"] = {
            "serve_points_per_s": res["points_answered_in_window"] / window}
    if engine.stats["compiles"] != compiles:
        raise SystemExit("bench: the engine compiled inside the window")
    served = [(p, r.out) for p, r in zip(points, loop.reqs) if r.done]
    del engine, loop
    out["checks"] = compare(cfg, mix, limits, served)
    return out


def compare(cfg: dict, mix: dict, limits: dict, served: list) -> dict:
    """Every served u against the plain reference at the same points: the
    largest gap (an answer altered shows here) and the mean gap over all
    points (a forward computed in a lower precision shows here)."""
    import jax
    import jax.numpy as jnp
    ref = reference_module(cfg)
    t = time.perf_counter()
    params, noise = ref.seeded_start(cfg, mix["weights_seed"])
    u = jax.jit(lambda p, nz, x: ref.u_points(p, nz, x, cfg, "highest"))
    fwd = lambda x: u(params, noise, x)
    pts = np.concatenate([p for p, _ in served]).astype(np.float32)
    got = np.concatenate([o for _, o in served])
    block = 65536
    want = np.concatenate([
        np.asarray(fwd(jnp.asarray(np.pad(
            pts[k:k + block], [(0, block - len(pts[k:k + block])), (0, 0)],
            mode="edge"))))[:len(pts[k:k + block])]
        for k in range(0, len(pts), block)])
    gap = np.abs(got - want.astype(np.float64))
    log(f"reference: {len(pts)} points in {time.perf_counter() - t:.3f} s")
    return {"u_gap_max": check(float(gap.max()), limits["u_gap_max"]),
            "u_gap_mean": check(float(gap.mean()), limits["u_gap_mean"])}
