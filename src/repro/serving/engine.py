"""Slot-batched PDE inference engine (DESIGN.md §Serving).

The PINN analogue of the LM ``ServingEngine`` (``launch/serve.py``): a
request is a batch of query points ``(x, t)`` for a named solver, and the
engine serves mixed traffic from many clients through a fixed pool of
``slots`` slots of ``slot_points`` points each.

The three invariants the whole design hangs on:

  * **compile-once / shape-stable** — exactly ONE program per
    ``(solver, dtype, slot-shape)`` triple, AOT-compiled (``jit.lower(...)
    .compile()``) the first time that triple sees traffic and reused for
    every subsequent step; its input shape is always the FULL pool
    ``(slots·slot_points, net_dim)`` (physical dims + coefficient slots
    for conditioned solvers — coefficient VALUES are input data, never
    part of the key), so no request mix, queue depth, request size, or
    coefficient instance can ever trigger a recompile.
    ``stats["compiles"]`` counts program builds and the tests pin it.
  * **pad-to-slot, bit-identical** — a chunk shorter than a slot pads with
    an in-domain fill point and idle slots evaluate pure fill; XLA:CPU/TPU
    GEMMs reduce over the contraction axis per output row, so a row's
    result does not depend on the other rows and the served values are
    BIT-identical to a direct ``TensorPinn.u`` forward on the bare points
    (asserted by tests and the benchmark).
  * **continuous admission** — requests queue in a deque; every step packs
    chunks of the head request(s) into whatever slots are free (a request
    larger than the pool simply spans steps).  A slot's lifetime is one
    step — PDE point inference has no decode loop — so the pool recycles
    completely under churn.

Repeated stencil/grid queries short-circuit through the ``StencilCache``
at submit time: cache hits never occupy a slot, and fully-cached requests
complete without touching a program (``repro.serving.cache``).

Host time is attributed by the engine's ``Recorder``
(``repro.runtime.trace``): spans ``engine.submit`` (with ``cache.lookup``
inside), and per step that has work ``engine.step`` with ``engine.pack``
(admission, grouping, pool buffers), ``engine.launch`` (host-to-device
copy and the program's asynchronous dispatch), ``engine.fetch`` (the wait
for the device and the copy back) and ``cache.insert`` inside; scatter
and slot retirement are ``engine.step``'s self time.  Counters
``queue_wait_s`` and ``requests_admitted`` sum submit-to-first-slot waits.
``serving_stats()`` reports all of them.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.trace import Recorder
from repro.serving.cache import StencilCache
from repro.serving.registry import SolverRegistry

__all__ = ["PointRequest", "PdeServingEngine"]


def _quant_tag(quant) -> str:
    """Canonical quant-config tag for program/cache keys; empty when
    quantization is off, so pre-quantization key formats (and the tests
    pinning them) are preserved exactly."""
    return "" if quant is None else quant.tag()


@dataclasses.dataclass
class PointRequest:
    """One client query: evaluate ``u`` of ``solver`` at ``points``.

    ``out`` is filled in place (same order as ``points``); ``done`` flips
    when every point is served.  Engine timestamps (``time.perf_counter``):
    ``t_submit`` when ``submit`` accepted the request, ``t_admit`` when a
    chunk of it first took a slot (``None`` for a request served wholly
    from the cache), ``t_done`` when its last point was served and put in
    the cache.  ``latency_s`` is ``t_done − t_submit``, queue wait
    included; an open loop that times from each request's due time
    starts earlier.
    ``quant`` (a ``kernels.quant.QuantConfig``) requests quantized
    inference: it extends the program key — one extra AOT program per
    (solver, dtype, quant, slot-shape), compiled once like any other —
    and isolates the request's cache entries under the quant tag.
    ``coeffs`` (one ``(K,)`` vector of RAW coefficient values, e.g.
    ``[kappa]``) selects the PDE instance a coefficient-conditioned
    solver evaluates: required for conditioned solvers, rejected for
    unconditioned ones, and validated against the TRAINED ranges at
    submit time.  The values ride in the input rows (every point gets
    the vector appended), never in the program key, so one AOT program
    serves the whole coefficient family with zero extra compiles; the
    augmented rows also key the stencil cache, isolating coefficient
    instances from each other automatically.
    """

    solver: str
    points: np.ndarray                    # (n, in_dim) physical points
    dtype: Any = np.float32
    quant: Any = None                     # QuantConfig | None (None = f32)
    coeffs: Any = None                    # (K,) raw coefficients | None
    out: np.ndarray | None = None         # (n,) served u-values
    done: bool = False
    t_submit: float = 0.0
    t_admit: float | None = None
    t_done: float = 0.0
    # internal bookkeeping (engine-owned)
    _miss_idx: np.ndarray | None = None   # positions still to compute
    _keys: list | None = None             # cache keys of the misses
    _cursor: int = 0                      # misses packed into slots so far
    _inflight: int = 0                    # chunks currently in slots

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass
class _Slot:
    """One occupied slot: a chunk of a request's miss-points."""

    req: PointRequest
    offset: int     # chunk start within req._miss_idx
    count: int      # chunk length (<= slot_points)


class PdeServingEngine:
    """Continuous-batching point-query server over a ``SolverRegistry``."""

    def __init__(self, registry: SolverRegistry, slots: int = 8,
                 slot_points: int = 256,
                 cache: StencilCache | None = None,
                 enable_cache: bool = True):
        if slots <= 0 or slot_points <= 0:
            raise ValueError("slots and slot_points must be positive")
        self.registry = registry
        self.slots = slots
        self.slot_points = slot_points
        self.cache = cache if cache is not None else (
            StencilCache() if enable_cache else None)
        # deque admission (the LM engine's list.pop(0) was O(n) per admit)
        self.queue: collections.deque[PointRequest] = collections.deque()
        self.active: list[_Slot | None] = [None] * slots
        self._programs: dict = {}      # (solver, dtype[, quant], S, C) -> exe
        self._fill: dict = {}          # solver -> in-domain fill point
        self.trace = Recorder()        # host spans and queue-wait counters
        self.stats = {"compiles": 0, "steps": 0, "program_runs": 0,
                      "points_served": 0, "points_padded": 0,
                      "requests_done": 0, "peak_active_slots": 0,
                      "cache_hits": 0, "cache_misses": 0,
                      "cache_evictions": 0}

    def _sync_cache_stats(self) -> None:
        """Mirror the ``StencilCache`` counters into ``stats`` so one dict
        answers 'how is serving going' (the launcher and tests read it)."""
        if self.cache is not None:
            self.stats["cache_hits"] = self.cache.hits
            self.stats["cache_misses"] = self.cache.misses
            self.stats["cache_evictions"] = self.cache.evictions

    # ------------------------------------------------------------ programs
    def _pool_shape(self, in_dim: int) -> tuple:
        return (self.slots * self.slot_points, in_dim)

    def _program(self, solver_name: str, dtype, quant=None):
        """The compiled full-pool forward for (solver, dtype[, quant]) —
        built (and counted) once, then a pure executable: calling it can
        never recompile, and a shape drift would be a hard error rather
        than a silent recompile (AOT executables reject mismatched
        shapes).  A quantized program serves through a model whose quant
        hooks are enabled; the frozen params are jit constants, so the
        fake-quant folds at AOT-compile time — steady-state cost is one
        program run, identical to f32 serving, with ZERO extra
        recompiles.  A conditioned solver's program consumes net_dim-wide
        rows (points + coefficient slots) and is tagged ``c{K}`` in the
        key — the coefficient VALUES live in the input buffer, so the
        whole family shares the one program."""
        solver = self.registry.get(solver_name)
        tag = _quant_tag(quant)
        ctag = f"c{solver.n_coeffs}" if solver.coeff_spec is not None else ""
        key = (solver_name, np.dtype(dtype).name,
               *((tag,) if tag else ()), *((ctag,) if ctag else ()),
               self.slots, self.slot_points)
        exe = self._programs.get(key)
        if exe is None:
            params, noise = solver.params, solver.noise
            if np.dtype(dtype) != np.float32:
                # lower-precision serving: cast the frozen params once at
                # build time, not per step
                cast = lambda x: (x.astype(dtype)
                                  if jnp.issubdtype(x.dtype, jnp.floating)
                                  else x)
                params = jax.tree.map(cast, params)
                noise = (jax.tree.map(cast, noise)
                         if noise is not None else None)
            model = solver.model
            if tag:
                # request-level quantization: rebind the solver's model
                # with the quant hooks on (same problem, same params).
                # NOTE: a prepared tonn solver is already densified, so
                # phase_bits only bites solvers quantized at train/load
                # time; core/weight quantization applies here regardless.
                from repro.core import pinn as pinn_lib
                model = pinn_lib.TensorPinn(
                    dataclasses.replace(model.cfg, quant=quant),
                    problem=model.problem)
            fwd = jax.jit(lambda pts: model.u(params, pts, noise))
            spec = jax.ShapeDtypeStruct(self._pool_shape(solver.net_dim),
                                        np.dtype(dtype))
            exe = fwd.lower(spec).compile()
            self._programs[key] = exe
            self.stats["compiles"] += 1
        return exe

    def warmup(self, solver_name: str | None = None,
               dtype=np.float32, quant=None) -> None:
        """Build AND execute the (solver, dtype[, quant], slot-shape)
        program(s) on a pure-fill pool, so the first real request pays
        neither the XLA compile nor the first-dispatch setup.  ``None``
        warms every registered solver."""
        names = (self.registry.names() if solver_name is None
                 else (solver_name,))
        for name in names:
            exe = self._program(name, dtype, quant)
            width = self.registry.get(name).net_dim
            buf = np.broadcast_to(
                self._fill_point(name),
                self._pool_shape(width)).astype(np.dtype(dtype), copy=True)
            jax.block_until_ready(exe(jnp.asarray(buf)))

    def _fill_point(self, solver_name: str) -> np.ndarray:
        """A fixed in-domain point for pad rows and idle slots (any valid
        collocation point works — its outputs are discarded; it just must
        not produce NaN/inf that could poison reductions elsewhere)."""
        p = self._fill.get(solver_name)
        if p is None:
            problem = self.registry.get(solver_name).problem
            p = np.asarray(problem.sample_collocation(
                jax.random.PRNGKey(0), 1), np.float64)[0]
            self._fill[solver_name] = p
        return p

    # -------------------------------------------------------------- submit
    def submit(self, req: PointRequest) -> PointRequest:
        """Enqueue a request; cache hits are served immediately and only
        the misses ever occupy slots.  Returns the request (its ``out`` /
        ``done`` fields are updated in place as the engine steps)."""
        with self.trace.span("engine.submit"):
            return self._submit(req)

    def _submit(self, req: PointRequest) -> PointRequest:
        pts = np.asarray(req.points, np.float64)
        if pts.ndim != 2 or pts.shape[0] == 0:
            raise ValueError(f"points must be (n>0, in_dim), "
                             f"got {pts.shape}")
        solver = self.registry.get(req.solver)
        if pts.shape[1] != solver.in_dim:
            raise ValueError(f"solver {req.solver!r} takes in_dim="
                             f"{solver.in_dim} points, got {pts.shape}")
        # conditioned/unconditioned mismatch is a client error, caught at
        # submit before any state changes (both directions: a conditioned
        # solver silently evaluated at garbage slots, or coefficients
        # silently dropped, would be far worse than the exception)
        spec = solver.coeff_spec
        if spec is None:
            if req.coeffs is not None:
                raise ValueError(
                    f"solver {req.solver!r} is not coefficient-conditioned "
                    "but the request carries coeffs; drop them or query a "
                    "conditioned solver")
        else:
            if req.coeffs is None:
                raise ValueError(
                    f"solver {req.solver!r} is coefficient-conditioned on "
                    f"({', '.join(spec.names)}); pass PointRequest(coeffs="
                    f"[{', '.join(spec.names)}]) with values in the "
                    "trained ranges")
            coeffs = np.asarray(req.coeffs, np.float64).reshape(-1)
            spec.check_in_range(coeffs)   # arity + trained-range, or raises
            req.coeffs = coeffs
            # augment once at submit: everything downstream — cache keys,
            # slot packing, the net_dim-wide pool — sees plain rows
            pts = np.concatenate(
                [pts, np.broadcast_to(coeffs, (pts.shape[0], spec.n))],
                axis=1)
        req.points = pts
        req.t_submit = time.perf_counter()
        req.out = np.empty(pts.shape[0], np.float64)
        if self.cache is not None:
            with self.trace.span("cache.lookup"):
                keys = self.cache.keys_for(req.solver, req.dtype, pts,
                                           quant_tag=_quant_tag(req.quant))
                hit_idx, hit_vals, miss_idx = self.cache.lookup(keys)
            if len(hit_idx):
                req.out[hit_idx] = hit_vals
            req._miss_idx = miss_idx
            req._keys = keys
            self._sync_cache_stats()
        else:
            req._miss_idx = np.arange(pts.shape[0])
            req._keys = None
        if len(req._miss_idx) == 0:       # fully cached: done at submit
            req.done = True
            req.t_done = time.perf_counter()
            self.stats["requests_done"] += 1
            return req
        self.queue.append(req)
        return req

    # ---------------------------------------------------------- step logic
    def _admit(self) -> None:
        """Pack head-of-queue chunks into free slots (continuous
        admission): the head request may leave partially packed — its
        remaining points wait for the next step's free slots.  A request's
        first chunk stamps ``t_admit`` and adds its queue wait."""
        free = [s for s in range(self.slots) if self.active[s] is None]
        now = time.perf_counter()
        while free and self.queue:
            req = self.queue[0]
            if req._cursor == 0:
                req.t_admit = now
                self.trace.add("queue_wait_s", now - req.t_submit)
                self.trace.add("requests_admitted", 1)
            remaining = len(req._miss_idx) - req._cursor
            count = min(remaining, self.slot_points)
            self.active[free.pop()] = _Slot(req, req._cursor, count)
            req._cursor += count
            req._inflight += 1
            if req._cursor >= len(req._miss_idx):
                self.queue.popleft()

    def _pack(self) -> list:
        """Admit, group the occupied slots by (solver, dtype, quant) and
        fill each group's full-pool input.  Returns ``[(exe, slot_ids,
        buf)]``, one entry per program run of this step."""
        self._admit()
        groups: dict = {}
        for s, slot in enumerate(self.active):
            if slot is not None:
                groups.setdefault(
                    (slot.req.solver, np.dtype(slot.req.dtype).name,
                     _quant_tag(slot.req.quant)),
                    []).append(s)
        runs = []
        for (solver_name, dtype_name, _tag), slot_ids in groups.items():
            dtype = np.dtype(dtype_name)
            quant = self.active[slot_ids[0]].req.quant
            exe = self._program(solver_name, dtype, quant)
            width = self.registry.get(solver_name).net_dim
            # full-pool input: fill point everywhere, then overwrite the
            # group's slots with their chunks (pad-to-slot; conditioned
            # rows are already coefficient-augmented from submit, and the
            # fill point carries in-range sampled coefficients itself)
            buf = np.broadcast_to(
                self._fill_point(solver_name),
                (self.slots, self.slot_points, width)).astype(
                    dtype, copy=True)
            for s in slot_ids:
                slot = self.active[s]
                idx = slot.req._miss_idx[slot.offset:slot.offset
                                         + slot.count]
                buf[s, :slot.count] = slot.req.points[idx]
            runs.append((exe, slot_ids,
                         buf.reshape(self._pool_shape(width))))
        return runs

    def step(self) -> int:
        """One engine step: admit, evaluate every (solver, dtype) group's
        full-pool program once, scatter results, retire slots.  Returns
        the number of request points served this step (0, and no span,
        when nothing is queued)."""
        if not self.queue and all(s is None for s in self.active):
            return 0
        with self.trace.span("engine.step"):
            with self.trace.span("engine.pack"):
                runs = self._pack()
            self.stats["steps"] += 1
            self.stats["peak_active_slots"] = max(
                self.stats["peak_active_slots"],
                sum(len(slot_ids) for _, slot_ids, _ in runs))
            served = 0
            for exe, slot_ids, buf in runs:
                with self.trace.span("engine.launch"):
                    u_dev = exe(jnp.asarray(buf))
                with self.trace.span("engine.fetch"):
                    u = np.asarray(u_dev).reshape(self.slots,
                                                  self.slot_points)
                self.stats["program_runs"] += 1
                results = []              # (request, positions, values)
                finished = []             # requests whose last point is here
                for s in slot_ids:
                    slot = self.active[s]
                    req = slot.req
                    idx = req._miss_idx[slot.offset:slot.offset
                                        + slot.count]
                    vals = u[s, :slot.count]
                    req.out[idx] = vals
                    results.append((req, idx, vals))
                    served += slot.count
                    self.stats["points_padded"] += \
                        self.slot_points - slot.count
                    req._inflight -= 1
                    if req._inflight == 0 and \
                            req._cursor >= len(req._miss_idx):
                        finished.append(req)
                    self.active[s] = None     # slot recycles next step
                if self.cache is not None:
                    with self.trace.span("cache.insert"):
                        self.cache.insert(
                            [r._keys[i] for r, idx, _ in results
                             for i in idx],
                            np.concatenate([v for _, _, v in results]))
                # a request is done once its values are in the cache too
                now = time.perf_counter()
                for req in finished:
                    req.done, req.t_done = True, now
                self.stats["requests_done"] += len(finished)
                # idle slots of this group's program run are pure padding
                self.stats["points_padded"] += \
                    (self.slots - len(slot_ids)) * self.slot_points
            self.stats["points_served"] += served
            self._sync_cache_stats()
            return served

    def run(self, max_steps: int | None = None) -> int:
        """Drain the queue: step until nothing is queued or in flight.
        Returns total points served."""
        total = 0
        for _ in (range(max_steps) if max_steps is not None
                  else itertools.count()):
            if not self.queue and all(s is None for s in self.active):
                break
            total += self.step()
        return total

    # ----------------------------------------------------------- reporting
    def serving_stats(self) -> dict:
        """``stats`` plus the queue, the program keys, the cache's own
        stats, the host spans (``spans``: per name ``count``, ``seconds``,
        ``self_seconds``, ``max_s``) and the queue-wait counters."""
        self._sync_cache_stats()
        out = dict(self.stats)
        trace = self.trace.summary()
        out["spans"] = trace["spans"]
        out["queue_wait_s"] = trace["counters"].get("queue_wait_s", 0.0)
        out["requests_admitted"] = trace["counters"].get(
            "requests_admitted", 0)
        out["queued"] = len(self.queue)
        out["programs"] = sorted(
            "|".join(map(str, k)) for k in self._programs)
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out
