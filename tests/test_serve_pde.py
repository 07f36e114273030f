"""Serving runtime: bit-identity vs direct inference, mixed-problem slot
batching, pad masking, cache hit/eviction, slot recycling under churn,
compile-once (no recompiles across steps), and checkpoint-metadata loading
(DESIGN.md §Serving)."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import read_checkpoint_meta, save_checkpoint
from repro.core import pinn
from repro.serving import (PdeServingEngine, PointRequest, SolverRegistry,
                           StencilCache)


def _registry(modes=(("heat", "heat-10d", "tt"),)):
    reg = SolverRegistry()
    for i, (name, pde, mode) in enumerate(modes):
        cfg = pinn.PINNConfig(hidden=16, mode=mode, tt_rank=2, tt_L=3,
                              pde=pde)
        reg.register_fresh(name, cfg, seed=i)
    return reg


def _query(reg, name, n, seed=0):
    prob = reg.get(name).problem
    return np.asarray(prob.sample_collocation(jax.random.PRNGKey(seed), n),
                      np.float32)


def _direct(reg, name, pts):
    s = reg.get(name)
    return np.asarray(jax.jit(
        lambda p: s.model.u(s.params, p, s.noise))(jnp.asarray(pts)))


@pytest.mark.parametrize("mode", ["tt", "tonn", "dense"])
def test_served_u_bit_identical_to_direct_forward(mode):
    """The acceptance contract: engine output == direct TensorPinn forward
    bit-for-bit, despite pad-to-slot batching (pad-invariance of the
    row-wise contraction)."""
    reg = _registry([("s", "heat-10d", mode)])
    eng = PdeServingEngine(reg, slots=3, slot_points=32)
    pts = _query(reg, "s", 50, seed=7)   # spans 2 slots, 3rd stays idle
    req = eng.submit(PointRequest("s", pts))
    eng.run()
    assert req.done
    np.testing.assert_array_equal(req.out.astype(np.float32),
                                  _direct(reg, "s", pts))


def test_mixed_problem_batching_one_program_each():
    """Interleaved traffic for two different PDEs (different in_dim!) is
    served concurrently from one pool; exactly one program per solver."""
    reg = _registry([("heat", "heat-10d", "tt"), ("hjb", "hjb-20d", "tt")])
    eng = PdeServingEngine(reg, slots=4, slot_points=16)
    reqs = []
    for i in range(10):
        name = ("heat", "hjb")[i % 2]
        reqs.append(eng.submit(
            PointRequest(name, _query(reg, name, 5 + 7 * i, seed=i))))
    eng.run()
    assert all(r.done for r in reqs)
    for r in reqs:
        np.testing.assert_array_equal(r.out.astype(np.float32),
                                      _direct(reg, r.solver, r.points))
    assert eng.stats["compiles"] == 2
    assert set(eng.serving_stats()["programs"]) == {
        "heat|float32|4|16", "hjb|float32|4|16"}


def test_pad_slot_masking():
    """A request far smaller than a slot: pad rows must not leak into the
    output, and the output must keep request order."""
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=64)
    pts = _query(reg, "heat", 3, seed=3)
    req = eng.submit(PointRequest("heat", pts))
    served = eng.run()
    assert req.done and req.out.shape == (3,)
    assert served == 3                      # padding never counted as served
    np.testing.assert_array_equal(req.out.astype(np.float32),
                                  _direct(reg, "heat", pts))
    # the pool shape is fixed: 2*64 evaluated, 3 useful
    assert eng.stats["points_padded"] == 2 * 64 - 3


def test_request_larger_than_pool_spans_steps():
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=8)   # pool = 16 points
    pts = _query(reg, "heat", 50, seed=11)
    req = eng.submit(PointRequest("heat", pts))
    eng.run()
    assert req.done
    assert eng.stats["steps"] >= 4          # ceil(50/16) steps minimum
    np.testing.assert_array_equal(req.out.astype(np.float32),
                                  _direct(reg, "heat", pts))


def test_cache_hit_and_value_correctness():
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=32)
    pts = _query(reg, "heat", 20, seed=5)
    r1 = eng.submit(PointRequest("heat", pts))
    eng.run()
    runs = eng.stats["program_runs"]
    # identical resubmit: served at submit time, no program run, same bits
    r2 = eng.submit(PointRequest("heat", pts))
    assert r2.done                          # completed without stepping
    assert eng.stats["program_runs"] == runs
    np.testing.assert_array_equal(r1.out, r2.out)
    st = eng.cache.stats()
    assert st["hits"] == 20 and st["misses"] == 20
    # partial overlap: only the fresh points occupy slots
    pts2 = np.concatenate([pts[:10], _query(reg, "heat", 6, seed=6)])
    r3 = eng.submit(PointRequest("heat", pts2))
    eng.run()
    assert r3.done
    np.testing.assert_array_equal(r3.out.astype(np.float32),
                                  _direct(reg, "heat", pts2))
    assert eng.cache.stats()["hits"] == 30


def test_cache_lru_eviction():
    cache = StencilCache(capacity=8)
    keys = cache.keys_for("s", np.float32, np.arange(24.0).reshape(12, 2))
    cache.insert(keys[:8], np.arange(8.0))
    _, _, miss = cache.lookup(keys[:2])     # refresh 0,1 to MRU
    assert len(miss) == 0
    cache.insert(keys[8:], np.arange(8.0, 12.0))   # evict 4 LRU: keys 2..5
    assert len(cache) == 8 and cache.evictions == 4
    hit, vals, miss = cache.lookup(keys)
    assert sorted(miss.tolist()) == [2, 3, 4, 5]
    np.testing.assert_array_equal(sorted(hit.tolist()),
                                  [0, 1, 6, 7, 8, 9, 10, 11])


def test_cache_quantization_and_dtype_isolation():
    cache = StencilCache(capacity=16, quantum=1e-3)
    p = np.array([[0.5, 0.5]])
    cache.insert(cache.keys_for("s", np.float32, p), np.array([1.25]))
    # same cell → hit; different cell / dtype / solver → miss
    hit, vals, _ = cache.lookup(cache.keys_for("s", np.float32,
                                               p + 1e-5))
    assert len(hit) == 1 and vals[0] == 1.25
    for other in (cache.keys_for("s", np.float32, p + 1e-2),
                  cache.keys_for("s", np.float64, p),
                  cache.keys_for("t", np.float32, p)):
        _, _, miss = cache.lookup(other)
        assert len(miss) == 1


def test_slot_recycling_under_churn():
    """Far more requests than slots: every slot is reused many times and
    the pool never grows."""
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=16,
                           enable_cache=False)
    reqs = [eng.submit(PointRequest("heat", _query(reg, "heat",
                                                   1 + (i * 5) % 30,
                                                   seed=100 + i)))
            for i in range(25)]
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.stats["peak_active_slots"] <= 2
    assert eng.stats["steps"] >= len(reqs) // 2
    for r in reqs[::6]:
        np.testing.assert_array_equal(r.out.astype(np.float32),
                                      _direct(reg, "heat", r.points))


def test_compile_once_across_steps_and_request_mixes():
    """The compile-once contract: after the first step touches a (solver,
    dtype, slot-shape) triple, NO request size, queue depth, or resubmit
    pattern may compile again."""
    reg = _registry([("heat", "heat-10d", "tt"), ("hjb", "hjb-10d", "tonn")])
    eng = PdeServingEngine(reg, slots=3, slot_points=8)
    eng.warmup()
    assert eng.stats["compiles"] == 2
    for i in range(12):                      # wildly varying request sizes
        name = ("heat", "hjb")[i % 2]
        eng.submit(PointRequest(name, _query(reg, name, 1 + 13 * i,
                                             seed=i)))
        eng.step()
    eng.run()
    assert eng.stats["compiles"] == 2        # zero recompiles under churn
    assert eng.stats["steps"] > 1


def test_latency_timestamps():
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=16)
    req = eng.submit(PointRequest("heat", _query(reg, "heat", 10)))
    eng.run()
    assert req.t_done >= req.t_submit and req.latency_s >= 0


def test_registry_errors():
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    with pytest.raises(KeyError):
        eng.submit(PointRequest("nope", np.zeros((1, 11), np.float32)))
    with pytest.raises(ValueError):          # wrong in_dim
        eng.submit(PointRequest("heat", np.zeros((4, 3), np.float32)))
    with pytest.raises(ValueError):          # empty batch
        eng.submit(PointRequest("heat", np.zeros((0, 11), np.float32)))


# ------------------------------------------------------- checkpoint loading

def _save_solver_ckpt(tmp_path, cfg, seed=0, with_meta=True):
    model = pinn.TensorPinn(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    extra = ({"pinn": pinn.config_to_meta(cfg), "pde": model.problem.name,
              "seed": seed} if with_meta else {})
    save_checkpoint(tmp_path, 5, {"params": params,
                                  "zo": {"key": key}}, extra)
    return model, params


def test_load_checkpoint_by_name_no_config_side_channel(tmp_path):
    """Self-describing checkpoints: the registry reconstructs the arch and
    problem from meta.json alone; optimizer state on disk is ignored."""
    cfg = pinn.PINNConfig(hidden=16, mode="tonn", tt_rank=2, tt_L=3,
                          pde="heat-10d")
    model, params = _save_solver_ckpt(tmp_path, cfg, seed=3)
    reg = SolverRegistry()
    s = reg.load_checkpoint("heat", tmp_path)
    assert s.problem.name == "heat-10d" and s.model.cfg.mode == "tonn"
    assert s.step == 5
    pts = np.asarray(model.problem.sample_collocation(
        jax.random.PRNGKey(1), 9), np.float32)
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    req = eng.submit(PointRequest("heat", pts))
    eng.run()
    direct = np.asarray(jax.jit(
        lambda p: model.u(params, p))(jnp.asarray(pts)))
    np.testing.assert_array_equal(req.out.astype(np.float32), direct)


def test_load_noise_enabled_checkpoint_reconstructs_chip(tmp_path):
    """Noise-on solvers: the recorded seed regenerates the exact fixed
    fabrication noise of launch/train.py's chip."""
    from repro.core.photonic import NoiseModel
    cfg = pinn.PINNConfig(hidden=16, mode="onn", pde="heat-10d",
                          noise=NoiseModel(enabled=True))
    model, params = _save_solver_ckpt(tmp_path, cfg, seed=4)
    hw = model.sample_noise(jax.random.fold_in(jax.random.PRNGKey(4), 99))
    reg = SolverRegistry()
    reg.load_checkpoint("noisy", tmp_path)
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    pts = np.asarray(model.problem.sample_collocation(
        jax.random.PRNGKey(2), 6), np.float32)
    req = eng.submit(PointRequest("noisy", pts))
    eng.run()
    direct = np.asarray(jax.jit(
        lambda p: model.u(params, p, hw))(jnp.asarray(pts)))
    np.testing.assert_array_equal(req.out.astype(np.float32), direct)


def test_old_checkpoint_without_meta_needs_explicit_cfg(tmp_path):
    """Pre-metadata checkpoints stay loadable — with cfg= passed the old
    way; without it the registry fails with a pointed message."""
    cfg = pinn.PINNConfig(hidden=16, mode="tt", tt_rank=2, tt_L=3,
                          pde="hjb-10d")
    _save_solver_ckpt(tmp_path, cfg, with_meta=False)
    reg = SolverRegistry()
    with pytest.raises(ValueError, match="pinn"):
        reg.load_checkpoint("old", tmp_path)
    s = reg.load_checkpoint("old", tmp_path, cfg=cfg)
    assert s.problem.name == "hjb-10d"


def test_config_meta_roundtrip():
    from repro.core.photonic import NoiseModel
    cfg = pinn.PINNConfig(hidden=48, mode="tonn", tt_rank=2, tt_L=4,
                          pde="black-scholes-100d", deriv="fd_fast",
                          use_fused_kernel=True, fd_step=2e-2,
                          noise=NoiseModel(enabled=True, gamma_std=0.004))
    meta = pinn.config_to_meta(cfg)
    import json
    assert pinn.config_from_meta(json.loads(json.dumps(meta))) == cfg
    # forward compatibility: unknown keys from a newer writer are ignored
    meta["from_the_future"] = 1
    meta["noise"]["also_new"] = 2
    assert pinn.config_from_meta(meta) == cfg


def test_trainer_writes_solver_metadata(tmp_path):
    """launch/train.py checkpoints are self-describing end to end."""
    from repro.launch import train
    train.main(["--arch", "tensor-pinn", "--pde", "hjb-10d", "--reduced",
                "--steps", "2", "--batch", "8", "--zo-samples", "2",
                "--hidden", "16", "--log-every", "10",
                "--ckpt-dir", str(tmp_path)])
    meta = read_checkpoint_meta(tmp_path)
    assert meta["pde"] == "hjb-10d" and meta["seed"] == 0
    cfg = pinn.config_from_meta(meta["pinn"])
    assert cfg.pde == "hjb-10d" and cfg.hidden == 16
    reg = SolverRegistry()
    s = reg.load_checkpoint("hjb", tmp_path)
    assert s.step == 2


# ------------------------------------------- non-f32 / quantized serving

def _direct_cast(reg, name, pts, dtype):
    """The engine's own lower-precision contract: frozen params cast ONCE
    to ``dtype`` (exactly ``_program``'s build-time cast), then the
    ordinary forward on ``dtype`` points."""
    s = reg.get(name)
    cast = lambda x: (x.astype(dtype)
                      if jnp.issubdtype(x.dtype, jnp.floating) else x)
    params = jax.tree.map(cast, s.params)
    noise = jax.tree.map(cast, s.noise) if s.noise is not None else None
    return np.asarray(jax.jit(
        lambda p: s.model.u(params, p, noise))(
            jnp.asarray(pts).astype(dtype)))


@pytest.mark.parametrize("mode", ["tt", "tonn"])
def test_bf16_serving_parity(mode):
    """The non-f32 program path (build-time param cast): served bf16 output
    is bit-identical to the equivalent direct bf16 forward (pad-invariance
    holds in any dtype), and within the bf16 accuracy floor of the f32
    values — 8-bit mantissa ⇒ ~4e-3 relative per rounding, amplified
    through the 3-layer sine chain; 5e-2 relative is the same floor
    tests/test_kernels.py documents for bf16 kernel parity."""
    reg = _registry([("s", "heat-10d", mode)])
    eng = PdeServingEngine(reg, slots=2, slot_points=32, enable_cache=False)
    pts = _query(reg, "s", 40, seed=9)
    req = eng.submit(PointRequest("s", pts, dtype=jnp.bfloat16))
    eng.run()
    assert req.done
    direct = _direct_cast(reg, "s", pts, jnp.bfloat16)
    np.testing.assert_array_equal(
        req.out.astype(jnp.bfloat16), direct)          # bit-identity
    f32 = _direct(reg, "s", pts)
    scale = np.maximum(np.abs(f32), 1.0)
    assert np.max(np.abs(req.out - f32) / scale) < 5e-2   # documented floor
    assert "s|bfloat16|2|32" in eng.serving_stats()["programs"]


def _quant_model_direct(reg, name, pts, qcfg):
    """Direct forward through the solver's model with the quant hooks on —
    exactly what ``_program`` builds for a quantized request."""
    import dataclasses
    s = reg.get(name)
    qmodel = pinn.TensorPinn(dataclasses.replace(s.model.cfg, quant=qcfg),
                             problem=s.model.problem)
    return np.asarray(jax.jit(
        lambda p: qmodel.u(s.params, p, s.noise))(jnp.asarray(pts)))


@pytest.mark.parametrize("qdtype", ["int8", "fp8_e4m3"])
def test_quantized_serving_parity_and_program_isolation(qdtype):
    """Quantized programs: one extra compile per quant config, outputs
    bit-identical to the fake-quant direct forward, within one accuracy
    notch of f32 (block-scaled 8-bit weights: ≤5e-2 relative on u — the
    notch DESIGN.md §Quantization documents), and the f32 program's
    outputs are untouched by quantized traffic."""
    from repro.kernels.quant import QuantConfig
    qcfg = QuantConfig(enabled=True, dtype=qdtype, block=32)
    reg = _registry([("s", "heat-10d", "tt")])
    eng = PdeServingEngine(reg, slots=2, slot_points=32, enable_cache=False)
    pts = _query(reg, "s", 40, seed=13)
    r_f32 = eng.submit(PointRequest("s", pts))
    r_q = eng.submit(PointRequest("s", pts, quant=qcfg))
    eng.run()
    assert r_f32.done and r_q.done
    # f32 arm: still bit-identical to the plain direct forward
    np.testing.assert_array_equal(r_f32.out.astype(np.float32),
                                  _direct(reg, "s", pts))
    # quant arm: bit-identical to the fake-quant forward, close to f32
    np.testing.assert_array_equal(r_q.out.astype(np.float32),
                                  _quant_model_direct(reg, "s", pts, qcfg))
    scale = np.maximum(np.abs(r_f32.out), 1.0)
    assert np.max(np.abs(r_q.out - r_f32.out) / scale) < 5e-2
    assert (r_q.out != r_f32.out).any()      # quantization actually bites
    # exactly two programs, tagged apart; resubmits never recompile
    assert eng.stats["compiles"] == 2
    progs = set(eng.serving_stats()["programs"])
    assert progs == {"s|float32|2|32", f"s|float32|{qcfg.tag()}|2|32"}
    for _ in range(3):
        eng.submit(PointRequest("s", _query(reg, "s", 17, seed=21),
                                quant=qcfg))
        eng.run()
    assert eng.stats["compiles"] == 2        # zero steady-state recompiles


def test_cache_isolates_quantized_results():
    """An int8-served value must never answer an f32 query (and vice
    versa): the quant tag is part of the cache key."""
    from repro.kernels.quant import QuantConfig
    qcfg = QuantConfig(enabled=True, dtype="int8", block=32)
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=32)
    pts = _query(reg, "heat", 12, seed=2)
    eng.submit(PointRequest("heat", pts))
    eng.run()
    hits_before = eng.cache.stats()["hits"]
    rq = eng.submit(PointRequest("heat", pts, quant=qcfg))   # same points
    eng.run()
    assert rq.done
    assert eng.cache.stats()["hits"] == hits_before          # no cross-hits
    # the quantized resubmit DOES hit its own entries
    rq2 = eng.submit(PointRequest("heat", pts, quant=qcfg))
    assert rq2.done and eng.cache.stats()["hits"] == hits_before + 12
    np.testing.assert_array_equal(rq.out, rq2.out)


def test_cache_counters_surface_in_engine_stats():
    """StencilCache hit/miss/eviction counters are mirrored into
    ``engine.stats`` (the launcher's summary line reads them there)."""
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=32)
    assert eng.stats["cache_hits"] == 0 and eng.stats["cache_misses"] == 0
    pts = _query(reg, "heat", 15, seed=4)
    eng.submit(PointRequest("heat", pts))
    eng.run()
    eng.submit(PointRequest("heat", pts))    # full cache hit at submit
    assert eng.stats["cache_hits"] == 15
    assert eng.stats["cache_misses"] == 15
    st = eng.serving_stats()
    assert st["cache_hits"] == st["cache"]["hits"] == 15
    assert st["cache_evictions"] == eng.cache.evictions == 0


def test_spans_reconcile_with_engine_stats():
    """After a mixed run (two solvers, a fully cached, a partly cached and
    a pool-spanning request), the host spans agree with the counters:
    one ``engine.step`` per step, one ``engine.launch``/``engine.fetch``
    per program run, children inside their parent."""
    reg = _registry([("heat", "heat-10d", "tt"), ("hjb", "hjb-20d", "tt")])
    eng = PdeServingEngine(reg, slots=3, slot_points=16)
    heat = _query(reg, "heat", 12, seed=1)
    reqs = [eng.submit(PointRequest("heat", heat)),
            eng.submit(PointRequest("hjb", _query(reg, "hjb", 70, seed=2)))]
    eng.run()
    reqs.append(eng.submit(PointRequest("heat", heat)))       # all cached
    reqs.append(eng.submit(PointRequest(
        "heat", np.concatenate([heat[:4], _query(reg, "heat", 5, seed=3)]))))
    eng.run()
    assert all(r.done for r in reqs)
    st = eng.serving_stats()
    json.dumps(st)                           # the launcher prints it whole
    spans = st["spans"]
    assert spans["engine.step"]["count"] == st["steps"] > 1
    assert spans["engine.launch"]["count"] == st["program_runs"]
    assert spans["engine.fetch"]["count"] == st["program_runs"]
    assert spans["cache.insert"]["count"] == st["program_runs"]
    assert spans["engine.pack"]["count"] == st["steps"]
    assert spans["engine.submit"]["count"] == 4
    assert spans["cache.lookup"]["count"] == 4
    children = sum(spans[n]["seconds"] for n in (
        "engine.pack", "engine.launch", "engine.fetch", "cache.insert"))
    assert children <= spans["engine.step"]["seconds"]
    assert spans["engine.step"]["self_seconds"] == pytest.approx(
        spans["engine.step"]["seconds"] - children, abs=1e-9)
    for span in spans.values():
        assert 0 <= span["self_seconds"] <= span["seconds"]
        assert span["max_s"] <= span["seconds"]
    assert st["requests_admitted"] == 3      # all but the cached one
    assert st["queue_wait_s"] >= 0
    for r in (reqs[0], reqs[1], reqs[3]):
        assert r.t_submit <= r.t_admit <= r.t_done


def test_fully_cached_request_adds_no_queue_wait():
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=16)
    pts = _query(reg, "heat", 10, seed=8)
    eng.submit(PointRequest("heat", pts))
    eng.run()
    before = eng.serving_stats()
    cached = eng.submit(PointRequest("heat", pts))
    assert cached.done and cached.t_admit is None
    assert eng.step() == 0                   # nothing queued: no step span
    after = eng.serving_stats()
    assert after["requests_admitted"] == before["requests_admitted"] == 1
    assert after["queue_wait_s"] == before["queue_wait_s"]
    assert after["spans"]["engine.step"] == before["spans"]["engine.step"]


def test_request_done_after_its_values_reach_the_cache():
    """``t_done`` is stamped after the step's cache insert, so a request's
    latency covers the insert of its own points."""
    import time

    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=16)
    inserted = []                            # perf_counter at each insert's end
    insert = eng.cache.insert

    def timed_insert(keys, values):
        insert(keys, values)
        inserted.append(time.perf_counter())

    eng.cache.insert = timed_insert
    reqs = [eng.submit(PointRequest("heat", _query(reg, "heat", n, seed=n)))
            for n in (5, 9, 40)]
    eng.run()
    assert all(r.done for r in reqs) and inserted
    for r in reqs:
        assert any(t <= r.t_done for t in inserted)
        last = max(t for t in inserted if t <= r.t_done)
        assert r.t_done - last < 0.05


# ---------------------------------------- coefficient-conditioned serving

def _cond_registry(name="fam", pde="heat-10d-kappa", mode="tt"):
    reg = SolverRegistry()
    cfg = pinn.PINNConfig(hidden=16, mode=mode, tt_rank=2, tt_L=3, pde=pde)
    reg.register_fresh(name, cfg, seed=0)
    return reg


def _phys_query(reg, name, n, seed=0):
    """Physical points only — what a conditioned client sends (the engine
    appends the request's coefficient vector itself)."""
    s = reg.get(name)
    return np.asarray(s.problem.sample_collocation(
        jax.random.PRNGKey(seed), n), np.float32)[:, :s.in_dim]


def test_conditioned_solver_rejects_missing_coeffs():
    """A conditioned checkpoint queried without coefficients is a hard
    submit-time error naming the expected coefficients — NOT a silent
    evaluation at whatever the padding slots happen to hold."""
    reg = _cond_registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    with pytest.raises(ValueError, match="kappa"):
        eng.submit(PointRequest("fam", _phys_query(reg, "fam", 4)))
    # nothing was enqueued or compiled by the failed submit
    assert len(eng.queue) == 0 and eng.stats["compiles"] == 0


def test_unconditioned_solver_rejects_coeffs():
    """The reverse direction: coefficients on an unconditioned solver are
    rejected, never silently dropped."""
    reg = _registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    with pytest.raises(ValueError, match="not coefficient-conditioned"):
        eng.submit(PointRequest("heat", _query(reg, "heat", 4),
                                coeffs=[1.0]))
    assert len(eng.queue) == 0


def test_conditioned_out_of_range_and_arity_rejected():
    """Coefficient values outside the TRAINED range (the model would be
    extrapolating) and wrong-arity vectors both fail at submit."""
    reg = _cond_registry()                   # kappa trained on [0.5, 2.0]
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    pts = _phys_query(reg, "fam", 4)
    with pytest.raises(ValueError, match="outside trained range"):
        eng.submit(PointRequest("fam", pts, coeffs=[5.0]))
    with pytest.raises(ValueError, match="outside trained range"):
        eng.submit(PointRequest("fam", pts, coeffs=[0.499]))
    with pytest.raises(ValueError, match="expected 1 coefficient"):
        eng.submit(PointRequest("fam", pts, coeffs=[1.0, 2.0]))
    # boundary values are inside the family
    r = eng.submit(PointRequest("fam", pts, coeffs=[0.5]))
    eng.run()
    assert r.done


def test_conditioned_family_one_program_bit_identical():
    """The family contract: ONE AOT program (tagged c{K}) serves every
    coefficient instance, each bit-identical to the direct net_dim-wide
    forward, with zero steady-state recompiles."""
    reg = _cond_registry()
    s = reg.get("fam")
    eng = PdeServingEngine(reg, slots=2, slot_points=16, enable_cache=False)
    pts = _phys_query(reg, "fam", 20, seed=3)
    reqs = [eng.submit(PointRequest("fam", pts, coeffs=[k]))
            for k in (0.6, 1.0, 1.9)]
    eng.run()
    assert all(r.done for r in reqs)
    for k, r in zip((0.6, 1.0, 1.9), reqs):
        aug = np.concatenate(
            [pts, np.full((len(pts), 1), k, np.float32)], axis=1)
        np.testing.assert_array_equal(r.out.astype(np.float32),
                                      _direct(reg, "fam", aug))
    # one program for the whole family, coefficient values never in the key
    assert eng.stats["compiles"] == 1
    assert eng.serving_stats()["programs"] == ["fam|float32|c1|2|16"]
    # different coefficients give different fields (conditioning bites)
    assert (reqs[0].out != reqs[2].out).any()
    for k in (0.55, 0.77, 1.23):             # fresh instances: no recompile
        eng.submit(PointRequest("fam", pts, coeffs=[k]))
    eng.run()
    assert eng.stats["compiles"] == 1


def test_cache_isolates_coefficient_instances():
    """Same physical points under different coefficients must never
    cross-hit: the coefficient slots are part of the cached row."""
    reg = _cond_registry()
    eng = PdeServingEngine(reg, slots=2, slot_points=16)
    pts = _phys_query(reg, "fam", 10, seed=5)
    eng.submit(PointRequest("fam", pts, coeffs=[0.7]))
    eng.run()
    hits = eng.cache.stats()["hits"]
    r2 = eng.submit(PointRequest("fam", pts, coeffs=[1.7]))  # same points!
    eng.run()
    assert r2.done and eng.cache.stats()["hits"] == hits
    # exact (points, coeffs) resubmit: full hit at submit, same bits
    r3 = eng.submit(PointRequest("fam", pts, coeffs=[1.7]))
    assert r3.done and eng.cache.stats()["hits"] == hits + 10
    np.testing.assert_array_equal(r2.out, r3.out)


def test_conditioned_checkpoint_roundtrip_restores_trained_ranges(tmp_path):
    """A conditioned checkpoint is self-describing: the registry restores
    the TRAINED coefficient ranges (here --coeff-range-style overridden,
    narrower than the registry default) and enforces them at serve time."""
    from repro import pde as pde_lib
    cfg = pinn.PINNConfig(hidden=16, mode="tt", tt_rank=2, tt_L=3,
                          pde="heat-10d-kappa")
    problem = pde_lib.get_problem("heat-10d-kappa")
    problem.coeff_spec = problem.coeff_spec.with_ranges(
        {"kappa": (0.8, 1.2)})              # narrower than default [0.5, 2]
    model = pinn.TensorPinn(cfg, problem=problem)
    params = model.init(jax.random.PRNGKey(0))
    save_checkpoint(tmp_path, 7, {"params": params, "zo": {}},
                    {"pinn": pinn.config_to_meta(cfg), "pde": problem.name,
                     "seed": 0, "coeff_spec": problem.coeff_spec.to_meta()})
    reg = SolverRegistry()
    s = reg.load_checkpoint("fam", tmp_path)
    assert s.coeff_spec.names == ("kappa",)
    assert s.coeff_spec.lo == (0.8,) and s.coeff_spec.hi == (1.2,)
    assert s.net_dim == s.in_dim + 1
    eng = PdeServingEngine(reg, slots=2, slot_points=8)
    pts = _phys_query(reg, "fam", 5, seed=1)
    with pytest.raises(ValueError, match="outside trained range"):
        # in the registry default range but outside the trained one
        eng.submit(PointRequest("fam", pts, coeffs=[1.5]))
    req = eng.submit(PointRequest("fam", pts, coeffs=[1.1]))
    eng.run()
    aug = np.concatenate([pts, np.full((5, 1), 1.1, np.float32)], axis=1)
    np.testing.assert_array_equal(req.out.astype(np.float32),
                                  _direct(reg, "fam", aug))


def test_lm_engine_queue_is_deque():
    """The O(n) list.pop(0) admission regression guard for BOTH engines."""
    from collections import deque
    from repro.launch.serve import ServingEngine
    assert ServingEngine.__init__.__defaults__  # importable, no model init
    reg = _registry()
    eng = PdeServingEngine(reg, slots=1, slot_points=4)
    assert isinstance(eng.queue, deque)
    import inspect
    src = inspect.getsource(ServingEngine)
    assert "popleft" in src and "queue.pop(0)" not in src
