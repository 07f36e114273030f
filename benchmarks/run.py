"""Benchmark driver: one section per paper table / deliverable.

Prints ``name,us_per_call,derived`` CSV rows:
  * table2/*      — photonic cost model vs the paper's Table 2 numbers
  * table1/*      — CI-scale Table-1 reproduction (val-MSE ordering)
  * pde_suite/*   — multi-PDE workload suite (fused/sequential parity +
                    short ZO training per registered problem)
  * kernels/*     — tt_contract + flash_attention vs refs (CPU wall time;
                    derived = max |err| vs oracle)
  * photonic_mesh/* — batched MZI-mesh engine: stacked phase-domain ZO
                    step vs the pre-PR vmap-fallback paths + mesh-apply
                    gather-vs-scan micro (BENCH_photonic_mesh.json)
  * distributed_zo/* — sharded SPSA sweep: per-layout step time + measured
                    bytes-on-wire vs the O(N)-scalar bound (needs a
                    multi-device process; the standalone script forces 8)
  * serve_pde/*   — slot-batched PDE inference runtime: p50/p99 request
                    latency + points/sec at 1k/10k concurrent points,
                    engine vs naive per-request-jit (BENCH_serve_pde.json)
  * quantized/*   — block-scaled int8/fp8 TT cores + 8-bit DAC phases vs
                    f32: step time, weight memory, final residual per
                    (pde, mode) cell (BENCH_quantized.json)
  * coeff_family/* — one coefficient-conditioned checkpoint vs dedicated
                    per-coefficient checkpoints: closed-form val MSE per
                    held-out coefficient (BENCH_coeff_family.json)
  * residual_perf/* — spectral vs fd residual estimator: inferences per
                    loss evaluation, matched-MSE check and jitted ZO-step
                    wall clock (BENCH_residual_perf.json)
  * ns_data/*     — ns-2d three-term composite loss: full vs data-ablated
                    ZO training, spectral-path and legacy loss parity
                    checks (BENCH_ns_data.json)
  * roofline/*    — aggregated dry-run roofline terms (derived = roofline
                    fraction; run launch/dryrun.py first to populate)
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, n=5):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def bench_kernels(rows):
    from repro.core import tt
    from repro.kernels import ops, ref

    spec = tt.PAPER_TONN_SPEC
    cores = tt.tt_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (4200, 1024))
    y_ref = ref.tt_contract_ref(x, cores, spec)
    f_ref = jax.jit(lambda: ref.tt_contract_ref(x, cores, spec))
    us_ref = _time(f_ref)
    y_k = ops.tt_linear(x, cores, spec, mode="interpret")
    err = float(jnp.max(jnp.abs(y_k - y_ref)))
    rows.append({"name": "kernels/tt_contract_ref_1024(batch=4200)",
                 "us_per_call": round(us_ref, 1), "derived": f"err={err:.1e}"})

    B, H, KH, S, D = 1, 8, 2, 1024, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, KH, S, D))
    v = jax.random.normal(ks[2], (B, KH, S, D))
    from repro.models.flash import flash_attention_hlo
    f_fa = jax.jit(lambda: flash_attention_hlo(q, k, v, True, 0, 256, 256))
    us = _time(f_fa)
    err = float(jnp.max(jnp.abs(f_fa() - ref.attention_ref(q, k, v))))
    rows.append({"name": "kernels/flash_attention_hlo(1x8x1024x64)",
                 "us_per_call": round(us, 1), "derived": f"err={err:.1e}"})


def bench_zo_step(rows):
    """Paper's training loop: one full BP-free step (11 loss evals × 42
    FD inferences × batch 100), fused vs the seed sequential path."""
    from benchmarks import zo_step
    result = zo_step.run(hidden=1024, repeats=3, modes=("tonn", "tt"))
    rows += zo_step.summarize(result)


def bench_photonic_mesh(rows):
    """Phase-domain (tonn/onn, noise on) ZO step through the batched mesh
    engine vs the pre-PR vmap-fallback paths, plus mesh-apply micro."""
    from benchmarks import photonic_mesh
    rows += photonic_mesh.summarize(photonic_mesh.run(repeats=2))


def bench_distributed_zo(rows):
    """Distributed ZO over the forced-host mesh: per-layout step time,
    bytes-on-wire vs the O(N)-scalar bound, per-PDE gradient identity.
    Skipped unless the process already has >1 device (the XLA device count
    locks on first jax use; run benchmarks/distributed_zo.py standalone
    for the full sweep — it forces 8 host devices itself)."""
    if len(jax.devices()) < 2:
        rows.append({"name": "distributed_zo/skipped",
                     "derived": "single-device process; run "
                                "benchmarks/distributed_zo.py standalone"})
        return
    from benchmarks import distributed_zo
    rows += distributed_zo.summarize(
        distributed_zo.run(hidden=64, batch=32, repeats=2))


def bench_serve_pde(rows):
    """Slot-batched serving runtime vs naive per-request jit at 1k/10k
    concurrent query points (mixed heat-tt / hjb-tonn traffic)."""
    from benchmarks import serve_pde
    rows += serve_pde.summarize(serve_pde.run())


def bench_quantized(rows):
    """Quantization sweep at a reduced budget (tt-only, one PDE each —
    benchmarks/quantized.py standalone runs the full bits×mode×pde grid
    with the training arms)."""
    from benchmarks import quantized
    rows += quantized.summarize(
        quantized.run(modes=("tt",), epochs=20))


def bench_residual_perf(rows):
    """Spectral vs fd estimator at a reduced budget (heat only —
    benchmarks/residual_perf.py standalone runs both workloads with the
    off-path bit-identity and MSE-ratio gate checks)."""
    from benchmarks import residual_perf
    rows += residual_perf.summarize(
        residual_perf.run(pdes=("heat-10d",), epochs=150, repeats=3))


def bench_ns_data(rows):
    """ns-2d composite-loss training at a reduced budget (one seed, short
    arms — benchmarks/ns_data.py standalone runs the full gated budget
    with the val-MSE floor, ablation, spectral-path and legacy-parity
    checks)."""
    from benchmarks import ns_data
    rows += ns_data.summarize(ns_data.run(epochs=150))


def bench_coeff_family(rows):
    """Conditioned-family comparison at a reduced budget (hjb only —
    benchmarks/coeff_family.py standalone runs all three families with
    the off-path and serving gate checks)."""
    from benchmarks import coeff_family
    rows += coeff_family.summarize(
        coeff_family.run(families=("hjb",)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--table1-epochs", type=int, default=300)
    ap.add_argument("--skip-table1", action="store_true")
    ap.add_argument("--skip-pde-suite", action="store_true")
    ap.add_argument("--skip-zo-step", action="store_true",
                    help="skip the paper-scale fused-vs-naive ZO benchmark "
                         "(~2-4 min on a 2-core box)")
    ap.add_argument("--skip-photonic-mesh", action="store_true",
                    help="skip the batched-mesh-engine phase-domain ZO "
                         "benchmark (~1-2 min on a 2-core box)")
    ap.add_argument("--skip-distributed-zo", action="store_true",
                    help="skip the sharded-SPSA layout sweep (multi-device "
                         "processes only; several shard_map compiles)")
    ap.add_argument("--skip-serve-pde", action="store_true",
                    help="skip the slot-batched serving runtime benchmark "
                         "(~30s; the naive arm compiles per request)")
    ap.add_argument("--skip-quantized", action="store_true",
                    help="skip the int8/fp8 quantization sweep (~1 min at "
                         "the reduced tt-only budget)")
    ap.add_argument("--skip-coeff-family", action="store_true",
                    help="skip the conditioned-family comparison (~1 min "
                         "at the reduced hjb-only budget)")
    ap.add_argument("--skip-residual-perf", action="store_true",
                    help="skip the spectral-vs-fd estimator comparison "
                         "(~2 min at the reduced heat-only budget)")
    ap.add_argument("--skip-ns-data", action="store_true",
                    help="skip the ns-2d composite-loss benchmark (~1 min "
                         "at the reduced single-seed budget)")
    args, _ = ap.parse_known_args()

    rows: list = []
    from benchmarks import table2_cost
    rows += table2_cost.run()
    bench_kernels(rows)
    if not args.skip_zo_step:
        bench_zo_step(rows)
    if not args.skip_photonic_mesh:
        bench_photonic_mesh(rows)
    if not args.skip_distributed_zo:
        bench_distributed_zo(rows)
    if not args.skip_serve_pde:
        bench_serve_pde(rows)
    if not args.skip_quantized:
        bench_quantized(rows)
    if not args.skip_coeff_family:
        bench_coeff_family(rows)
    if not args.skip_residual_perf:
        bench_residual_perf(rows)
    if not args.skip_ns_data:
        bench_ns_data(rows)
    if not args.skip_table1:
        from benchmarks import table1_hjb
        rows += table1_hjb.run(hidden=64, epochs=args.table1_epochs)
    if not args.skip_pde_suite:
        from benchmarks import pde_suite
        rows += pde_suite.summarize(pde_suite.run(ci=True))
    from benchmarks import roofline
    rows += roofline.summarize()

    print("name,us_per_call,derived")
    for r in rows:
        name = r.pop("name")
        us = r.pop("us_per_call", "")
        derived = r.pop("derived", json.dumps(r, default=str))
        print(f"{name},{us},{json.dumps(derived, default=str) if not isinstance(derived, str) else derived}")


if __name__ == "__main__":
    main()
