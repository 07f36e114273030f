"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

At 1000+ nodes the cross-pod links are the scarcest resource; instead of
pure DP over ``pod`` (an all-reduce of every gradient across pods), the pod
axis can host pipeline STAGES: each pod keeps 1/P of the layer stack, and
only (microbatch × d_model) activations cross the pod boundary — orders of
magnitude fewer inter-pod bytes for deep models.

Implementation: ``shard_map`` over the pipeline axis; the classic
(num_microbatches + num_stages − 1)-tick schedule as a ``lax.scan`` whose
carry is each stage's in-flight activation; ``jax.lax.ppermute`` moves
activations stage→stage+1 each tick.  Losses are computed on the last stage
and psum'd.  The schedule is the standard GPipe fill/drain; bubble fraction
(P−1)/(M+P−1) is reported by ``bubble_fraction``.

This module is exercised by ``tests/test_pipeline.py`` on an 8-device host
mesh; the production dry-run keeps ``pod`` as a DP axis by default
(``launch/dryrun.py``) — switching is a config flag, and the §Perf log
discusses when PP wins.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_forward(mesh: Mesh, stage_fn: Callable, stage_params,
                     x: jax.Array, num_microbatches: int,
                     axis: str = "pod") -> jax.Array:
    """Run ``stage_fn(params, h) -> h`` as a P-stage pipeline.

    stage_params: pytree whose leaves have a leading stage axis sharded over
    ``axis``.  x: (B, ...) global batch, B % num_microbatches == 0; batch is
    REPLICATED across the pipeline axis (each stage sees every microbatch in
    turn).  Returns the final stage's outputs for all microbatches.
    """
    num_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % num_microbatches == 0
    mb = B // num_microbatches
    T = num_microbatches + num_stages - 1

    def per_stage(params, xs):
        stage = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params)   # my stage's slice
        mbs = xs.reshape(num_microbatches, mb, *xs.shape[1:])
        out0 = jnp.zeros_like(stage_fn(p, mbs[0]))

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if still filling)
            inject = mbs[jnp.clip(t, 0, num_microbatches - 1)]
            h_in = jnp.where(stage == 0, inject, inflight)
            h_out = stage_fn(p, h_in)
            # was this tick's work real for this stage?
            mb_idx = t - stage
            valid = (mb_idx >= 0) & (mb_idx < num_microbatches)
            # last stage records its finished microbatch
            outputs = jax.lax.cond(
                valid & (stage == num_stages - 1),
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, h_out[None], jnp.clip(mb_idx, 0, num_microbatches - 1),
                    axis=0),
                lambda o: o, outputs)
            # shift activations forward one stage
            nxt = jax.lax.ppermute(
                h_out, axis, [(i, (i + 1) % num_stages)
                              for i in range(num_stages)])
            return (nxt, outputs), None

        outputs0 = jnp.zeros((num_microbatches,) + out0.shape, out0.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (out0, outputs0),
                                       jnp.arange(T))
        # broadcast final outputs from the last stage: only it holds nonzero
        # results, so a psum over the pipeline axis is a one-to-all broadcast
        outputs = jnp.where(stage == num_stages - 1, outputs, 0.0)
        outputs = jax.lax.psum(outputs, axis)
        return outputs.reshape(B, *out0.shape[1:])

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x)
