"""Deterministic, shardable data pipeline.

Two sources:
  * synthetic LM token streams — a counter-based PRNG keyed by
    (seed, step, shard) so every data-parallel worker draws a disjoint,
    *reproducible* slice with no cross-host coordination.  Restart-safe:
    resuming from step k regenerates exactly the batches ≥ k (this is what
    makes checkpoint/restart bit-exact end to end).
  * PDE collocation sampler for the PINN experiments (uniform over the
    domain, fresh each step, same counter-based determinism), plus the
    loss-term channel (``pde_term_batch_iterator``) streaming boundary /
    data batches for the composite-loss engine on disjoint shards of the
    same key space.

Synthetic tokens follow a Zipf-ish distribution so MoE routing and the CE
softmax see realistic skew rather than uniform noise.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pinn as pinn_lib


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    zipf_alpha: float = 1.1


def _step_key(seed: int, step: int, shard: int = 0) -> jax.Array:
    key = jax.random.PRNGKey(seed)
    key = jax.random.fold_in(key, step)
    return jax.random.fold_in(key, shard)


def synthetic_lm_batch(cfg: DataConfig, step: int, shard: int = 0,
                       num_shards: int = 1) -> dict:
    """One (possibly per-shard) LM batch: tokens + next-token labels."""
    assert cfg.global_batch % num_shards == 0
    b = cfg.global_batch // num_shards
    key = _step_key(cfg.seed, step, shard)
    # Zipf via inverse-CDF on uniform samples (cheap, jit-able)
    u = jax.random.uniform(key, (b, cfg.seq_len + 1), minval=1e-6, maxval=1.0)
    ranks = jnp.floor(cfg.vocab_size * u ** cfg.zipf_alpha).astype(jnp.int32)
    ranks = jnp.clip(ranks, 0, cfg.vocab_size - 1)
    return {"tokens": ranks[:, :-1], "labels": ranks[:, 1:]}


def lm_batch_iterator(cfg: DataConfig, start_step: int = 0,
                      shard: int = 0, num_shards: int = 1) -> Iterator[dict]:
    step = start_step
    while True:
        yield synthetic_lm_batch(cfg, step, shard, num_shards)
        step += 1


# A block's batches stay in device memory until the iterator has yielded
# them, so the block is kept to a few MiB: a large batch gets a short block
# (one batch a call from 2 MiB a batch on) and the memory stays the batch's.
MAX_BLOCK_STEPS = 64
BLOCK_BYTES = 4 << 20


def block_steps(batch_bytes: int) -> int:
    """K: the largest power of two ≤ ``MAX_BLOCK_STEPS`` whose K batches of
    ``batch_bytes`` fit in ``BLOCK_BYTES``; at least 1."""
    k = MAX_BLOCK_STEPS
    while k > 1 and k * batch_bytes > BLOCK_BYTES:
        k //= 2
    return k


class CounterStream:
    """Counter-keyed batches, drawn K steps at a time: step s yields
    ``sample(_step_key(seed, s, shard))``, from ``start_step`` on.

    A block of K steps takes three calls: a jitted one derives the K keys
    and advances the step counter it keeps on the device (no transfer), the
    sampler runs once over all K keys (``jax.vmap``), and a jitted one
    splits the result into the K batches the iterator yields in turn.  The
    sampler runs op by op, as the per-step form runs it, and not under
    ``jit``: in one program XLA:CPU fuses its multiply-adds into FMAs,
    which would move the conditioned families' coefficients and ns-2d's
    observations by a rounding, and the stream stays bit-identical to the
    per-step form.
    K follows the bytes of one batch (``block_steps``).  Blocks start at
    ``start_step``, and a batch depends on its key alone, so a stream
    resumed at step k replays the batches ≥ k exactly.  A sampler whose
    batch holds no arrays (a problem with no non-collocation terms) is
    never called: the stream yields its empty structure and draws nothing.

    ``device_calls`` counts the blocks drawn, ``batches`` the batches
    yielded.
    """

    def __init__(self, sample, seed: int, start_step: int = 0,
                 shard: int = 0):
        key_shape = jax.eval_shape(jax.random.PRNGKey, 0)
        leaves, self._treedef = jax.tree.flatten(
            jax.eval_shape(sample, key_shape))
        self.block = block_steps(sum(x.size * x.dtype.itemsize
                                     for x in leaves))
        self.device_calls = 0
        self.batches = 0
        self._ready = collections.deque()
        self._sample = sample
        if leaves:
            self._keys = jax.jit(partial(_block_keys, k=self.block,
                                         shard=shard))
            self._unstack = jax.jit(partial(_unstack, k=self.block))
            self._base = jax.random.PRNGKey(seed)
            self._step = jnp.asarray(start_step, jnp.int32)

    def __iter__(self):
        return self

    def __next__(self):
        self.batches += 1
        if self._treedef.num_leaves == 0:
            return jax.tree.unflatten(self._treedef, [])
        if not self._ready:
            self._step, keys = self._keys(self._base, self._step)
            self._ready.extend(self._unstack(jax.vmap(self._sample)(keys)))
            self.device_calls += 1
        return self._ready.popleft()


def _block_keys(base, step, *, k: int, shard: int):
    """(step + k, the keys of steps step … step + k − 1 on ``shard``)."""
    steps = step + jnp.arange(k, dtype=step.dtype)
    return step + k, jax.vmap(lambda s: jax.random.fold_in(
        jax.random.fold_in(base, s), shard))(steps)


def _unstack(out, *, k: int) -> tuple:
    return tuple(jax.tree.map(lambda a: a[j], out) for j in range(k))


def pde_collocation_iterator(n: int, space_dim: int = 20, seed: int = 0,
                             start_step: int = 0,
                             pde: str | None = None,
                             problem=None,
                             coeffs_per_step: int | None = None
                             ) -> CounterStream:
    """Counter-based collocation stream (a ``CounterStream`` on shard 0).
    ``pde`` selects a registered problem's own domain sampler
    (``repro.pde``); an explicit ``problem``
    instance overrides the registry lookup (how the trainer threads
    ``--coeff-range`` rebuilt specs through); the default keeps the
    legacy HJB-domain behavior parameterized by ``space_dim``.

    ``coeffs_per_step`` (conditioned problems only) switches the
    coefficient draw from per-point iid — the problem sampler's default —
    to C scenario draws per step tiled over the batch: n // C consecutive
    points share each coefficient vector.  Grouped draws expose the model
    to whole mini-trajectories per scenario, which stabilizes early
    conditioned training; the counter-based key derivation keeps both
    modes restart-safe and deterministic.
    """
    if problem is None and pde is not None:
        from repro import pde as pde_lib
        problem = pde_lib.get_problem(pde)
    if problem is not None:
        if coeffs_per_step is not None:
            spec = problem.coeff_spec
            if spec is None:
                raise ValueError(
                    f"coeffs_per_step set but PDE {problem.name!r} is not "
                    "coefficient-conditioned")
            if not 1 <= coeffs_per_step <= n:
                raise ValueError(
                    f"coeffs_per_step must be in [1, {n}], "
                    f"got {coeffs_per_step}")

            def sample(key):
                kx, kc = jax.random.split(key)
                pts = problem.sample_collocation(kx, n)[:, :problem.in_dim]
                draws = spec.sample(kc, coeffs_per_step)    # (C, K)
                reps = -(-n // coeffs_per_step)             # ceil(n / C)
                tiled = jnp.repeat(draws, reps, axis=0)[:n]
                return jnp.concatenate(
                    [pts, tiled.astype(pts.dtype)], axis=-1)
        else:
            sample = lambda key: problem.sample_collocation(key, n)
    else:
        sample = lambda key: pinn_lib.sample_collocation(key, n, space_dim)
    return CounterStream(sample, seed, start_step)


def pde_term_batch_iterator(n: int, seed: int = 0, start_step: int = 0,
                            pde: str | None = None, problem=None,
                            sizes: dict | None = None) -> CounterStream:
    """Counter-based stream of NON-collocation term batches: yields one
    ``{term_name: (x, target)}`` dict per step — the ``term_batches=``
    form ``repro.core.pinn.residual_loss`` consumes — covering every
    boundary/data term of ``problem.loss_terms()``.

    Key derivation: the per-step key uses shard=1 (the collocation stream
    owns shard 0 at the same seed/step, so the two streams never reuse a
    key) and is folded with the term's INDEX in ``loss_terms()`` order, so
    each term draws an independent, restart-safe sequence.  Problems whose
    samplers draw noise from the key (ns-2d's data term) therefore replay
    identical observations on resume.

    ``n`` is the default batch size per term; ``sizes`` overrides it per
    name (``{"data": 256}``).  Terms whose sampler returns None are
    skipped; a problem with no non-collocation terms yields empty dicts
    and draws nothing on the device.
    """
    if problem is None:
        from repro import pde as pde_lib
        problem = pde_lib.get_problem(pde)
    sizes = sizes or {}
    terms = [(i, t) for i, t in enumerate(problem.loss_terms())
             if t.kind != "collocation" and t.sample is not None]

    def sample(key):
        out = {}
        for i, t in terms:
            batch = t.sample(jax.random.fold_in(key, i),
                             int(sizes.get(t.name, n)))
            if batch is not None:
                out[t.name] = batch
        return out

    return CounterStream(sample, seed, start_step, shard=1)


def pde_line_grid_iterator(n_anchors: int, seed: int = 0,
                           start_step: int = 0,
                           pde: str | None = None, problem=None,
                           points: int | None = None
                           ) -> Iterator[tuple]:
    """Counter-based collocation stream for the spectral estimator:
    yields ``(anchors, rows)`` per step — ``anchors`` (B, net_dim) drawn
    by the problem's own sampler (same key derivation as
    ``pde_collocation_iterator``, so an anchor stream at batch B matches
    the fd stream's points exactly), ``rows`` the deduped per-axis line
    grids ``spectral_line_rows`` builds through them.

    The loss paths rebuild ``rows`` from ``anchors`` internally (they are
    a pure function of the anchors), so trainers feed only ``anchors`` to
    ``residual_losses_stacked``; the materialized ``rows`` exist for
    consumers that meter or evaluate the actual inference bill — the
    residual-perf benchmark and the serving-side batch planner.
    """
    from repro.core import spectral as spectral_lib
    if problem is None:
        from repro import pde as pde_lib
        problem = pde_lib.get_problem(pde)
    M = problem.spectral_points if points is None else points
    step = start_step
    while True:
        anchors = problem.sample_collocation(_step_key(seed, step),
                                             n_anchors)
        rows = spectral_lib.spectral_line_rows(
            anchors, problem.in_dim, M, problem.spectral_extent)
        yield anchors, rows
        step += 1
