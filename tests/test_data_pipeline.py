"""The counter-keyed PDE streams drawn in blocks (``CounterStream``): every
batch equals the per-step form ``sample(key(seed, step, shard))`` bit for
bit, across block boundaries and from any start step, and the streams
count the blocks they draw."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pde as pde_lib
from repro.data import pde_collocation_iterator, pde_term_batch_iterator
from repro.data.pipeline import BLOCK_BYTES, MAX_BLOCK_STEPS, block_steps

SEED = 3266489917


def _key(seed, step, shard):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.fold_in(key, shard)


def _colloc_case(name, n, start):
    prob = pde_lib.get_problem(name)
    stream = pde_collocation_iterator(n, seed=SEED, start_step=start,
                                      problem=prob)
    return stream, lambda s: prob.sample_collocation(_key(SEED, s, 0), n)


def _grouped_coeffs_case(name, n, start, groups=4):
    prob = pde_lib.get_problem(name)
    stream = pde_collocation_iterator(n, seed=SEED, start_step=start,
                                      problem=prob, coeffs_per_step=groups)

    def per_step(s):
        kx, kc = jax.random.split(_key(SEED, s, 0))
        pts = prob.sample_collocation(kx, n)[:, :prob.in_dim]
        draws = prob.coeff_spec.sample(kc, groups)
        tiled = jnp.repeat(draws, -(-n // groups), axis=0)[:n]
        return jnp.concatenate([pts, tiled.astype(pts.dtype)], axis=-1)

    return stream, per_step


def _terms_case(name, n, start):
    prob = pde_lib.get_problem(name)
    stream = pde_term_batch_iterator(n, seed=SEED, start_step=start,
                                     problem=prob)
    terms = [(i, t) for i, t in enumerate(prob.loss_terms())
             if t.kind != "collocation"]

    def per_step(s):
        return {t.name: t.sample(jax.random.fold_in(_key(SEED, s, 1), i), n)
                for i, t in terms}

    return stream, per_step


@pytest.mark.parametrize("case, args, block", [
    (_colloc_case, ("hjb-20d", 100, 0), 64),
    (_colloc_case, ("hjb-20d", 100, 37), 64),
    (_colloc_case, ("hjb-20d", 5000, 5), 8),       # 420 kB a batch
    (_grouped_coeffs_case, ("hjb-10d-lam", 64, 0), 64),
    (_grouped_coeffs_case, ("hjb-10d-lam", 64, 21), 64),
    (_terms_case, ("ns-2d", 16, 0), 64),
    (_terms_case, ("ns-2d", 16, 11), 64),
])
def test_blocked_stream_matches_per_step_form(case, args, block):
    """Steps start … start + 2K + 3 (two block boundaries crossed) equal
    the per-step draw exactly: same keys, same sampler, same dtype."""
    stream, per_step = case(*args)
    assert stream.block == block
    start = args[2]
    for s in range(start, start + 2 * block + 4):
        got, want = next(stream), per_step(s)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), s
    assert stream.device_calls == 3


def test_streams_count_blocks_and_skip_empty_terms():
    """hjb-20d at batch 100: 130 collocation batches take three blocks of
    64; the problem has no non-collocation terms, so its term stream
    yields empty dicts and draws nothing."""
    colloc = pde_collocation_iterator(100, seed=SEED, pde="hjb-20d")
    terms = pde_term_batch_iterator(25, seed=SEED, pde="hjb-20d")
    for _ in range(130):
        assert next(colloc).shape == (100, 21)
        assert next(terms) == {}
    assert (colloc.device_calls, colloc.batches) == (3, 130)
    assert (terms.device_calls, terms.batches) == (0, 130)


@pytest.mark.parametrize("batch_bytes, block", [
    (100 * 21 * 4, MAX_BLOCK_STEPS),
    (BLOCK_BYTES // MAX_BLOCK_STEPS, MAX_BLOCK_STEPS),
    (BLOCK_BYTES // MAX_BLOCK_STEPS + 1, MAX_BLOCK_STEPS // 2),
    (BLOCK_BYTES // 2, 2),
    (BLOCK_BYTES // 2 + 1, 1),
    (1_000_000 * 21 * 4, 1),
])
def test_block_steps_keeps_a_block_within_the_byte_cap(batch_bytes, block):
    assert block_steps(batch_bytes) == block
    assert block == 1 or block * batch_bytes <= BLOCK_BYTES
