"""The plain reference against the program where both compute in
float32: the same weights from the seed, and the same losses, gradients
and weight changes through the zebra pipeline's capacity and router
losses, at a tiny size on the CPU."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import TINY, TINY_SERVE, TINY_TRAIN
from drivers import train
from reference import mixtral as ref


def test_reference_draws_the_programs_weights():
    from repro.models import stack
    from repro.pytree import split_params
    cfg = harness.model_config(TINY)
    mine = ref.init_params(TINY, 2**31 + 9)
    layers = mine.pop("layers")
    mine["blocks"] = {"pos0": jax.tree.map(lambda *x: jnp.stack(x), *layers)}
    theirs = split_params(stack.init_model(
        jax.random.PRNGKey(np.uint32(2**31 + 9)), cfg))[0]
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("microbatches", [1, 2])
def test_float32_program_matches_reference(microbatches):
    from repro.models.modules import Policy
    mix = dict(TINY_TRAIN, zebra=dict(TINY_TRAIN["zebra"],
                                      num_microbatches=microbatches))
    cell = types.SimpleNamespace(config=dict(TINY, capacity_factor=0.8),
                                 mix=mix)
    b = train.build(cell, jax.devices(), harness.Spans(False),
                    policy=Policy(compute_dtype=jnp.float32))
    assert b.program.zcfg.num_microbatches == microbatches
    _, batches, prog = train.first_steps(b, 5, 3)
    got = train.compare(prog, train.reference_readings(cell, 5, batches))
    assert got["loss_gap"] < 2e-5
    assert got["grad_norm_gap"] < 2e-4
    assert got["update_norm_gap"] < 2e-4


def test_served_readings_are_zero_for_the_references_own_tokens():
    m = TINY
    params = ref.init_params(m, 3)
    prompt = list(range(5, 17))
    toks, served, rows = list(prompt), [], []
    for _ in range(6):
        logits = ref.logits_at(params, m, jnp.asarray(toks), ref.make_dot("f32"))
        nxt = int(jnp.argmax(logits[-1]))
        served.append(nxt)
        rows.append(np.asarray(logits[-1]))
        toks.append(nxt)
    L, R = TINY_SERVE["engine"]["max_len"], TINY_SERVE["output"]["max"]
    (gaps, dist), = ref.served_readings(m, 3, L, R,
                                        [(prompt, served, np.stack(rows))])
    assert gaps.shape == dist.shape == (6,)
    np.testing.assert_allclose(gaps, 0.0, atol=1e-5)
    np.testing.assert_allclose(dist, 0.0, atol=1e-5)
    (bad, far), = ref.served_readings(
        m, 3, L, R, [(prompt, [(t + 1) % m["vocab_size"] for t in served],
                      0.5 * np.stack(rows))])
    assert np.max(bad) > 1e-3
    np.testing.assert_allclose(far[0], 0.5, rtol=1e-4)
