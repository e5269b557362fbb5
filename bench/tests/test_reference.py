"""The plain reference against the program where both compute in
float32: the same weights from the seed, and the same losses, gradients
and weight changes through the zebra pipeline's capacity and router
losses, replicated and expert-parallel, at a tiny size on the CPU."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import TINY, TINY_A2A, TINY_SERVE, TINY_TRAIN
from drivers import train
from reference import mixtral as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _file(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


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


@pytest.mark.parametrize("mode,mesh,n_chunks,microbatches", [
    ("replicated", [1, 1], 1, 1), ("replicated", [1, 1], 1, 2),
    ("alltoall", [1, 4], 1, 2), ("alltoall", [1, 4], 2, 2)])
def test_float32_program_matches_reference(mode, mesh, n_chunks,
                                           microbatches):
    from repro.models.modules import Policy
    base = TINY_A2A if mode == "alltoall" else TINY_TRAIN
    mix = dict(base, mesh=mesh, zebra={"mode": mode, "n_chunks": n_chunks,
                                       "num_microbatches": microbatches})
    cell = types.SimpleNamespace(config=dict(TINY, capacity_factor=0.8),
                                 mix=mix)
    b = train.build(cell, jax.devices(), harness.Spans(False),
                    policy=Policy(compute_dtype=jnp.float32))
    assert b.program.zcfg.num_microbatches == microbatches
    assert b.program.zcfg.mode == mode
    _, batches, prog = train.first_steps(b, 5, 3)
    got = train.compare(prog, train.reference_readings(cell, 5, batches))
    assert got["loss_gap"] < 2e-5
    assert got["grad_norm_gap"] < 2e-4
    assert got["update_norm_gap"] < 2e-4
    # At the capacity the program packs, and no other: the groups' drops
    # move the loss by more than the tolerance.
    groups, capacity = train.capacity_groups(cell.config, mix)
    losses, *_ = ref.train_readings(cell.config, mix["optimizer"], 5,
                                    batches[:1], groups, 2 * capacity)
    assert abs(losses[0] - prog["losses"][0]) > 1e-4


def test_capacity_groups_hand_counts():
    # d2-train-zebra: 2 microbatches of 4096 tokens on one chip;
    # int(4096 * 2 / 18 * 1.25) = 568, a multiple of 8.
    assert train.capacity_groups(_file("configs", "mixtral-d2-3l"),
                                 _file("traffic/mixes",
                                       "train-zebra-2x4096")) == (2, 568)
    # Mixtral-D1 (24 experts, top-2) in zebra all-to-all on a [1, 4] mesh,
    # batch 8 x 1024: 2 microbatches x 4 batch shards of 1024 tokens;
    # int(1024 * 2 / 24 * 1.25) = 106 -> 112, padded for 4 combine slices
    # (2 x 2 dispatch chunks) of round_up(112 / 4, 8) = 32 rows -> 128.
    d1 = {"n_experts": 24, "top_k": 2, "capacity_factor": 1.25}
    a2a = {"batch": 8, "seq": 1024, "mesh": [1, 4],
           "zebra": {"mode": "alltoall", "num_microbatches": 2,
                     "n_chunks": 2}}
    assert train.capacity_groups(d1, a2a) == (8, 128)
    assert train.dropped_shard(d1, a2a) == (18, 24)


def test_reference_over_four_devices_equals_one():
    from repro.data import DataConfig, DataLoader
    loader = DataLoader(DataConfig(vocab_size=TINY["vocab_size"],
                                   seq_len=TINY_A2A["seq"],
                                   global_batch=TINY_A2A["batch"], seed=3))
    batches = [{k: np.asarray(v) for k, v in next(loader).items()}
               for _ in range(2)]
    groups, capacity = train.capacity_groups(TINY, TINY_A2A)
    one, four = (ref.train_readings(TINY, TINY_A2A["optimizer"], 3, batches,
                                    groups, capacity, devices=d)
                 for d in (jax.devices()[:1], jax.devices()[:4]))
    np.testing.assert_allclose(one[0], four[0], rtol=1e-6)
    for a, b in zip(one[1:3], four[1:3]):
        assert a.keys() == b.keys()
        np.testing.assert_allclose([a[k] for k in a], [b[k] for k in a],
                                   rtol=1e-5)
    for k in one[3]:
        np.testing.assert_allclose(one[3][k], four[3][k], rtol=1e-5,
                                   atol=1e-7)
    sh, _ = ref.weight_shardings(TINY, jax.devices()[:4])
    assert sh["layers"][0]["ffn"]["wi_gate"].spec[0] == "expert"
    assert sh["layers"][0]["ffn"]["router"].spec == ()


@pytest.mark.parametrize("name", ["mixtral-d2", "mixtral-d2-3l"])
def test_model_config_of_mixtral_d2_is_unchanged(name):
    # The registry's architecture with the file's name and a fixed list of
    # its sizes, and the default reference and FLOP counts.
    from repro.models import registry
    m = _file("configs", name)
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "d_ff_expert", "vocab_size", "n_experts", "top_k",
            "rope_theta", "capacity_factor", "router_aux_coef",
            "router_z_coef")
    want = dataclasses.replace(registry.get_config(m["arch"]),
                               name=name, **{k: m[k] for k in keys})
    assert harness.model_config(m) == want
    assert harness.model_module(m, "reference").__name__ == \
        "reference.mixtral"
    assert harness.model_module(m, "flops").__name__ == "flops"


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
