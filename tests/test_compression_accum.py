"""Gradient compression (error feedback) + gradient accumulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.models.config import ShapeConfig
from repro.models.modules import Policy, RunConfig
from repro.train import compression as comp
from repro.train import optimizer as opt
from repro.train.step import make_train_program

RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32), moe_impl="gather")


def test_compress_roundtrip_bounded_error():
    g = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3.0
    q, scale = comp.compress(g)
    g_hat = comp.decompress(q, scale)
    assert float(jnp.max(jnp.abs(g - g_hat))) <= float(scale) / 2 + 1e-6


def test_error_feedback_removes_bias():
    """With error feedback, the accumulated applied gradient converges to
    the accumulated true gradient (bias -> 0)."""
    key = jax.random.PRNGKey(1)
    g_true = jax.random.normal(key, (64,)) * 1e-3  # small: heavy quant error
    err = jnp.zeros((64,))
    applied_sum = jnp.zeros((64,))
    for _ in range(200):
        corrected, new_err_fn = comp.apply_error_feedback(g_true, err)
        q, s = comp.compress(corrected)
        g_hat = comp.decompress(q, s)
        err = new_err_fn(g_hat)
        applied_sum = applied_sum + g_hat
    rel = float(jnp.linalg.norm(applied_sum - 200 * g_true)
                / jnp.linalg.norm(200 * g_true))
    assert rel < 0.02, rel
    # without error feedback the same setup keeps a persistent bias
    applied_nf = jnp.zeros((64,))
    for _ in range(200):
        q, s = comp.compress(g_true)
        applied_nf = applied_nf + comp.decompress(q, s)
    rel_nf = float(jnp.linalg.norm(applied_nf - 200 * g_true)
                   / jnp.linalg.norm(200 * g_true))
    assert rel < rel_nf


def test_compressed_psum_matches_mean(mesh8):
    """shard_map int8 psum with EF ~= exact mean within quant tolerance."""
    from jax.sharding import PartitionSpec as P
    key = jax.random.PRNGKey(2)
    grads = {"w": jax.random.normal(key, (8, 32))}
    err = {"w": jnp.zeros((8, 32))}

    def f(g, e):
        return comp.compressed_psum(g, e, "data")

    out, new_err = jax.jit(jax.shard_map(
        f, mesh=mesh8, in_specs=({"w": P("data", None)},
                                 {"w": P("data", None)}),
        out_specs=({"w": P(None, None)}, {"w": P("data", None)}),
        check_vma=False))(grads, err)
    want = jnp.mean(grads["w"].reshape(2, 4, 32), axis=0)
    # each data-shard row group averaged across the 2 'data' rows
    got = out["w"][:4]
    amax = float(jnp.max(jnp.abs(grads["w"])))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=amax / 127)


def test_grad_accumulation_matches_full_batch(mesh4):
    """accum_steps=2 gives the same update as the full-batch step."""
    cfg = registry.smoke_config(registry.get_config("llama3.2-3b"))
    shape = ShapeConfig("t", "train", 32, 4)
    ocfg = opt.OptimizerConfig(peak_lr=1e-3, warmup_steps=0, total_steps=5,
                               grad_clip=0.0)
    p_full = make_train_program(cfg, mesh4, RUN, shape, opt_cfg=ocfg)
    p_acc = make_train_program(cfg, mesh4, RUN, shape, opt_cfg=ocfg,
                               accum_steps=2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    with mesh4:
        params = p_full.init_params()
        o1 = p_full.init_opt(params)
        params2 = p_acc.init_params()  # fresh buffers (steps donate args)
        o2 = p_acc.init_opt(params2)
        pa, _, m1 = p_full.train_step(params, o1, batch)
        pb, _, m2 = p_acc.train_step(params2, o2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # Adam divides by sqrt(nu): f32 reduction-order differences in the
    # grads are amplified to O(lr)-relative param deltas. lr=1e-3 here.
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), pa, pb)))
    assert err < 2e-4, err
