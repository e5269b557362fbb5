"""Single-pack fused MoE expert FFN (kernels/ops.moe_ffn) + gmm_glu_tiled.

Covers: forward/gradient parity against the pure-jnp oracle for both
execution paths (Pallas interpret + XLA tile-gather fallback), an expert
receiving zero tokens, non-tile-multiple group sizes, the already-packed
[E, C, d] variant, and the structural single-pack guarantee (exactly one
pack scatter + one unpack gather in the forward jaxpr).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gmm as gmm_kernel
from repro.kernels import ops, ref
from repro.models import modules
from repro.models.config import LayerSpec, ModelConfig
from repro.models.modules import Policy, RunConfig
from repro.pytree import split_params

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype=jnp.float32, k=0, scale=1.0):
    x = jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32)
    return (x * scale).astype(dtype)


def make_ffn(M, d, f, G, dtype=jnp.float32):
    x = rand((M, d), dtype, 1, 0.5)
    wg = rand((G, d, f), dtype, 2, 0.1)
    wu = rand((G, d, f), dtype, 3, 0.1)
    wo = rand((G, f, d), dtype, 4, 0.1)
    return x, wg, wu, wo


# ---------------------------------------------------------------------------
# moe_ffn parity (both execution paths)
# ---------------------------------------------------------------------------

# Group partitions: zero-token expert, non-tile-multiple sizes, all-one-group.
SIZE_CASES = [
    [37, 0, 90, 73],
    [0, 0, 200, 0],
    [1, 1, 1, 197],
    [50, 50, 50, 50],
]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("sizes", SIZE_CASES)
def test_moe_ffn_matches_oracle(use_kernel, sizes):
    M, d, f, G = sum(sizes), 32, 48, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G)
    gs = jnp.asarray(sizes, jnp.int32)
    out = ops.moe_ffn(x, wg, wu, wo, gs, use_kernel=use_kernel, block_m=32)
    want = ref.moe_ffn(x, wg, wu, wo, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_moe_ffn_grads_match_oracle(use_kernel):
    sizes = [37, 0, 90, 73]
    M, d, f, G = sum(sizes), 32, 48, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G)
    gs = jnp.asarray(sizes, jnp.int32)

    g1 = jax.grad(
        lambda *a: jnp.sum(ops.moe_ffn(*a, gs, use_kernel=use_kernel,
                                       block_m=32) ** 2),
        argnums=(0, 1, 2, 3))(x, wg, wu, wo)
    g2 = jax.grad(
        lambda *a: jnp.sum(ref.moe_ffn(*a, gs) ** 2),
        argnums=(0, 1, 2, 3))(x, wg, wu, wo)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_moe_ffn_group_dense_matches_oracle():
    """Small-M fallback parity (values + grads), with and without fused
    row scales."""
    sizes = [37, 0, 90, 73]
    M, d, f, G = sum(sizes), 32, 48, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G)
    gs = jnp.asarray(sizes, jnp.int32)
    s = rand((M,), k=11, scale=0.5)

    out = ops.moe_ffn_group_dense(x, wg, wu, wo, gs)
    want = ref.moe_ffn(x, wg, wu, wo, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)

    # auto-routing picks it at decode shapes (M*(G-1) <= G*block_m)
    xs_small = x[:24]
    gs_small = jnp.asarray([10, 0, 9, 5], jnp.int32)
    auto = ops.moe_ffn(xs_small, wg, wu, wo, gs_small)
    np.testing.assert_allclose(
        np.asarray(auto),
        np.asarray(ops.moe_ffn_group_dense(xs_small, wg, wu, wo, gs_small)),
        atol=1e-6)

    g1 = jax.grad(lambda *a: jnp.sum(
        ops.moe_ffn_group_dense(*a[:4], gs, row_scales=a[4]) ** 2),
        argnums=(0, 1, 2, 3, 4))(x, wg, wu, wo, s)
    g2 = jax.grad(lambda *a: jnp.sum(
        (ref.moe_ffn(*a[:4], gs) * a[4][:, None]) ** 2),
        argnums=(0, 1, 2, 3, 4))(x, wg, wu, wo, s)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_moe_ffn_packed_path_row_scales(use_kernel):
    """Fused row-scale combine on the packed pipeline: values + all grads
    (incl. d(scales), which needs the rematerialized unscaled rows)."""
    sizes = [37, 0, 90, 73]
    M, d, f, G = sum(sizes), 32, 48, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G)
    gs = jnp.asarray(sizes, jnp.int32)
    s = rand((M,), k=12, scale=0.5)

    out = ops.moe_ffn(x, wg, wu, wo, gs, row_scales=s, small_m=False,
                      use_kernel=use_kernel, block_m=32)
    want = ref.moe_ffn(x, wg, wu, wo, gs) * s[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)

    g1 = jax.grad(lambda *a: jnp.sum(
        ops.moe_ffn(*a[:4], gs, row_scales=a[4], small_m=False,
                    use_kernel=use_kernel, block_m=32) ** 2),
        argnums=(0, 1, 2, 3, 4))(x, wg, wu, wo, s)
    g2 = jax.grad(lambda *a: jnp.sum(
        (ref.moe_ffn(*a[:4], gs) * a[4][:, None]) ** 2),
        argnums=(0, 1, 2, 3, 4))(x, wg, wu, wo, s)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_moe_ffn_bf16():
    sizes = [64, 96, 40]
    M, d, f, G = sum(sizes), 32, 64, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G, jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    out = ops.moe_ffn(x, wg, wu, wo, gs, use_kernel=False, block_m=32)
    want = ref.moe_ffn(x, wg, wu, wo, gs)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=5e-2)


# ---------------------------------------------------------------------------
# Already-packed [E, C, d] variant (zebra dispatch buffers)
# ---------------------------------------------------------------------------

def _dense_expert_ffn(buf, wg, wu, wo):
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", g * u, wo)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("E,C", [(3, 16), (1, 8), (4, 40), (2, 25)])
def test_moe_ffn_packed_matches_dense(use_kernel, E, C):
    d, f = 32, 48
    buf = rand((E, C, d), k=6, scale=0.5)
    wg = rand((E, d, f), k=2, scale=0.1)
    wu = rand((E, d, f), k=3, scale=0.1)
    wo = rand((E, f, d), k=4, scale=0.1)
    out = ops.moe_ffn_packed(buf, wg, wu, wo, use_kernel=use_kernel)
    want = _dense_expert_ffn(buf, wg, wu, wo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)

    gp = jax.grad(lambda b: jnp.sum(
        ops.moe_ffn_packed(b, wg, wu, wo, use_kernel=use_kernel) ** 2))(buf)
    gd = jax.grad(lambda b: jnp.sum(
        _dense_expert_ffn(b, wg, wu, wo) ** 2))(buf)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=2e-3)


# ---------------------------------------------------------------------------
# gmm_glu_tiled vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_glu_tiled_matches_ref(dtype):
    M, K, N, G = 160, 32, 48, 4
    bm = 32
    lhs = rand((M, K), dtype, 1, 0.5)
    w12 = rand((G, K, 2 * N), dtype, 2, 0.1)
    gs = jnp.array([37, 0, 90, 33], jnp.int32)
    dest, tile_group, Mp = ops._pack_meta(gs, M, G, bm)
    lhs_p = jnp.zeros((Mp, K), lhs.dtype).at[dest].set(lhs)
    out_p = gmm_kernel.gmm_glu_tiled(lhs_p, w12, tile_group, block_m=bm,
                                     interpret=True)
    out = jnp.take(out_p, dest, axis=0)
    want = ref.gmm_glu(lhs, w12, gs)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# Structural single-pack guarantee
# ---------------------------------------------------------------------------

def _count_eqns(jaxpr, pred, acc=None):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    acc = [] if acc is None else acc

    def visit(v):
        if isinstance(v, ClosedJaxpr):
            _count_eqns(v.jaxpr, pred, acc)
        elif isinstance(v, Jaxpr):
            _count_eqns(v, pred, acc)
        elif isinstance(v, (list, tuple)):
            for u in v:
                visit(u)

    for eqn in jaxpr.eqns:
        if pred(eqn):
            acc.append(eqn)
        for v in eqn.params.values():
            visit(v)
    return acc


def test_moe_ffn_single_pack_scatter_gather():
    """The fused kernel-path forward contains exactly ONE pack scatter and
    ONE d-wide unpack gather (the remaining gathers are 1-D metadata
    lookups over [G]-sized arrays)."""
    sizes = [37, 0, 90, 73]
    M, d, f, G = sum(sizes), 32, 48, len(sizes)
    x, wg, wu, wo = make_ffn(M, d, f, G)
    gs = jnp.asarray(sizes, jnp.int32)
    jx = jax.make_jaxpr(
        lambda x_: ops.moe_ffn(x_, wg, wu, wo, gs, use_kernel=True,
                               block_m=32))(x)
    scatters = _count_eqns(
        jx.jaxpr, lambda e: e.primitive.name.startswith("scatter"))
    wide_gathers = _count_eqns(
        jx.jaxpr, lambda e: e.primitive.name == "gather"
        and e.invars[0].aval.ndim >= 2)
    assert len(scatters) == 1, [e.primitive.name for e in scatters]
    assert len(wide_gathers) == 1


def test_apply_moe_gather_single_pack():
    """Whole gather-mode MoE layer at a training shape (M > E*block_m so
    the packed pipeline is taken): one pack scatter (.set) total; every
    other scatter is an int/combine ADD (bincount histograms + the
    segment-sum combine), never a d-wide repack. The fused row-scale
    combine must not add a second d-wide pass."""
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, d_ff_expert=64,
                      vocab_size=64, n_experts=4, top_k=2,
                      pattern=(LayerSpec(ffn="moe"),))
    run = RunConfig(policy=Policy(compute_dtype=jnp.float32),
                    moe_impl="gather", use_gmm_kernel=True)
    p, _ = split_params(modules.init_moe(KEY, cfg))
    x = rand((4, 128, cfg.d_model), k=9, scale=0.5)  # M = 1024 > 4*128
    jx = jax.make_jaxpr(
        lambda x_: modules.apply_moe(p, cfg, run, x_)[0])(x)
    set_scatters = _count_eqns(
        jx.jaxpr, lambda e: e.primitive.name == "scatter")
    assert len(set_scatters) == 1, [e.primitive.name for e in set_scatters]


def test_apply_moe_decode_shape_uses_group_dense():
    """Decode shapes (M <= E*block_m) skip the packed pipeline entirely:
    no pack scatter in the jaxpr at all (ROADMAP small-M fallback)."""
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, d_ff_expert=64,
                      vocab_size=64, n_experts=4, top_k=2,
                      pattern=(LayerSpec(ffn="moe"),))
    run = RunConfig(policy=Policy(compute_dtype=jnp.float32),
                    moe_impl="gather")
    p, _ = split_params(modules.init_moe(KEY, cfg))
    x = rand((4, 1, cfg.d_model), k=9, scale=0.5)  # M = 8 (decode step)
    jx = jax.make_jaxpr(
        lambda x_: modules.apply_moe(p, cfg, run, x_)[0])(x)
    set_scatters = _count_eqns(
        jx.jaxpr, lambda e: e.primitive.name == "scatter")
    assert len(set_scatters) == 0, [e.primitive.name for e in set_scatters]


# ---------------------------------------------------------------------------
# Full-layer parity (gather+fused vs dense), forward AND backward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("seq", [8, 256])  # group-dense / packed regimes
def test_apply_moe_gather_fused_grads_match_dense(use_kernel, seq):
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, d_ff_expert=64,
                      vocab_size=64, n_experts=4, top_k=2,
                      pattern=(LayerSpec(ffn="moe"),))
    pol = Policy(compute_dtype=jnp.float32)
    run_d = RunConfig(policy=pol, moe_impl="dense")
    run_g = RunConfig(policy=pol, moe_impl="gather",
                      use_gmm_kernel=use_kernel)
    p, _ = split_params(modules.init_moe(KEY, cfg))
    x = rand((2, seq, cfg.d_model), k=9, scale=0.5)

    def loss(run):
        def fn(p_, x_):
            y, aux = modules.apply_moe(p_, cfg, run, x_)
            return jnp.sum(y ** 2) + aux["moe_aux_loss"]
        return fn

    y_d, _ = modules.apply_moe(p, cfg, run_d, x)
    y_g, _ = modules.apply_moe(p, cfg, run_g, x)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_g), atol=1e-5)

    gd = jax.grad(loss(run_d), argnums=(0, 1))(p, x)
    gg = jax.grad(loss(run_g), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
