"""Overlapped zebra dispatch (DESIGN.md §8).

Covers: chunked a2a/compute pipelining parity (n_chunks > 1 matches the
serialized path and the fused oracle, forward AND gradients, including
zero-token experts inside a chunk and non-tile-multiple capacities), the
unified local+remote grouped GEMM (ops.moe_ffn_packed_multi — structurally
ONE grouped GEMM call per projection direction covering both expert sets),
the overlap-aware simulator/planner cost model, and the dense-mode routing
satellite (RunConfig defaults to the fused pipeline)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_moe_ffn import _count_eqns

from repro.core import zebra_spmd as Z
from repro.core.asym_ea import asym_ea_offload
from repro.core.simulator import CommTimes, exposed_comm, simulate_hetermoe
from repro.kernels import gmm as gmm_kernel
from repro.kernels import ops
from repro.models import modules, registry
from repro.models.config import LayerSpec, ModelConfig
from repro.models.modules import Policy, RunConfig
from repro.pytree import split_params

pytestmark = pytest.mark.zebra  # CI job slice (see .github/workflows/ci.yml)

RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32), moe_impl="gather")
KEY = jax.random.PRNGKey(0)


def moe_cfg(arch="qwen3-moe-30b-a3b", cap=99.0, **kw):
    cfg = registry.smoke_config(registry.get_config(arch))
    return dataclasses.replace(cfg, capacity_factor=cap, **kw)


def rand(shape, k=0, scale=1.0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape,
                             jnp.float32) * scale


# ---------------------------------------------------------------------------
# ops.moe_ffn_packed_multi: unified local+remote grouped GEMM
# ---------------------------------------------------------------------------

def _dense_expert_ffn(buf, wg, wu, wo):
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", g * u, wo)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_moe_ffn_packed_multi_matches_separate(use_kernel):
    """Two segments with different, non-tile-multiple capacities and a
    zero-token expert (all-zero rows) inside the first segment: the ONE
    unified call matches per-segment moe_ffn_packed calls and the dense
    oracle, forward and gradients."""
    d, f = 32, 48
    b1 = rand((3, 25, d), k=1, scale=0.5).at[1].set(0.0)  # zero-token expert
    b2 = rand((2, 40, d), k=2, scale=0.5)
    ws = [(rand((g, d, f), k=3 + i, scale=0.1),
           rand((g, d, f), k=5 + i, scale=0.1),
           rand((g, f, d), k=7 + i, scale=0.1))
          for i, g in enumerate((3, 2))]
    (wg1, wu1, wo1), (wg2, wu2, wo2) = ws

    o1, o2 = ops.moe_ffn_packed_multi(
        [b1, b2], [wg1, wg2], [wu1, wu2], [wo1, wo2], use_kernel=use_kernel)
    np.testing.assert_allclose(
        np.asarray(o1), np.asarray(ops.moe_ffn_packed(
            b1, wg1, wu1, wo1, use_kernel=use_kernel)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o2), np.asarray(_dense_expert_ffn(b2, wg2, wu2, wo2)),
        atol=1e-4)

    def loss_multi(x1, x2):
        a, b = ops.moe_ffn_packed_multi(
            [x1, x2], [wg1, wg2], [wu1, wu2], [wo1, wo2],
            use_kernel=use_kernel)
        return jnp.sum(a ** 2) + jnp.sum(b ** 2)

    def loss_dense(x1, x2):
        return jnp.sum(_dense_expert_ffn(x1, wg1, wu1, wo1) ** 2) + \
            jnp.sum(_dense_expert_ffn(x2, wg2, wu2, wo2) ** 2)

    g1 = jax.grad(loss_multi, argnums=(0, 1))(b1, b2)
    g2 = jax.grad(loss_dense, argnums=(0, 1))(b1, b2)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_chunk_capacity():
    assert ops.chunk_capacity(24, 1) == (24, 24)
    assert ops.chunk_capacity(24, 2) == (32, 16)   # sublane-aligned chunks
    assert ops.chunk_capacity(24, 4) == (32, 8)
    assert ops.chunk_capacity(1, 2) == (16, 8)
    for c, q in [(8, 1), (40, 2), (100, 4), (7, 3)]:
        cp, cq = ops.chunk_capacity(c, q)
        assert cp == q * cq and cp >= c and cq % 8 == 0


def test_unified_one_grouped_gemm_per_direction():
    """ACCEPTANCE: the unified call covering BOTH segments (local + remote
    experts) lowers to exactly ONE custom_vjp and, inside it, exactly TWO
    grouped-GEMM kernel calls — one fused gate+up, one down projection:
    one grouped GEMM per direction."""
    d, f = 32, 48
    b1, b2 = rand((2, 16, d), k=1), rand((3, 32, d), k=2)
    wg = [rand((g, d, f), k=4) for g in (2, 3)]
    wu = [rand((g, d, f), k=5) for g in (2, 3)]
    wo = [rand((g, f, d), k=6) for g in (2, 3)]
    jx = jax.make_jaxpr(lambda x1, x2: ops.moe_ffn_packed_multi(
        [x1, x2], wg, wu, wo, use_kernel=True)[0])(b1, b2)
    vjps = _count_eqns(jx.jaxpr,
                       lambda e: e.primitive.name == "custom_vjp_call")
    assert len(vjps) == 1, [e.primitive.name for e in jx.jaxpr.eqns]
    kernels = _count_eqns(jx.jaxpr,
                          lambda e: e.primitive.name == "pallas_call")
    assert len(kernels) == 2, [e.primitive.name for e in kernels]


# ---------------------------------------------------------------------------
# SPMD engine: chunked dispatch parity + engine-level structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_chunks,offload", [(2, 0), (2, 4), (4, 4)])
def test_alltoall_chunked_matches_oracle(mesh8, n_chunks, offload):
    """Chunked (n_chunks > 1) and offloaded dispatch matches the fused
    single-program oracle to fp32 tolerance. The smoke routing leaves some
    experts with zero tokens in some chunks; capacities are rounded to
    sublane (8) multiples, not GEMM-tile (128) multiples."""
    cfg = moe_cfg()
    ffn, _ = split_params(modules.init_moe(KEY, cfg))
    x = rand((8, 16, cfg.d_model), k=9, scale=0.3)
    y_ref, _ = modules.apply_moe(ffn, cfg, RUN, x)
    zcfg = Z.ZebraConfig(mode="alltoall", capacity_factor=99.0,
                         batch_axes=("data", "model"), n_chunks=n_chunks,
                         offload_experts=offload)
    with mesh8:
        moe_fn = Z.make_ep_moe(mesh8, cfg, RUN, zcfg)
        y, _ = jax.jit(moe_fn)(ffn, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(y.reshape(x.shape), y_ref, atol=1e-4)


@pytest.mark.parametrize("n_chunks,n_chunks_combine", [(2, 2), (2, 6),
                                                       (1, 1), (2, None)])
def test_alltoall_combine_chunks_decoupled_parity(mesh8, n_chunks,
                                                  n_chunks_combine):
    """Decoupled combine chunking (ZebraConfig.n_chunks_combine): the
    combine all-to-all runs at a FINER granularity than dispatch (default
    2x — combine cotangents are f32 in the backward, 2x the wire volume)
    with no numeric effect: forward AND gradients match the serialized
    path at every (dispatch, combine) chunk pairing."""
    cfg = moe_cfg()
    ffn, _ = split_params(modules.init_moe(KEY, cfg))
    x2d = rand((128, cfg.d_model), k=11, scale=0.3)

    def run(n_c, n_cc):
        zcfg = Z.ZebraConfig(mode="alltoall", capacity_factor=99.0,
                             batch_axes=("data", "model"), n_chunks=n_c,
                             n_chunks_combine=n_cc)
        with mesh8:
            moe_fn = Z.make_ep_moe(mesh8, cfg, RUN, zcfg)
            y = jax.jit(moe_fn)(ffn, x2d)[0]
            g = jax.jit(jax.grad(
                lambda f, xx: jnp.sum(moe_fn(f, xx)[0] ** 2)))(ffn, x2d)
        return y, g

    y_ref, g_ref = run(1, 1)
    y, g = run(n_chunks, n_chunks_combine)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_ref, g)))
    assert err < 1e-3, err


def test_combine_chunks_must_divide_dispatch_chunks(mesh8):
    cfg = moe_cfg()
    zcfg = Z.ZebraConfig(mode="alltoall", batch_axes=("data", "model"),
                         n_chunks=2, n_chunks_combine=3)
    with pytest.raises(AssertionError, match="multiple of n_chunks"):
        Z.make_ep_moe(mesh8, cfg, RUN, zcfg)


def test_alltoall_chunked_grads_match_serialized(mesh8):
    """Gradients through the chunked+offloaded pipeline equal the
    serialized (n_chunks=1, no offload) path's."""
    cfg = moe_cfg()
    ffn, _ = split_params(modules.init_moe(KEY, cfg))
    x2d = rand((128, cfg.d_model), k=10, scale=0.3)

    def grads(n_chunks, offload):
        zcfg = Z.ZebraConfig(mode="alltoall", capacity_factor=99.0,
                             batch_axes=("data", "model"),
                             n_chunks=n_chunks, offload_experts=offload)
        with mesh8:
            moe_fn = Z.make_ep_moe(mesh8, cfg, RUN, zcfg)
            return jax.jit(jax.grad(
                lambda f, xx: jnp.sum(moe_fn(f, xx)[0] ** 2)))(ffn, x2d)

    g_ser = grads(1, 0)
    g_chk = grads(2, 4)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_ser, g_chk)))
    assert err < 1e-3, err


def test_alltoall_offload_single_unified_call(mesh8):
    """ACCEPTANCE (engine level): with offload_experts > 0 and n_chunks=1
    the whole expert hop — local AND remote experts — is ONE unified
    grouped-GEMM custom_vjp with one kernel call per projection
    direction."""
    cfg = moe_cfg()
    run = dataclasses.replace(RUN, use_gmm_kernel=True)
    ffn, _ = split_params(modules.init_moe(KEY, cfg))
    x2d = rand((128, cfg.d_model), k=11, scale=0.3)
    zcfg = Z.ZebraConfig(mode="alltoall", capacity_factor=99.0,
                         batch_axes=("data", "model"), n_chunks=1,
                         offload_experts=4)
    with mesh8:
        moe_fn = Z.make_ep_moe(mesh8, cfg, run, zcfg)
        jx = jax.make_jaxpr(moe_fn)(ffn, x2d)
    vjps = _count_eqns(jx.jaxpr,
                       lambda e: e.primitive.name == "custom_vjp_call")
    assert len(vjps) == 1
    kernels = _count_eqns(jx.jaxpr,
                          lambda e: e.primitive.name == "pallas_call")
    assert len(kernels) == 2


# ---------------------------------------------------------------------------
# Overlap-aware cost model (simulator / planner / Asym-EA)
# ---------------------------------------------------------------------------

def _sim_cfg(L, n):
    return ModelConfig(name="sim", family="moe", n_layers=L, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                       pattern=(LayerSpec(ffn="moe"),), n_experts=n, top_k=2)


def _times(t_attn=1.0, t_exp=1.0, t_exp_attn=0.75):
    from repro.core.profiler import LayerTimes
    return LayerTimes(t_attn=t_attn, t_exp=t_exp, t_exp_attn=t_exp_attn,
                      t_exp_on_exp=t_exp, t_attn_on_exp=2.0)


def test_exposed_comm_properties():
    assert exposed_comm(1.0, 0.5, 1) == 1.0          # serialized: all exposed
    assert exposed_comm(0.0, 1.0, 4) == 0.0
    # fully hidden tail: only the first chunk's wire time stays exposed
    assert abs(exposed_comm(1.0, 100.0, 4) - 0.25) < 1e-12
    # nothing to hide under: still the full transfer
    assert abs(exposed_comm(1.0, 0.0, 4) - 1.0) < 1e-12
    # monotone nonincreasing in n_chunks, bounded below by t_comm/q
    prev = exposed_comm(1.0, 0.8, 1)
    for q in (2, 3, 4, 8):
        cur = exposed_comm(1.0, 0.8, q)
        assert cur <= prev + 1e-12
        assert cur >= 1.0 / q - 1e-12
        prev = cur


def test_chunked_dispatch_shrinks_sim_iter_time():
    cfg = _sim_cfg(8, 8)
    t = _times(1.0, 1.2)
    comm = CommTimes(0.5, 0.5)
    z1 = simulate_hetermoe(cfg, t, comm, 4, 1, 1, n_chunks=1)
    z4 = simulate_hetermoe(cfg, t, comm, 4, 1, 1, n_chunks=4)
    assert z4.iter_time < z1.iter_time
    # compute totals are untouched — only exposed link time shrinks
    assert abs(z4.attn_busy - z1.attn_busy) < 1e-9


def test_asym_ea_does_not_double_count_hidden_a2a():
    """Serialized comm joins the bubble and increases offload; once the
    planner reports only the exposed residue of a chunked dispatch, the
    offload decision shrinks back toward the comm-free one. n_max is set
    high so the memory cap's alpha-damping does not mask the effect."""
    kw = dict(n_min=0, n_max=40)
    base = asym_ea_offload(8, 6, 1, 1, 1.0, 0.75, 1.2, **kw)
    full = asym_ea_offload(8, 6, 1, 1, 1.0, 0.75, 1.2,
                           t_comm_exposed=0.6, **kw)
    hidden = asym_ea_offload(8, 6, 1, 1, 1.0, 0.75, 1.2,
                             t_comm_exposed=exposed_comm(0.6, 1.2, 4), **kw)
    assert full.t_gather > hidden.t_gather > base.t_gather
    assert sum(full.offload) > sum(hidden.offload) >= sum(base.offload)


def test_planner_overlap_aware():
    """plan_zp_group sweeps n_chunks; the chosen plan is never worse than
    the forced-serialized plan and records the chunking it priced."""
    from repro.core import hardware as HW
    from repro.core import planner
    from repro.core.profiler import ZPGroupShape
    cfg = registry.get_config("mixtral-w1")
    zp = ZPGroupShape(M=4, N=4, attn_class=HW.A40, exp_class=HW.V100)
    serialized = planner.plan_zp_group(cfg, zp, 8, 1024, n_chunks=1)
    best = planner.plan_zp_group(cfg, zp, 8, 1024)
    assert serialized.n_chunks == 1
    assert best.n_chunks in (1, 2, 4)
    assert best.predicted.iter_time <= serialized.predicted.iter_time
    # overlap-aware LayerTimes carry the a2a wire times
    assert best.times.t_dispatch > 0.0 and best.times.t_combine > 0.0


# ---------------------------------------------------------------------------
# Satellites: dense-mode routing default + VMEM-budget block candidates
# ---------------------------------------------------------------------------

def test_default_runconfig_routes_through_fused_pipeline():
    """Serve/train paths (RunConfig defaults) ride the single-pack fused
    pipeline; the O(E) einsum stays behind the explicit 'dense' reference
    impl. Structural check: default-run apply_moe at a training shape has
    exactly the gather path's ONE pack scatter, not the dense mode's
    scatter-add gate table."""
    assert RunConfig().moe_impl == "gather"
    cfg = moe_cfg(cap=99.0)
    p, _ = split_params(modules.init_moe(KEY, cfg))
    x = rand((4, 256, cfg.d_model), k=12, scale=0.5)
    run = RunConfig(policy=Policy(compute_dtype=jnp.float32))
    jx = jax.make_jaxpr(lambda x_: modules.apply_moe(p, cfg, run, x_)[0])(x)
    scatters = _count_eqns(jx.jaxpr,
                           lambda e: e.primitive.name == "scatter")
    assert len(scatters) == 1, [e.primitive.name for e in scatters]


def test_glu_block_candidates_fit_vmem_budget():
    cands = gmm_kernel.glu_block_candidates()
    assert cands and (128, 128) in cands
    for bm, bn in cands:
        assert gmm_kernel.glu_vmem_bytes(bm, 128, bn) \
            <= gmm_kernel.VMEM_BUDGET_BYTES
    # budget actually binds: a deliberately absurd tile must be rejected
    assert not gmm_kernel.glu_block_candidates(ms=(8192,), ns=(8192,))
