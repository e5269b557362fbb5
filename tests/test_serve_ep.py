"""Expert-parallel decode serving (DESIGN.md §11).

Covers the EP decode contract end to end: placement validation (an
ep_size that does not divide the expert count is REJECTED, never
truncated), greedy token-exact parity of the EP-sharded engine against
the replicated ``ContinuousBatchingEngine`` on a MoE Poisson trace,
token-exactness ACROSS a mid-trace placement re-balance (page/slot state
survives the params swap), the routing-EMA drift trigger, the
heterogeneity-aware placement planner strictly beating round-robin on a
Zipf-routed trace in ``simulate_serve_trace``, the per-device HBM
accounting, and the kernels' small-M auto-route evaluating its crossover
at the PER-SHARD group count G/ep_size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner
from repro.core import simulator as sim
from repro.core.asym_ea import asym_ea_place, round_robin_placement
from repro.core.hardware import A40, V100
from repro.core.profiler import ep_decode_step_time, expert_param_bytes
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.launch.serve import build_trace
from repro.models import registry, stack
from repro.models.modules import Policy, RunConfig
from repro.pytree import split_params
from repro.serve import (ContinuousBatchingEngine, GREEDY, Scheduler,
                         make_continuous_program)
from repro.serve.ep_decode import (EPContinuousBatchingEngine,
                                   EPDecodeConfig, balanced_placement,
                                   ep_hbm_budget, placement_to_perm,
                                   validate_ep_config)
from repro.serve.metrics import RoutingEMA

pytestmark = pytest.mark.ep  # CI ep-smoke job slice

RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32), attn_impl="ref",
                moe_impl="gather")


@pytest.fixture(scope="module")
def moe_cfg():
    return registry.smoke_config(registry.get_config("qwen3-moe-30b-a3b"))


@pytest.fixture(scope="module")
def moe_params(moe_cfg):
    return split_params(stack.init_model(jax.random.PRNGKey(0), moe_cfg))[0]


@pytest.fixture(scope="module")
def trace(moe_cfg):
    return build_trace(seed=0, n=4, rate=0.6, prompt_len=10, gen=8,
                       vocab=moe_cfg.vocab_size, sampling=GREEDY)


@pytest.fixture(scope="module")
def ref_results(moe_cfg, moe_params, trace):
    """The replicated engine's greedy output on the shared trace."""
    mesh = make_mesh((1, 1), ("data", "model"))
    prog = make_continuous_program(moe_cfg, mesh, RUN, n_slots=3, max_len=24)
    eng = ContinuousBatchingEngine(prog, moe_params,
                                   Scheduler(3, 24, prefill_chunk=4))
    return eng.run(list(trace))


@pytest.fixture(scope="module")
def ep_mesh():
    return make_mesh((1, 2), ("data", "model"))


@pytest.fixture(scope="module")
def ep_prog(moe_cfg, ep_mesh):
    return make_continuous_program(
        moe_cfg, ep_mesh, RUN, n_slots=3, max_len=24,
        ep=EPDecodeConfig(ep_size=2, n_chunks=2))


# -- placement algebra -------------------------------------------------------

def test_round_robin_placement():
    pl = round_robin_placement(8, 2)
    assert pl == ((0, 2, 4, 6), (1, 3, 5, 7))
    assert sorted(e for s in pl for e in s) == list(range(8))
    with pytest.raises(ValueError):
        round_robin_placement(8, 3)  # 3 does not divide 8
    with pytest.raises(ValueError):
        round_robin_placement(8, 0)


def test_asym_ea_place_hot_to_fast():
    # One hot expert, seven cold; shard 1 is the fast class.
    load = [0.02, 0.65, 0.02, 0.05, 0.05, 0.05, 0.08, 0.08]
    pl = asym_ea_place(load, [1.0, 3.0], 4)
    assert sorted(e for s in pl for e in s) == list(range(8))
    assert all(len(s) == 4 for s in pl)  # exact cardinality, never ragged
    assert 1 in pl[1], "hottest expert must land on the fast shard"


def test_asym_ea_place_validation():
    with pytest.raises(ValueError):
        asym_ea_place([0.5, 0.5, 0.5], [1.0, 1.0], 2)  # 3 != 2*2
    with pytest.raises(ValueError):
        asym_ea_place([0.5, 0.5], [1.0, 0.0], 1)  # non-positive speed


def test_balanced_placement_uniform_hist_is_exact_partition():
    pl = balanced_placement([1.0 / 8] * 8, 2)
    assert sorted(e for s in pl for e in s) == list(range(8))
    assert all(len(s) == 4 for s in pl)


def test_placement_to_perm_rejects():
    with pytest.raises(ValueError):
        placement_to_perm(((0, 1, 2, 3),), 8, 2)  # wrong shard count
    with pytest.raises(ValueError):
        placement_to_perm(((0, 1, 2), (3, 4, 5, 6, 7)), 8, 2)  # ragged
    with pytest.raises(ValueError):
        placement_to_perm(((0, 1, 2, 3), (3, 4, 5, 6)), 8, 2)  # dup/missing


def test_validate_ep_config_rejects(moe_cfg, ep_mesh):
    dense = registry.smoke_config(registry.get_config("llama3.2-3b"))
    with pytest.raises(ValueError):
        validate_ep_config(dense, ep_mesh, EPDecodeConfig(ep_size=2))
    # 3 does not divide 8 experts: rejected, never truncated.
    with pytest.raises(ValueError, match="truncate"):
        validate_ep_config(moe_cfg, ep_mesh, EPDecodeConfig(ep_size=3))
    mesh1 = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        validate_ep_config(moe_cfg, mesh1, EPDecodeConfig(ep_size=2))
    with pytest.raises(ValueError):
        validate_ep_config(moe_cfg, ep_mesh,
                           EPDecodeConfig(ep_size=2, n_chunks=0))
    bad = EPDecodeConfig(ep_size=2,
                         placement=((0, 1, 2, 3), (3, 4, 5, 6)))
    with pytest.raises(ValueError):
        validate_ep_config(moe_cfg, ep_mesh, bad)


# -- routing EMA -------------------------------------------------------------

def test_routing_ema_drift():
    ema = RoutingEMA(4, decay=0.5)
    uniform = [0.25] * 4
    assert ema.drift(uniform) == 0.0  # empty EMA reads as uniform
    for _ in range(8):
        ema.update(np.array([8.0, 0.0, 0.0, 0.0]))
    m = ema.merged()
    assert np.isclose(m.sum(), 1.0)
    assert m[0] > 0.9
    assert ema.drift(uniform) > 0.5  # skew is visible as TV distance
    assert ema.drift(m) < 1e-9


# -- HBM accounting ----------------------------------------------------------

@pytest.mark.parametrize("ep_size", [2, 4])
def test_ep_hbm_budget_reduction(ep_size):
    cfg = registry.get_config("qwen3-moe-30b-a3b")
    b = ep_hbm_budget(cfg, hbm_bytes=A40.mem_bytes, ep_size=ep_size,
                      page_size=16)
    assert b["expert_bytes_total"] == expert_param_bytes(cfg)
    assert b["hbm_reduction"] >= ep_size  # exact partition of the stack
    # Freed expert HBM turns into KV pages: the EP pool can only grow.
    assert b["pool_pages_ep"] >= b["pool_pages_replicated"]


# -- planner: heterogeneity-aware placement ----------------------------------

def test_planned_beats_round_robin_on_zipf_trace():
    cfg = registry.get_config("qwen3-moe-30b-a3b")
    reqs, hist = sim.zipf_poisson_trace(0, 40, 2.0, 256, 128,
                                        cfg.n_experts, zipf_s=1.4)
    plan = planner.plan_ep_decode_group(
        cfg, (A40, V100), hist, reqs, decode_batch=8, ctx=1024,
        n_chunks=2, link_bw=min(A40.link_bw, V100.link_bw))
    assert plan.placement != plan.uniform
    assert plan.placement_ratio > 1.0        # per-step analytical win
    assert plan.placement_ratio_sim > 1.0    # strictly beats round-robin
    assert plan.predicted.makespan < plan.predicted_uniform.makespan
    assert plan.hbm_reduction >= plan.ep_size
    # The hottest expert sits on the higher-HBM-bandwidth shard.
    hot = max(range(cfg.n_experts), key=lambda e: plan.hist[e])
    fast = max(range(2), key=lambda j: (A40, V100)[j].hbm_bw)
    assert hot in plan.placement[fast]


def test_ep_decode_step_time_prefers_hot_on_fast():
    cfg = registry.get_config("qwen3-moe-30b-a3b")
    hist = [0.5, 0.3] + [0.2 / 6] * 6  # experts 0,1 hot
    hot_on_fast = ((2, 3, 4, 5), (0, 1, 6, 7))  # V100 (fast HBM) = shard 1
    hot_on_slow = ((0, 1, 6, 7), (2, 3, 4, 5))
    t_good = ep_decode_step_time(cfg, 8, 1024, hot_on_fast, (A40, V100),
                                 hist)
    t_bad = ep_decode_step_time(cfg, 8, 1024, hot_on_slow, (A40, V100),
                                hist)
    assert t_good < t_bad


def test_zipf_trace_is_deterministic_and_normalized():
    r1, h1 = sim.zipf_poisson_trace(7, 10, 1.0, 64, 32, 8)
    r2, h2 = sim.zipf_poisson_trace(7, 10, 1.0, 64, 32, 8)
    assert r1 == r2 and h1 == h2
    assert abs(sum(h1) - 1.0) < 1e-9
    assert len({round(x, 12) for x in h1}) > 1  # actually skewed


# -- kernels: small-M auto-route at per-shard group count --------------------

def _moe_inputs(M, G, d=16, f=32, seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    x = jax.random.normal(ks[0], (M, d), jnp.float32)
    wg = jax.random.normal(ks[1], (G, d, f), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (G, d, f), jnp.float32) * 0.1
    wo = jax.random.normal(ks[3], (G, f, d), jnp.float32) * 0.1
    sizes = jnp.full((G,), M // G, jnp.int32)
    return x, wg, wu, wo, sizes


def test_moe_ffn_autoroute_uses_per_shard_groups(monkeypatch):
    # M=256, G=8, block_m=128: globally 256*7 > 8*128 (packed), but at
    # ep_size=4 the per-shard count Gs=2 gives 256*1 <= 2*128 (dense).
    calls = []
    real = ops.moe_ffn_group_dense
    monkeypatch.setattr(ops, "moe_ffn_group_dense",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    x, wg, wu, wo, sizes = _moe_inputs(256, 8)
    ops.moe_ffn(x, wg, wu, wo, sizes, small_m=None, ep_size=4,
                interpret=True, use_kernel=False)
    assert calls, "per-shard crossover must take the group-dense route"
    calls.clear()
    ops.moe_ffn(x, wg, wu, wo, sizes, small_m=None, ep_size=1,
                interpret=True, use_kernel=False)
    assert not calls, "global crossover must stay on the packed pipeline"


def test_packed_multi_autoroute_uses_per_shard_groups(monkeypatch):
    calls = []
    real = ops._packed_group_dense
    monkeypatch.setattr(ops, "_packed_group_dense",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    buf = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 16), jnp.float32)
    _, wg, wu, wo, _ = _moe_inputs(256, 8)
    ops.moe_ffn_packed_multi([buf], [wg], [wu], [wo], small_m=None,
                             ep_size=4, interpret=True, use_kernel=False)
    assert calls
    calls.clear()
    ops.moe_ffn_packed_multi([buf], [wg], [wu], [wo], small_m=None,
                             ep_size=1, interpret=True, use_kernel=False)
    assert not calls


# -- the EP engine: token-exactness ------------------------------------------

def test_ep_engine_token_exact_vs_replicated(moe_params, trace, ref_results,
                                             ep_prog):
    eng = EPContinuousBatchingEngine(ep_prog, moe_params,
                                     Scheduler(3, 24, prefill_chunk=4))
    assert eng.run(list(trace)) == ref_results
    assert eng.ema.n_updates > 0  # routed-copy histograms did flow
    assert np.isclose(eng.ema.merged().sum(), 1.0)


def test_ep_engine_token_exact_across_rebalance(moe_params, trace,
                                                ref_results, ep_prog):
    """Mid-trace re-placement (params-only swap) must not disturb page
    tables, slot state, or sampling — the generated streams stay
    bit-identical to the replicated engine's."""
    eng = EPContinuousBatchingEngine(ep_prog, moe_params,
                                     Scheduler(3, 24, prefill_chunk=4))
    pending = sorted(trace, key=lambda r: r.arrival)
    n = 0
    while pending or eng.sched.has_work() or eng._active.any():
        while pending and pending[0].arrival <= eng.tick_count:
            eng.submit(pending.pop(0))
        eng.tick()
        n += 1
        if n == 5:  # mid-trace: slots live, pages allocated
            assert eng.rebalance(tuple(reversed(eng.placement)))
        assert n < 500
    assert eng.n_rebalances == 1
    assert eng.results == ref_results


# -- the EP engine holds the compute view ------------------------------------

def test_ep_engine_holds_compute_view_across_rebalance(moe_cfg, moe_params,
                                                       trace, ep_mesh):
    """With a bf16 compute policy the EP engine's expert stacks are in bf16
    after placement and after a re-balance (the view is cast after
    placing), the router stays f32, and the served tokens equal the
    replicated bf16 engine's."""
    run = RunConfig(attn_impl="ref", moe_impl="gather")
    mesh = make_mesh((1, 1), ("data", "model"))
    prog = make_continuous_program(moe_cfg, mesh, run, n_slots=3,
                                   max_len=24)
    want = ContinuousBatchingEngine(
        prog, moe_params, Scheduler(3, 24, prefill_chunk=4)).run(list(trace))

    ep_prog = make_continuous_program(
        moe_cfg, ep_mesh, run, n_slots=3, max_len=24,
        ep=EPDecodeConfig(ep_size=2, n_chunks=2))
    eng = EPContinuousBatchingEngine(ep_prog, moe_params,
                                     Scheduler(3, 24, prefill_chunk=4))

    def ffn_dtypes():
        ffn = eng.params["blocks"]["pos0"]["ffn"]
        return {k: v.dtype for k, v in ffn.items()}

    placed = {"wi_gate": jnp.bfloat16, "wi_up": jnp.bfloat16,
              "wo": jnp.bfloat16, "router": jnp.float32,
              "eslot": jnp.int32}
    assert ffn_dtypes() == placed
    pending = sorted(trace, key=lambda r: r.arrival)
    n = 0
    while pending or eng.sched.has_work() or eng._active.any():
        while pending and pending[0].arrival <= eng.tick_count:
            eng.submit(pending.pop(0))
        eng.tick()
        n += 1
        if n == 5:
            assert eng.rebalance(tuple(reversed(eng.placement)))
            assert ffn_dtypes() == placed
        assert n < 500
    assert eng.n_rebalances == 1
    assert eng.results == want
