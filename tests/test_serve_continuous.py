"""Continuous-batching serving subsystem (DESIGN.md §7).

Covers the scheduler invariants (token-budget chunking, admission,
recycling), the fused sampler (greedy / top-k / top-p + the per-request
determinism contract), per-slot cache writes (vector cache_index),
chunked-prefill == whole-prefill logits, decode parity with the lockstep
engine, slot recycling never leaking KV across requests, and the
mixed-length Poisson acceptance trace on a smoke MoE config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.launch.serve import build_trace
from repro.models import modules, registry, stack
from repro.models.config import LayerSpec, ModelConfig, ShapeConfig
from repro.models.modules import Policy, RunConfig
from repro.pytree import split_params
from repro.serve import (BatchedServer, ContinuousBatchingEngine, GREEDY,
                         Request, SamplingParams, Scheduler, ServeMetrics,
                         make_continuous_program, make_serve_program)
from repro.serve.sampling import request_keys, sample_tokens

pytestmark = pytest.mark.serve  # CI job slice (see .github/workflows/ci.yml)

RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32), attn_impl="ref",
                moe_impl="gather")

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64)


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def tiny_params():
    return split_params(stack.init_model(jax.random.PRNGKey(0), TINY))[0]


def _prompt(seed, n, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, size=(n,)).tolist()


def _ref_greedy(params, cfg, run, prompt, n, eos=None):
    """Unbatched reference: full recompute each step, greedy."""
    seq = jnp.asarray(prompt, jnp.int32)[None]
    out = []
    for _ in range(n):
        logits, _, _ = stack.apply_model(params, cfg, run, seq)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        if eos is not None and nxt == eos:
            break
        seq = jnp.concatenate([seq, jnp.asarray([[nxt]], jnp.int32)], 1)
    return out


# ---------------------------------------------------------------------------
# Scheduler invariants (host-side, no jax)
# ---------------------------------------------------------------------------

def test_scheduler_chunking_budget_recycle():
    sched = Scheduler(2, max_len=64, prefill_chunk=8, token_budget=8)
    for rid, (plen, gen) in enumerate([(20, 3), (5, 2), (5, 1)]):
        sched.submit(Request(rid=rid, prompt=list(range(plen)),
                             max_new_tokens=gen))
    assert sched.queue_depth == 3

    # r0 is chunked 8 / 3 (budget-clipped) / 8 / 1 — never more than the
    # per-call budget, chunks strictly sequential.
    c = sched.plan_prefill(8)
    assert (c.slot, c.start, c.length, c.final) == (0, 0, 8, False)
    assert not sched.finish_prefill_chunk(c)
    c = sched.plan_prefill(3)  # budget smaller than a chunk clips it
    assert (c.start, c.length) == (8, 3)
    assert not sched.finish_prefill_chunk(c)
    c = sched.plan_prefill(99)  # chunk size still caps the slice
    assert (c.start, c.length) == (11, 8)
    assert not sched.finish_prefill_chunk(c)
    c = sched.plan_prefill(8)
    assert (c.start, c.length, c.final) == (19, 1, True)
    assert sched.finish_prefill_chunk(c)
    assert not sched.activate(c, first_token=42)  # 3 tokens to go
    assert sched.n_active == 1 and sched.results[0] == [42]

    # r1 takes the remaining slot; r2 must wait (no free slot).
    c1 = sched.plan_prefill(8)
    assert c1.slot == 1 and c1.final
    assert sched.finish_prefill_chunk(c1)
    assert not sched.activate(c1, first_token=7)
    assert sched.plan_prefill(8) is None  # r2 queued, both slots busy
    assert sched.queue_depth == 1

    # r1 finishes (gen=2) -> slot 1 recycled -> r2 admitted into it.
    assert sched.note_token(1, 9)
    assert sched.results[1] == [7, 9] and sched.free == [1]
    c2 = sched.plan_prefill(8)
    assert c2.slot == 1 and c2.request.rid == 2
    assert sched.finish_prefill_chunk(c2)
    assert sched.activate(c2, first_token=3)  # max_new == 1: done at once
    assert sched.results[2] == [3] and sched.free == [1]

    # r0 still live; finishes after its remaining tokens.
    assert not sched.note_token(0, 1)
    assert sched.note_token(0, 2)
    assert not sched.has_work()


def test_scheduler_rejects_oversize():
    sched = Scheduler(1, max_len=10, prefill_chunk=4)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=list(range(8)),
                             max_new_tokens=4))
    with pytest.raises(ValueError):
        sched.submit(Request(rid=1, prompt=[], max_new_tokens=4))
    assert sched.n_rejected == 2 and not sched.has_work()


# ---------------------------------------------------------------------------
# Sampler
# ---------------------------------------------------------------------------

def test_sampler_greedy_topk_topp():
    base = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 33), jnp.float32)
    rids = jnp.arange(4, dtype=jnp.int32)
    ngen = jnp.zeros((4,), jnp.int32)
    keys = request_keys(base, rids, ngen)
    amax = np.asarray(jnp.argmax(logits, -1))

    # temperature 0 -> greedy
    got = sample_tokens(logits, keys, jnp.zeros(4), jnp.zeros(4, jnp.int32),
                        jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(got), amax)
    # top_k = 1 -> argmax at any temperature
    got = sample_tokens(logits, keys, jnp.full((4,), 7.0),
                        jnp.ones(4, jnp.int32), jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(got), amax)
    # tiny top_p -> argmax survives alone
    got = sample_tokens(logits, keys, jnp.full((4,), 7.0),
                        jnp.zeros(4, jnp.int32), jnp.full((4,), 1e-6))
    np.testing.assert_array_equal(np.asarray(got), amax)
    # top_k cut: samples always land in the top-k set
    for trial in range(5):
        ks = request_keys(base, rids, jnp.full((4,), trial, jnp.int32))
        got = np.asarray(sample_tokens(logits, ks, jnp.full((4,), 2.0),
                                       jnp.full((4,), 5, jnp.int32),
                                       jnp.ones(4)))
        topk = np.asarray(jax.lax.top_k(logits, 5)[1])
        for b in range(4):
            assert got[b] in topk[b]


def _sample_tokens_argsort(logits, keys, temperature, top_k, top_p):
    """The sampler as it was before the key-value sort: argsort, then a
    gather of the scaled logits through the permutation. Kept as the
    reference the fused sampler must reproduce token for token."""
    V = logits.shape[-1]

    def one(lg, key, t, k, p):
        lg = lg.astype(jnp.float32)
        greedy = t <= 0.0
        scaled = lg / jnp.maximum(t, 1e-6)
        order = jnp.argsort(-scaled)
        vals = scaled[order]
        rank = jnp.arange(V)
        keep = rank < jnp.where(k <= 0, V, k)
        probs = jax.nn.softmax(vals)
        cum = jnp.cumsum(probs)
        keep &= (cum - probs) < p
        keep |= rank == 0
        vals = jnp.where(keep, vals, -jnp.inf)
        g = jax.random.gumbel(key, (V,), jnp.float32)
        pick = order[jnp.argmax(vals + g)]
        return jnp.where(greedy, jnp.argmax(lg), pick).astype(jnp.int32)

    return jax.vmap(one)(logits, keys, temperature, top_k, top_p)


# One row per mode: (temperature, top_k, top_p).
_SAMPLER_MODES = {
    "greedy": (0.0, 0, 1.0),
    "temperature": (0.8, 0, 1.0),
    "top_k_1": (1.5, 1, 1.0),
    "top_k_5": (1.5, 5, 1.0),
    "top_k_50": (1.5, 50, 1.0),
    "top_p_0.5": (1.5, 0, 0.5),
    "top_p_0.9": (1.5, 0, 0.9),
    "top_p_1.0": (1.5, 0, 1.0),
}
_PARITY_DRAWS = 4


@pytest.fixture(scope="module")
def sampler_parity():
    """Both samplers on one 8 x 4096 batch mixing every mode, over a few
    token indices. Logits sit on a 1/8 grid, so values tie everywhere, and
    each row's maximum is planted at three indices."""
    B, V = len(_SAMPLER_MODES), 4096
    rng = np.random.RandomState(11)
    logits = np.round(rng.randn(B, V) * 16) / 8
    for b in range(B):
        logits[b, rng.choice(V, 3, replace=False)] = logits[b].max() + 0.5
    logits = jnp.asarray(logits, jnp.float32)
    t, k, p = (jnp.asarray(c) for c in zip(*_SAMPLER_MODES.values()))
    k = k.astype(jnp.int32)
    rids = jnp.arange(B, dtype=jnp.int32)
    got, want = [], []
    for n in range(_PARITY_DRAWS):
        keys = request_keys(jax.random.PRNGKey(5), rids,
                            jnp.full((B,), n, jnp.int32))
        got.append(np.asarray(sample_tokens(logits, keys, t, k, p)))
        want.append(np.asarray(_sample_tokens_argsort(logits, keys, t, k, p)))
    return np.stack(got), np.stack(want)


@pytest.mark.parametrize("mode", list(_SAMPLER_MODES))
def test_sampler_matches_argsort_gather(sampler_parity, mode):
    """The key-value sort picks exactly what argsort + gather picked, in
    every row of a mixed batch, ties included."""
    got, want = sampler_parity
    row = list(_SAMPLER_MODES).index(mode)
    np.testing.assert_array_equal(got[:, row], want[:, row])


def test_sampler_lowers_to_one_sort_and_no_vocab_gather():
    """At the serving shape (64 slots x 32,000 vocabulary) the sampler
    holds one sort and no gather whose result spans B x V elements."""
    B, V = 64, 32000
    spec = jax.ShapeDtypeStruct
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), B))
    text = jax.jit(sample_tokens).lower(
        spec((B, V), jnp.float32), keys, spec((B,), jnp.float32),
        spec((B,), jnp.int32), spec((B,), jnp.float32)).as_text()
    gathers = [line for line in text.splitlines()
               if "stablehlo.gather" in line]
    assert gathers  # the per-row order[argmax] remains
    for line in gathers:
        dims = line.rsplit("->", 1)[1].split("tensor<", 1)[1].split("x")[:-1]
        assert int(np.prod([int(d) for d in dims])) < B * V, line
    assert text.count("stablehlo.sort") == 1


def test_sampler_deterministic_across_batch_composition():
    """key(rid, n) only — the same request samples the same token whatever
    its slot, neighbours, or batch size (DESIGN.md §7.4)."""
    base = jax.random.PRNGKey(3)
    row = jnp.asarray(np.random.RandomState(1).randn(17), jnp.float32)
    other = jnp.asarray(np.random.RandomState(2).randn(17), jnp.float32)
    t = jnp.asarray([1.3], jnp.float32)
    alone = sample_tokens(row[None], request_keys(base, jnp.asarray([7]),
                                                  jnp.asarray([3])),
                          t, jnp.zeros(1, jnp.int32), jnp.ones(1))
    batched = sample_tokens(
        jnp.stack([other, row]),
        request_keys(base, jnp.asarray([5, 7]), jnp.asarray([0, 3])),
        jnp.asarray([0.9, 1.3]), jnp.zeros(2, jnp.int32), jnp.ones(2))
    assert int(alone[0]) == int(batched[1])


# ---------------------------------------------------------------------------
# Per-slot cache writes (vector cache_index)
# ---------------------------------------------------------------------------

def test_vector_cache_index_matches_scalar(tiny_params):
    p, _ = split_params(modules.init_attention(jax.random.PRNGKey(1), TINY))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 1, TINY.d_model),
                    jnp.float32)
    pos = jnp.asarray([[3], [3]], jnp.int32)
    cache = modules.init_attention_cache(TINY, 2, 8, 0, jnp.float32)
    o_s, c_s = modules.apply_attention(p, TINY, RUN, x, pos, causal=True,
                                       cache=cache,
                                       cache_index=jnp.asarray(3, jnp.int32))
    o_v, c_v = modules.apply_attention(p, TINY, RUN, x, pos, causal=True,
                                       cache=cache,
                                       cache_index=jnp.asarray([3, 3],
                                                               jnp.int32))
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_v), atol=1e-6)
    for k in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(c_s[k]), np.asarray(c_v[k]))


def test_inactive_slot_writes_nothing():
    p, _ = split_params(modules.init_attention(jax.random.PRNGKey(1), TINY))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 1, TINY.d_model),
                    jnp.float32)
    pos = jnp.asarray([[2], [-1]], jnp.int32)
    cache = modules.init_attention_cache(TINY, 2, 8, 0, jnp.float32)
    _, c = modules.apply_attention(p, TINY, RUN, x, pos, causal=True,
                                   cache=cache,
                                   cache_index=jnp.asarray([2, -1],
                                                           jnp.int32))
    assert np.asarray(c["pos"][0])[2] == 2  # active row wrote its line
    np.testing.assert_array_equal(np.asarray(c["pos"][1]),
                                  np.full((8,), -1))  # dead row untouched
    np.testing.assert_array_equal(np.asarray(c["k"][1]), np.zeros_like(
        np.asarray(c["k"][1])))


# ---------------------------------------------------------------------------
# Chunked prefill == whole prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_whole(mesh1, tiny_params):
    prog = make_continuous_program(TINY, mesh1, RUN, n_slots=1, max_len=32)
    with mesh1:
        params = jax.device_put(tiny_params, prog.param_shardings)
    prompt = jnp.asarray(_prompt(5, 13), jnp.int32)[None]

    with mesh1:
        ps_w = prog.init_pstate()
        ps_w, l_w = prog.prefill_step(params, ps_w, prompt,
                                      jnp.asarray(0, jnp.int32))
        ps_c = prog.init_pstate()
        off = 0
        for c in (5, 5, 3):
            ps_c, l_c = prog.prefill_step(params, ps_c,
                                          prompt[:, off:off + c],
                                          jnp.asarray(off, jnp.int32))
            off += c

    np.testing.assert_allclose(np.asarray(l_w), np.asarray(l_c),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(ps_w), jax.tree.leaves(ps_c)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-5, atol=2e-5)
    # and both match the cache-free structural forward
    logits, _, _ = stack.apply_model(tiny_params, TINY, RUN, prompt)
    np.testing.assert_allclose(np.asarray(l_w), np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Decode parity with the lockstep engine
# ---------------------------------------------------------------------------

def test_active_mask_decode_parity_with_lockstep(mesh1, tiny_params):
    B, plen, gen = 2, 9, 6
    prompts = jnp.asarray([_prompt(11, plen), _prompt(12, plen)], jnp.int32)

    shape = ShapeConfig("t", "decode", plen + gen, B)
    sprog = make_serve_program(TINY, mesh1, RUN, shape, max_len=plen + gen)
    with mesh1:
        sparams = jax.device_put(tiny_params, sprog.param_shardings)
    server = BatchedServer(sprog, sparams, B, plen + gen)
    got = [server.submit_prefill(prompts)]
    for _ in range(gen - 1):
        got.append(server.step())
    lock = np.asarray(jnp.concatenate(got, axis=1))

    prog = make_continuous_program(TINY, mesh1, RUN, n_slots=B,
                                   max_len=plen + gen)
    with mesh1:
        params = jax.device_put(tiny_params, prog.param_shardings)
    reqs = [Request(rid=b, prompt=list(map(int, prompts[b])),
                    max_new_tokens=gen) for b in range(B)]
    eng = ContinuousBatchingEngine(
        prog, params, Scheduler(B, plen + gen, prefill_chunk=plen))
    res = eng.run(reqs)
    for b in range(B):
        assert res[b] == list(map(int, lock[b])), (b, res[b], lock[b])


# ---------------------------------------------------------------------------
# Slot recycling never leaks KV
# ---------------------------------------------------------------------------

def test_slot_recycle_no_kv_leak(mesh1, tiny_params):
    """Prefill request A into slot 0, finish it, admit B into slot 0: B's
    logits must match a fresh single-request run bit-for-bit-close."""
    prog = make_continuous_program(TINY, mesh1, RUN, n_slots=1, max_len=24)
    with mesh1:
        params = jax.device_put(tiny_params, prog.param_shardings)
    req_a = Request(rid=0, prompt=_prompt(21, 10), max_new_tokens=4)
    req_b = Request(rid=1, prompt=_prompt(22, 7), max_new_tokens=6)

    eng = ContinuousBatchingEngine(
        prog, params, Scheduler(1, 24, prefill_chunk=6), record_logits=True)
    res = eng.run([req_a, req_b])

    fresh = ContinuousBatchingEngine(
        prog, params, Scheduler(1, 24, prefill_chunk=6), record_logits=True)
    res_f = fresh.run([Request(rid=1, prompt=req_b.prompt,
                               max_new_tokens=6)])

    assert res[1] == res_f[1]
    assert len(eng.logits[1]) == len(fresh.logits[1]) == 6
    for a, b in zip(eng.logits[1], fresh.logits[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # and the recycled run still matches the unbatched reference
    assert res[1] == _ref_greedy(tiny_params, TINY, RUN, req_b.prompt, 6)


def test_chunked_prefill_ring_cache_wrap(mesh1):
    """Sliding-window arch: prefill chunks that cross the ring edge must
    WRAP (per-position modular scatter), not clamp. Window 8, chunks of 5
    over a 13-token prompt wrap twice; greedy continuation must match the
    cache-free reference."""
    cfg = ModelConfig(name="tiny-win", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64,
                      pattern=(LayerSpec(mixer="local_attn"),), window=8)
    params0 = split_params(stack.init_model(jax.random.PRNGKey(2), cfg))[0]
    prog = make_continuous_program(cfg, mesh1, RUN, n_slots=1, max_len=24)
    with mesh1:
        params = jax.device_put(params0, prog.param_shardings)
    req = Request(rid=0, prompt=_prompt(31, 13), max_new_tokens=6)
    eng = ContinuousBatchingEngine(
        prog, params, Scheduler(1, 24, prefill_chunk=5))
    res = eng.run([req])
    assert res[0] == _ref_greedy(params0, cfg, RUN, req.prompt, 6)


def test_oversized_request_rejected_not_fatal(mesh1, tiny_params):
    """An inadmissible request in a trace is rejected; the rest of the
    trace keeps serving."""
    prog = make_continuous_program(TINY, mesh1, RUN, n_slots=1, max_len=16)
    with mesh1:
        params = jax.device_put(tiny_params, prog.param_shardings)
    good = Request(rid=0, prompt=_prompt(41, 6), max_new_tokens=4)
    bad = Request(rid=1, prompt=_prompt(42, 20), max_new_tokens=4)
    eng = ContinuousBatchingEngine(
        prog, params, Scheduler(1, 16, prefill_chunk=8))
    res = eng.run([bad, good])
    assert eng.rejected == [1]
    assert sorted(res) == [0] and len(res[0]) == 4


# ---------------------------------------------------------------------------
# Acceptance: mixed-length Poisson trace on a smoke MoE config
# ---------------------------------------------------------------------------

def test_poisson_trace_moe_acceptance(mesh1):
    """Requests finish and free slots while others are mid-decode (asserted
    via per-request completion ticks), outputs match the unbatched greedy
    reference."""
    cfg = registry.smoke_config(registry.get_config("qwen3-moe-30b-a3b"))
    max_len = 30
    prog = make_continuous_program(cfg, mesh1, RUN, n_slots=2,
                                   max_len=max_len)
    params0, _ = split_params(stack.init_model(jax.random.PRNGKey(0), cfg))
    with mesh1:
        params = jax.device_put(params0, prog.param_shardings)

    trace = build_trace(seed=0, n=5, rate=0.6, prompt_len=16, gen=12,
                        vocab=cfg.vocab_size, sampling=GREEDY)
    metrics = ServeMetrics()
    eng = ContinuousBatchingEngine(
        prog, params, Scheduler(2, max_len, prefill_chunk=4),
        metrics=metrics)
    res = eng.run(trace)

    # every request completed with its full budget (no EOS in the trace)
    assert sorted(res) == [r.rid for r in trace]
    for r in trace:
        assert len(res[r.rid]) == r.max_new_tokens

    # continuous behaviour: more requests than slots; at least one request
    # was admitted after another finished (slot recycled) and at some tick
    # two requests decoded concurrently.
    tr = metrics.requests
    assert len(trace) > prog.n_slots
    recycled = [(i.rid, j.rid) for i in tr.values() for j in tr.values()
                if i.finish_tick is not None
                and j.first_token_tick is not None
                and j.first_token_tick > i.finish_tick]
    assert recycled, "no slot was recycled during the trace"
    assert metrics.summary()["max_concurrent_active"] >= 2

    # greedy parity with the unbatched reference, per request
    for r in trace:
        want = _ref_greedy(params0, cfg, RUN, r.prompt, r.max_new_tokens)
        assert res[r.rid] == want, (r.rid, res[r.rid], want)
