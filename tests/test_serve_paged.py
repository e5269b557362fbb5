"""Paged KV-cache serving (DESIGN.md §9).

Covers the block allocator invariants (no page shared by two live
requests, all-or-nothing allocation, copy-free recycle), the page-indexed
cache scatter/gather in ``apply_attention``, the Pallas paged decode
kernel vs the XLA gather fallback vs a dense oracle, chunked == whole
prefill THROUGH page tables, greedy parity of the paged engine against
the dense-cache reference engine (token-exact at temperature 0), slot
recycling under paging (the PR-2 no-leak contract, now with zero device
traffic on free), preemption-requeue determinism (sampler keys unchanged
after requeue), and the slot-lift acceptance: at equal simulated HBM the
paged engine sustains >= 1.5x the reservation engine's slot count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.launch.serve import build_trace
from repro.models import modules, registry, stack
from repro.models.config import LayerSpec, ModelConfig
from repro.models.modules import Policy, RunConfig
from repro.pytree import split_params
from repro.serve import (BlockAllocator, ContinuousBatchingEngine, GREEDY,
                         Request, SamplingParams, Scheduler, ServeMetrics,
                         make_continuous_program, pages_for)

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


def _paged_engine(cfg, mesh, params, *, n_slots, max_len, page_size=8,
                  n_pages=None, prefill_chunk=6, **eng_kw):
    prog = make_continuous_program(cfg, mesh, RUN, n_slots=n_slots,
                                   max_len=max_len, page_size=page_size,
                                   n_pages=n_pages)
    with mesh:
        p = jax.device_put(params, prog.param_shardings)
    alloc = BlockAllocator(prog.n_pages, prog.page_size, prog.max_pages)
    sched = Scheduler(n_slots, max_len, prefill_chunk=prefill_chunk,
                      allocator=alloc)
    return ContinuousBatchingEngine(prog, p, sched, **eng_kw)


# ---------------------------------------------------------------------------
# Block allocator (host-side, no jax)
# ---------------------------------------------------------------------------

def test_allocator_no_sharing_and_all_or_nothing():
    a = BlockAllocator(n_pages=6, page_size=8, max_pages_per_seq=4)
    assert a.pages_for(1) == 1 and a.pages_for(8) == 1 and a.pages_for(9) == 2
    assert a.allocate(0, 17)  # 3 pages
    assert a.allocate(1, 20)  # 3 pages
    a.check()
    assert a.n_free == 0 and a.pages_in_use == 6
    # all-or-nothing: a failing allocate/extend changes nothing
    assert not a.allocate(2, 1)
    assert not a.extend(0)
    a.check()
    assert 2 not in a.tables and a.n_free == 0
    # per-seq table bound binds even with free pages
    a.free(1)
    assert a.n_free == 3
    assert a.extend(0)  # 4th page — at the per-seq cap
    assert not a.extend(0)  # 5th would exceed max_pages_per_seq
    a.check()
    # copy-free recycle: free returns every page exactly once
    a.free(0)
    a.check()
    assert a.n_free == 6 and not a.tables
    # covers/n_lines track the owned frontier
    assert a.allocate(7, 10)
    assert a.covers(7, 15) and not a.covers(7, 16)
    assert a.n_lines(7) == 16
    t = a.table(7, pad_to=4)
    assert t.shape == (4,) and (t[:2] >= 0).all() and (t[2:] == -1).all()


def test_allocator_fits_pool_guard():
    a = BlockAllocator(n_pages=4, page_size=8, max_pages_per_seq=4)
    assert a.fits_pool(32) and not a.fits_pool(33)
    sched = Scheduler(1, max_len=64, prefill_chunk=8, allocator=a)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=_prompt(0, 40),
                             max_new_tokens=8))  # 48 lines > 32-line pool
    assert sched.n_rejected == 1


def test_no_page_shared_across_live_requests_during_trace(mesh1,
                                                          tiny_params):
    """Drive a tight-pool trace tick by tick and assert the allocator's
    exactly-once page ownership invariant at every step."""
    eng = _paged_engine(TINY, mesh1, tiny_params, n_slots=2, max_len=32,
                        n_pages=6)
    reqs = [Request(rid=i, prompt=_prompt(i, 9 + i), max_new_tokens=8)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    alloc = eng.sched.allocator
    while eng.sched.has_work() or eng._active.any():
        eng.tick()
        alloc.check()  # no page owned twice, none leaked
        # live page tables on the device side mirror the allocator
        for slot in np.nonzero(eng._active)[0]:
            rid = int(eng._rid[slot])
            np.testing.assert_array_equal(
                eng._ptab[slot], alloc.table(rid, eng.p.max_pages))
        assert eng.tick_count < 500
    assert alloc.pages_in_use == 0  # everything returned on finish


# ---------------------------------------------------------------------------
# Page-indexed cache scatter (apply_attention paged paths)
# ---------------------------------------------------------------------------

def test_paged_decode_write_matches_table_and_drops_dead():
    p, _ = split_params(modules.init_attention(jax.random.PRNGKey(1), TINY))
    x = jnp.asarray(np.random.RandomState(0).randn(3, 1, TINY.d_model),
                    jnp.float32)
    # slot 0 at position 9 (page 1, line 1), slot 1 dead, slot 2 at
    # position 3 (page 0, line 3); tables point into a 5-page pool.
    pt = jnp.asarray([[4, 2, -1], [-1, -1, -1], [0, -1, -1]], jnp.int32)
    pos = jnp.asarray([[9], [-1], [3]], jnp.int32)
    cache = modules.init_paged_attention_cache(TINY, 5, 8, jnp.float32)
    _, c = modules.apply_attention(p, TINY, RUN, x, pos, causal=True,
                                   cache=cache,
                                   cache_index=jnp.asarray([9, -1, 3],
                                                           jnp.int32),
                                   page_table=pt)
    assert int(c["pos"][2, 1]) == 9   # slot 0: page_table[0][1]=2 -> page 2
    assert int(c["pos"][0, 3]) == 3   # slot 2: page 0, line 3
    written = {(2, 1), (0, 3)}
    expect = np.full((5, 8), -1)
    for pg, ln in written:
        expect[pg, ln] = c["pos"][pg, ln]
    np.testing.assert_array_equal(np.asarray(c["pos"]), expect)


def test_paged_kernel_matches_xla_fallback_and_oracle():
    rng = np.random.RandomState(0)
    B, H, KH, hd, P, ps, MP = 3, 4, 2, 16, 10, 8, 4
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(P, ps, KH, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(P, ps, KH, hd), jnp.float32)
    pt = jnp.asarray([[3, 7, 1, -1], [0, -1, -1, -1], [5, 2, -1, -1]],
                     jnp.int32)
    q_pos = jnp.asarray([19, -1, 9], jnp.int32)

    for kw in ({}, dict(window=6), dict(softcap=5.0),
               dict(window=6, softcap=5.0)):
        ref = kops.paged_decode_attention(q, kp, vp, pt, q_pos,
                                          use_kernel=False, **kw)
        ker = kops.paged_decode_attention(q, kp, vp, pt, q_pos,
                                          use_kernel=True, interpret=True,
                                          **kw)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                                   rtol=2e-5, atol=2e-5)
        assert np.all(np.asarray(ref)[1] == 0)  # dead slot -> zeros

    # dense oracle: pages 0..2 hold positions 0..23 contiguously
    pt3 = jnp.asarray([[0, 1, 2, -1]], jnp.int32)
    qq = jnp.asarray(rng.randn(1, H, hd), jnp.float32)
    qp3 = jnp.asarray([13], jnp.int32)
    out = kops.paged_decode_attention(qq, kp, vp, pt3, qp3,
                                      use_kernel=False)
    k_lin = np.asarray(kp[:3]).reshape(24, KH, hd)[:14]
    v_lin = np.asarray(vp[:3]).reshape(24, KH, hd)[:14]
    qf = np.asarray(qq).reshape(KH, H // KH, hd)
    s = np.einsum("kgh,tkh->kgt", qf, k_lin) * hd ** -0.5
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("kgt,tkh->kgh", pr, v_lin).reshape(1, H, hd)
    np.testing.assert_allclose(np.asarray(out), o, rtol=1e-5, atol=1e-5)


def test_stale_lines_of_recycled_pages_unreachable():
    """A page carrying a PREVIOUS owner's K/V beyond the new owner's
    frontier contributes nothing: structural positions put stale lines
    past the causal mask (DESIGN.md §9.2)."""
    rng = np.random.RandomState(1)
    KH, hd, ps = 2, 16, 8
    kp = jnp.asarray(rng.randn(4, ps, KH, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(4, ps, KH, hd), jnp.float32)
    q = jnp.asarray(rng.randn(1, 4, hd), jnp.float32)
    pt = jnp.asarray([[2, 3]], jnp.int32)
    q_pos = jnp.asarray([11], jnp.int32)  # lines 0..11 live, 12..15 stale
    base = kops.paged_decode_attention(q, kp, vp, pt, q_pos,
                                       use_kernel=False)
    # scribble over the stale tail of page 3 (lines 4..7 = positions 12..15)
    kp2 = kp.at[3, 4:].set(99.0)
    vp2 = vp.at[3, 4:].set(-99.0)
    got = kops.paged_decode_attention(q, kp2, vp2, pt, q_pos,
                                      use_kernel=False)
    np.testing.assert_allclose(np.asarray(base), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Chunked prefill == whole prefill, through page tables
# ---------------------------------------------------------------------------

def test_paged_chunked_prefill_matches_whole(mesh1, tiny_params):
    prompt = jnp.asarray(_prompt(5, 13), jnp.int32)[None]
    # non-contiguous, differently-ordered physical pages for the two runs:
    # logits must not care WHERE the pages live
    pt_w = jnp.asarray([[5, 0, 3, -1]], jnp.int32)
    pt_c = jnp.asarray([[1, 4, 2, -1]], jnp.int32)

    def run_prefill(pt, chunks):
        state = stack.init_paged_decode_state(TINY, 1, 6, 8, jnp.float32)
        off = 0
        for c in chunks:
            logits, state, _ = stack.apply_model(
                tiny_params, TINY, RUN, prompt[:, off:off + c],
                decode_state=state, cache_index=jnp.asarray(off, jnp.int32),
                attend_to_cache=True, page_table=pt)
            off += c
        return logits[:, -1]

    l_w = run_prefill(pt_w, [13])
    l_c = run_prefill(pt_c, [5, 5, 3])
    np.testing.assert_allclose(np.asarray(l_w), np.asarray(l_c),
                               rtol=2e-5, atol=2e-5)
    # and both match the cache-free structural forward
    logits, _, _ = stack.apply_model(tiny_params, TINY, RUN, prompt)
    np.testing.assert_allclose(np.asarray(l_w), np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Engine parity vs the dense-cache reference engine
# ---------------------------------------------------------------------------

def test_paged_engine_greedy_parity_with_dense(mesh1, tiny_params):
    """Token-exact greedy parity (temperature 0) between the paged engine
    and the dense reservation engine over a multi-request trace."""
    reqs = [Request(rid=i, prompt=_prompt(40 + i, 9 + i), max_new_tokens=6)
            for i in range(3)]

    dense_prog = make_continuous_program(TINY, mesh1, RUN, n_slots=2,
                                         max_len=32)
    with mesh1:
        dp = jax.device_put(tiny_params, dense_prog.param_shardings)
    dense = ContinuousBatchingEngine(
        dense_prog, dp, Scheduler(2, 32, prefill_chunk=6))
    res_d = dense.run([Request(rid=r.rid, prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens)
                       for r in reqs])

    eng = _paged_engine(TINY, mesh1, tiny_params, n_slots=2, max_len=32)
    res_p = eng.run(reqs)
    assert res_p == res_d


def test_paged_engine_moe_poisson_acceptance(mesh1):
    """Smoke MoE arch through a Poisson trace on the paged engine: every
    request completes and matches the unbatched greedy reference."""
    cfg = registry.smoke_config(registry.get_config("qwen3-moe-30b-a3b"))
    max_len = 30
    params0, _ = split_params(stack.init_model(jax.random.PRNGKey(0), cfg))
    eng = _paged_engine(cfg, mesh1, params0, n_slots=2, max_len=max_len,
                        page_size=8, prefill_chunk=4)
    trace = build_trace(seed=0, n=4, rate=0.6, prompt_len=16, gen=10,
                        vocab=cfg.vocab_size, sampling=GREEDY)
    res = eng.run(trace)
    assert sorted(res) == [r.rid for r in trace]
    for r in trace:
        want = _ref_greedy(params0, cfg, RUN, r.prompt, r.max_new_tokens)
        assert res[r.rid] == want, (r.rid, res[r.rid], want)


def test_paged_windowed_arch_matches_reference(mesh1):
    """Sliding-window layers use the linear paged layout with the window
    enforced by masking: greedy output matches the cache-free reference
    (the paged path never evicts, so chunked prefill stays exact)."""
    cfg = ModelConfig(name="tiny-win", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64,
                      pattern=(LayerSpec(mixer="local_attn"),), window=8)
    params0 = split_params(stack.init_model(jax.random.PRNGKey(2), cfg))[0]
    eng = _paged_engine(cfg, mesh1, params0, n_slots=1, max_len=24,
                        prefill_chunk=5)
    req = Request(rid=0, prompt=_prompt(31, 13), max_new_tokens=6)
    res = eng.run([req])
    assert res[0] == _ref_greedy(params0, cfg, RUN, req.prompt, 6)


# ---------------------------------------------------------------------------
# Recycle-no-leak under paging (PR-2 contract, zero device traffic on free)
# ---------------------------------------------------------------------------

def test_paged_slot_recycle_no_kv_leak(mesh1, tiny_params):
    """Serve A then B through the same slot AND the same physical pages
    (1-slot engine, pool barely fitting one request): B's logits must
    match a fresh run bit-for-bit-close even though its pages still hold
    A's stale K/V beyond B's frontier."""
    req_a = Request(rid=0, prompt=_prompt(21, 10), max_new_tokens=4)
    req_b = Request(rid=1, prompt=_prompt(22, 7), max_new_tokens=6)

    eng = _paged_engine(TINY, mesh1, tiny_params, n_slots=1, max_len=24,
                        n_pages=3, record_logits=True)
    res = eng.run([req_a, req_b])
    # pool of exactly one sequence: B necessarily reused A's pages
    assert eng.sched.allocator.pages_in_use == 0

    fresh = _paged_engine(TINY, mesh1, tiny_params, n_slots=1, max_len=24,
                          n_pages=3, record_logits=True)
    res_f = fresh.run([Request(rid=1, prompt=req_b.prompt,
                               max_new_tokens=6)])

    assert res[1] == res_f[1]
    assert len(eng.logits[1]) == len(fresh.logits[1]) == 6
    for a, b in zip(eng.logits[1], fresh.logits[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert res[1] == _ref_greedy(tiny_params, TINY, RUN, req_b.prompt, 6)


# ---------------------------------------------------------------------------
# Preemption: requeue determinism (sampler keys unchanged)
# ---------------------------------------------------------------------------

def test_preemption_requeue_determinism(mesh1, tiny_params):
    """A pool too small for the trace forces preempt-newest; the resumed
    request replays prompt+generated and continues sampling at key(rid,
    n_done) — results must equal the ample-pool run token for token, under
    REAL sampling (temperature/top-k/top-p), not just greedy."""
    sp = SamplingParams(temperature=0.8, top_k=5, top_p=0.9)
    reqs = [Request(rid=i, prompt=_prompt(60 + i, 9 + i),
                    max_new_tokens=12, sampling=sp) for i in range(3)]

    ample = _paged_engine(TINY, mesh1, tiny_params, n_slots=2, max_len=32)
    res_a = ample.run([Request(rid=r.rid, prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens,
                               sampling=sp) for r in reqs])
    assert ample.sched.n_preempted == 0

    tight = _paged_engine(TINY, mesh1, tiny_params, n_slots=2, max_len=32,
                          n_pages=5)
    res_t = tight.run(reqs)
    assert tight.sched.n_preempted > 0, "pool was not tight enough"
    assert res_t == res_a
    tight.sched.allocator.check()


def test_serve_driver_exits_nonzero_on_dropped_requests(monkeypatch):
    """launch/serve.py must FAIL (non-zero) when any arch drops or leaves
    a request unfinished, so the CI serve-smoke step actually gates."""
    from repro.launch import serve as serve_mod
    monkeypatch.setattr(serve_mod, "serve_arch",
                        lambda arch, args, serve_cfg=None: {"ok": arch == serve_mod.
                                            SMOKE_ARCHS[0]})
    assert serve_mod.main(["--smoke"]) == 1
    monkeypatch.setattr(serve_mod, "serve_arch",
                        lambda arch, args, serve_cfg=None: {"ok": True})
    assert serve_mod.main(["--smoke"]) == 0


def test_serve_driver_runs_a_given_trace():
    """serve_arch serves a caller's trace in place of its generated one:
    prompts of whole prefill chunks all finish on the paged engine."""
    from repro.launch import serve as serve_mod
    args = serve_mod.parse_args([
        "--arch", "mixtral-d2", "--smoke", "--paged", "--slots", "2",
        "--prompt-len", "32", "--gen", "4", "--prefill-chunk", "16"])
    rng = np.random.default_rng(0)
    trace = [Request(rid=i, prompt=rng.integers(0, 200,
                                                16 * (1 + i % 2)).tolist(),
                     max_new_tokens=4, arrival=float(i)) for i in range(3)]
    s = serve_mod.serve_arch(args.arch, args, trace=trace)
    assert s["ok"] and s["n_requests"] == 3
    assert s["n_generated_tokens"] == 12
    assert "paged" in s


# ---------------------------------------------------------------------------
# Acceptance: slot lift at fixed simulated HBM
# ---------------------------------------------------------------------------

def test_paged_slot_lift_at_fixed_hbm(mesh1, tiny_params):
    """With the pool capped at the reservation engine's HBM (slots_ref x
    max_len cache lines), the paged engine sustains >= 1.5x slots_ref
    concurrent requests on a mixed-length trace."""
    slots_ref, max_len, ps = 2, 32, 8
    budget_pages = slots_ref * max_len // ps  # equal simulated HBM
    eng = _paged_engine(TINY, mesh1, tiny_params, n_slots=3 * slots_ref,
                        max_len=max_len, page_size=ps,
                        n_pages=budget_pages, prefill_chunk=8,
                        metrics=ServeMetrics())
    trace = build_trace(seed=3, n=10, rate=2.0, prompt_len=12, gen=8,
                        vocab=TINY.vocab_size, sampling=GREEDY)
    res = eng.run(trace)
    assert sorted(res) == [r.rid for r in trace]
    sustained = eng.metrics.summary()["max_concurrent_active"]
    assert sustained >= 1.5 * slots_ref, \
        f"paged engine sustained {sustained} slots at the HBM budget " \
        f"that backs {slots_ref} reserved slots"
    assert eng.page_peak <= budget_pages
