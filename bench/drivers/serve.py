"""Serving driver: an open-loop client of the continuous-batching engine
that ``serve.config.build_deployment`` builds.

Requests are submitted when they fall due on the wall clock, whether or
not the engine keeps up, and every latency counts from the due time. The
client calls ``engine.tick()`` while there is work and sleeps until the
next due time when there is none. After the window closes no request is
submitted; the ones already due run to completion, for at most
``drain_s`` seconds, and one that never completes counts as failed.

What is compared, for a sample of the window's requests drawn from the
seed before it opens (the one with the longest output among them):
every served token against the reference's best logit at its position,
and the program's own logits there against the reference's. The logits
are the ones the timed path computes: the prefill's last row, held as it
is returned, and each decode row, taken from the decode step's output on
the device by one small gather per sampled live slot.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import harness
from flops import prefill_chunk_flops, serve_token_flops
from reference import mixtral as ref
from traffic import generate


class _Counters:
    def __init__(self):
        self.flops = 0.0
        self.tick_s = 0.0
        self.ticks = 0
        self.queue_depth = []


def _instrument(engine, m, spans, counters, live, tracked, rows):
    """Wrap the engine's prefill-chunk and decode calls to count the
    tokens they process (``live[0]`` is whether the window is open), and
    keep the logits of the ``tracked`` requests' tokens in ``rows``
    (rid -> {token index: [V] or [1, V] device array})."""
    import jax

    run_chunk, decode_once = engine._run_prefill_chunk, engine._decode_once
    admit, decode_step = engine._admit, engine.p.decode_step
    take = jax.jit(lambda x, i: x[i])

    def prefill(chunk):
        if live[0]:
            counters.flops += prefill_chunk_flops(m, chunk.start,
                                                  chunk.length)
        with spans.span("prefill"):
            run_chunk(chunk)

    def decode():
        if live[0]:
            for r in engine.sched.running.values():
                counters.flops += serve_token_flops(
                    m, len(r.request.prompt) + r.n_generated, True)
        with spans.span("decode"):
            decode_once()

    def admit_(chunk, last_logits):
        if chunk.request.rid in tracked:
            rows.setdefault(chunk.request.rid, {})[chunk.n_done] = \
                last_logits
        admit(chunk, last_logits)

    def decode_step_(*args):
        out = decode_step(*args)
        for slot in np.nonzero(engine._active)[0]:
            rid = int(engine._rid[slot])
            if rid in tracked:
                rows[rid][int(engine._ngen[slot])] = take(out[2],
                                                          np.int32(slot))
        return out

    engine._run_prefill_chunk = prefill
    engine._decode_once = decode
    engine._admit = admit_
    engine.p = dataclasses.replace(engine.p, decode_step=decode_step_)


def _busy(engine) -> bool:
    return engine.sched.has_work() or engine.sched.n_active > 0


def session(cell: harness.Cell, ctx) -> dict:
    """Build, warm up, and serve the window's requests to completion."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import stack
    from repro.models.modules import RunConfig
    from repro.pytree import split_params
    from repro.serve.config import PagedCfg, ServeConfig, build_deployment
    from repro.serve.sampling import GREEDY
    from repro.serve.scheduler import Request

    m, mix, spans = cell.config, cell.mix, ctx.spans
    e = mix["engine"]
    cfg = harness.model_config(m)
    mesh = harness.make_mesh((1, 1), ctx.devices)
    run_cfg = RunConfig(attn_impl="ref", moe_impl="gather")
    seed = harness.seed32(ctx.seed)
    # The sampling key stays fixed: greedy decoding never reads it, and a
    # key taken from the run's seed would be a new constant in every
    # program, so no seed's run would find them in the compile cache.
    sc = ServeConfig(slots=e["slots"], max_len=e["max_len"],
                     prefill_chunk=e["prefill_chunk"],
                     token_budget=e.get("token_budget"), seed=0,
                     temperature=0.0,
                     paged=PagedCfg(enabled=True, page_size=e["page_size"],
                                    pool_pages=e.get("pool_pages")))
    with mesh:
        params = jax.jit(
            lambda key: split_params(stack.init_model(key, cfg))[0],
            out_shardings=NamedSharding(mesh, P()))(
                jax.random.PRNGKey(np.uint32(seed)))

    times: dict = {}
    tokens: dict = {}

    def on_token(rid, tok, finished):
        times.setdefault(rid, []).append(time.perf_counter())
        tokens.setdefault(rid, []).append(int(tok))

    engine = build_deployment(cfg, mesh, run_cfg, sc, params=params,
                              on_token=on_token)
    reqs = generate.serve_requests(mix, ctx.seed, ctx.seconds,
                                   cfg.vocab_size)
    pick = pick_for_check(mix, ctx.seed, reqs)
    warm = generate.warmup_prompt_lengths(mix)
    counters, live, rows = _Counters(), [False], {}
    tracked = {r.rid for r in pick} | {-1 - j for j in range(len(warm))}
    _instrument(engine, m, spans, counters, live, tracked, rows)

    # Warm-up: one request per last-chunk length the mix can produce, and
    # two decode steps each: every program the window runs.
    for j, n in enumerate(warm):
        engine.submit(Request(rid=-1 - j, prompt=[1] * n, max_new_tokens=3,
                              sampling=GREEDY))
    while _busy(engine):
        engine.tick()
    jax.block_until_ready(engine.state)
    times.clear()
    tokens.clear()
    rows.clear()
    tracked -= {-1 - j for j in range(len(warm))}
    setup_s = time.perf_counter() - ctx.t_start

    n, i, late = len(reqs), 0, []
    drain = float(mix.get("drain_s", 60.0))
    page_ticks0 = len(engine._page_ticks)
    with harness.Window(ctx.compiles, ctx.trace_dir) as w:
        t0 = w.t0
        live[0] = True
        while True:
            now = time.perf_counter() - t0
            if live[0] and now >= ctx.seconds:
                live[0] = False
                w.close()
            while i < n and reqs[i].arrival_s <= now:
                r = reqs[i]
                with spans.span("submit"):
                    engine.submit(Request(rid=r.rid, prompt=r.prompt,
                                          max_new_tokens=r.max_new_tokens,
                                          sampling=GREEDY))
                late.append(now - r.arrival_s)
                i += 1
            if _busy(engine):
                t_tick = time.perf_counter()
                with spans.span("tick"):
                    engine.tick()
                if live[0]:
                    counters.tick_s += time.perf_counter() - t_tick
                    counters.ticks += 1
                    counters.queue_depth.append(engine.sched.queue_depth)
            elif i >= n:
                break
            else:
                with spans.span("idle"):
                    time.sleep(max(0.0, min(0.002, reqs[i].arrival_s - now)))
            if now > ctx.seconds + drain:
                break
        w.close()
        t_end = time.perf_counter()

    peak = harness.memory_peak(ctx.devices)
    pages = [p for p, _ in engine._page_ticks[page_ticks0:]]
    logits = {rid: np.stack([np.asarray(got[j]).reshape(-1)
                             for j in range(len(tokens.get(rid, [])))])
              for rid, got in rows.items()
              if all(j in got for j in range(len(tokens.get(rid, []))))}
    harness.free(engine.state, params, rows)
    engine.state = None

    # Latencies of every request due in the window, from its due time.
    ttft, itl, failed, in_window = [], [], 0, 0
    for r in reqs:
        due = t0 + r.arrival_s
        ts = times.get(r.rid, [])
        in_window += sum(1 for t in ts if t <= t0 + ctx.seconds)
        if len(ts) < r.max_new_tokens:
            failed += 1
            ttft.append((ts[0] if ts else t_end) - due)
            continue
        ttft.append(ts[0] - due)
        itl += list(np.diff(ts))
    pct = generate.percentile
    harness.log(f"client: {n} requests due, {failed} failed, lateness "
                f"p50={pct(late, 0.5) * 1e3:.3f} ms max="
                f"{max(late) * 1e3:.3f} ms; {counters.ticks} ticks in the "
                f"window; drained {t_end - t0 - ctx.seconds:.2f} s after it")

    return {
        "pick": pick, "tokens": tokens, "logits": logits,
        "setup_s": setup_s,
        "end_to_end": {
            "serve_tokens_per_s": in_window / ctx.seconds,
            "ttft_p95_ms": pct(ttft, 0.95) * 1e3,
            "itl_p95_ms": pct(itl, 0.95) * 1e3 if itl else float("nan"),
        },
        "attempted": n,
        "failed": failed,
        "memory_peak_bytes": peak,
        "window_s": ctx.seconds,
        "counters": {"flops": counters.flops, "tick_s": counters.tick_s,
                     "ticks": counters.ticks,
                     "queue_depth": counters.queue_depth},
        "notes": {"compiles_in_window": ctx.compiles.count,
                  "drained_s": t_end - t0 - ctx.seconds,
                  "lateness_p50_ms": pct(late, 0.5) * 1e3,
                  "lateness_max_ms": max(late) * 1e3,
                  "ttft_p50_ms": pct(ttft, 0.5) * 1e3,
                  "kv_pages_peak": max(pages, default=0),
                  "kv_pages": engine.p.n_pages,
                  "itl_p50_ms": pct(itl, 0.5) * 1e3 if itl else None},
    }


def run(cell: harness.Cell, ctx) -> dict:
    out = session(cell, ctx)
    out["checks"] = _check(cell, ctx.seed, out.pop("pick"),
                           out.pop("tokens"), out.pop("logits"))
    return out


def pick_for_check(mix: dict, seed: int, reqs) -> list:
    """Requests to compare, drawn before the window: the one with the
    longest output, then others drawn from the seed until ``check.tokens``
    served tokens and ``check.requests`` requests are covered."""
    longest = max(reqs, key=lambda r: r.max_new_tokens)
    rest = [r for r in reqs if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    pick, total = [longest], longest.max_new_tokens
    for j in order:
        if total >= mix["check"]["tokens"] and \
                len(pick) >= mix["check"].get("requests", 1):
            break
        pick.append(rest[j])
        total += rest[j].max_new_tokens
    return pick


def readings(cell, seed, pick, tokens, logits, control=None) -> dict:
    """The compared numbers over the picked requests that finished with
    every logit row kept: the mean gap of the served tokens under the
    reference's best logit, and the larger of the median distance of the
    program's logits from the reference's over the prefill rows (each
    request's first token) and over the decode rows (the rest). With
    ``control``, that precision's forward in the program's place."""
    m, mix = cell.config, cell.mix
    done = [r for r in pick if r.rid in logits
            and len(tokens[r.rid]) == r.max_new_tokens]
    if not done:
        return {"served_logit_gap_mean": float("inf"),
                "logit_distance": float("inf"), "tokens": 0}
    got = ref.served_readings(
        m, harness.seed32(seed), mix["engine"]["max_len"],
        mix["output"]["max"],
        [(r.prompt, tokens[r.rid], logits[r.rid]) for r in done],
        control=control)
    gaps = np.concatenate([g for g, _ in got])
    first = np.array([d[0] for _, d in got])
    later = np.concatenate([d[1:] for _, d in got])
    return {"served_logit_gap_mean": float(gaps.mean()),
            "logit_distance": max(float(np.median(first)),
                                  float(np.median(later)) if len(later)
                                  else 0.0),
            "widest_gap": float(gaps.max()),
            "distance_max": float(max(first.max(), later.max(initial=0.0))),
            "requests": len(done), "tokens": len(gaps)}


def _check(cell, seed, pick, tokens, logits) -> list:
    """The numbers the cell's limits name, of the served tokens' mean gap
    (which an altered token fails) and the logits' median distance (meant
    for a lower precision); the others are logged. (The widest gap is
    logged too: in bfloat16 a near tie in the router or the head flips a
    few tokens by up to about 3, as far as the float8 control flips its
    widest.)"""
    lim = cell.workload["limits"]
    t_ref = time.perf_counter()
    got = readings(cell, seed, pick, tokens, logits)
    harness.log(f"reference: {got.get('requests', 0)} requests, "
                f"{got['tokens']} served tokens, widest gap "
                f"{got.get('widest_gap')}, largest distance "
                f"{got.get('distance_max')}, "
                f"{time.perf_counter() - t_ref:.1f} s")
    for k in ("served_logit_gap_mean", "logit_distance"):
        if k not in lim:
            harness.log(f"{k} = {got[k]} (not compared)")
    return [(k, got[k], lim[k]) for k in sorted(lim)]
