"""Continuous-batching serving driver: Poisson arrivals, chunked prefill,
per-slot sampled decode, streaming per-request output (DESIGN.md §7).

The CLI is a thin shell around ONE config object and ONE factory
(DESIGN.md §14.5): flags parse into a :class:`repro.serve.ServeConfig`,
``serve_cfg.validate()`` rejects every invalid combination in a single
clear non-zero-exit error (conflicting ``--fleet``+``--disagg``,
``--ep-size`` on a dense arch, ``--prefix-cache`` without a paged
deployment, malformed chaos/kill specs, ...), and
:func:`repro.serve.build_deployment` constructs whichever engine the
config describes.

    # MoE + dense smoke archs through a mixed-length Poisson trace:
    PYTHONPATH=src python -m repro.launch.serve --smoke --mesh 1x1

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
        --smoke --slots 4 --requests 8 --prompt-len 64 --gen 32 --mesh 1x2

    # paged smoke with an overcommitted pool (preemption exercised):
    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --page-size 16 --pool-pages 12

    # prefix-cached COW paged KV over a shared-prefix multi-tenant trace
    # (DESIGN.md §14); --fair switches admission to per-tenant deficit
    # round-robin:
    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --prefix-cache --tenants 2 --fair --requests 8

    # disaggregated prefill/decode smoke (role-split workers, page-id
    # KV handoff, DESIGN.md §10); tight decode pool exercises the
    # preempt -> re-prefill path:
    PYTHONPATH=src python -m repro.launch.serve --smoke --disagg \
        --page-size 16 --pool-pages 12

``--ep-size N`` shards MoE expert weights across N devices of the mesh
``model`` axis for the decode-time expert hop (DESIGN.md §11); on a
dense arch it is REJECTED (pass an explicit MoE ``--arch``).
``--ep-placement planned`` turns on online heterogeneity-aware
re-placement from the observed routing EMA:

    PYTHONPATH=src python -m repro.launch.serve --smoke \
        --arch qwen3-moe-30b-a3b --mesh 1x2 --ep-size 2 \
        --ep-placement planned

``--fleet`` scales disagg to an elastic multi-group fleet (DESIGN.md
§12): N prefill + M decode groups of mixed device classes behind a
router, with heartbeat failure recovery and (``--fleet-elastic``)
role flips. ``--kill-group GID@TICK`` injects a crash mid-trace (the
shorthand is sugar for a ``crash_start@TICK:gGID`` entry of the ONE
``ft.chaos`` fault grammar, which is also accepted verbatim); the
killed group's in-flight requests re-enter the router and re-prefill
token-exactly:

    PYTHONPATH=src python -m repro.launch.serve --smoke --fleet \
        --prefill-groups a40 --decode-groups v100,v100 \
        --page-size 8 --kill-group 2@8

``--chaos SPEC --chaos-seed N`` (fleet mode only) arms the seeded fault
injector (DESIGN.md §13) with a ``ft.chaos`` schedule — transfer chunk
drop/corrupt/stall, heartbeat loss (zombie + rejoin), mid-tick group
crashes — and ``--slo-ttft S`` turns on SLO-aware shedding. The summary
gains a ``chaos`` section with the replayable event log + signature:

    PYTHONPATH=src python -m repro.launch.serve --smoke --fleet \
        --prefill-groups a40,a40 --decode-groups v100,v100 \
        --page-size 8 --chaos 'drop%0.6*4' --chaos-seed 101

Exit status: non-zero when any request is rejected, dropped, or left
unfinished — the CI serve-smoke, disagg-smoke, ep-smoke, fleet-smoke,
chaos-smoke and prefix-smoke steps gate on it — and when the ServeConfig
is invalid (one aggregated error message, before any device work).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import format_report, write_chrome_trace
from repro.obs import trace as obs_trace
from repro.models.modules import Policy, RunConfig
from repro.serve import (Request, SamplingParams, ServeConfig,
                         ServeConfigError, ServeMetrics, build_deployment)
# Re-exported here for back-compat (tests and older tooling import the
# parsers from the driver); the implementations live in serve.config.
from repro.serve.config import parse_group_spec, parse_kills  # noqa: F401

SMOKE_ARCHS = ("qwen3-moe-30b-a3b", "llama3.2-3b")  # MoE + dense


def build_trace(seed: int, n: int, rate: float, prompt_len: int, gen: int,
                vocab: int, sampling: SamplingParams,
                eos_token=None) -> list:
    """Mixed-length Poisson trace: exponential inter-arrivals (in engine
    ticks), prompt lengths in [prompt_len/4, prompt_len], generation
    budgets in [gen/2, gen]."""
    rng = np.random.RandomState(seed)
    t, reqs = 0.0, []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.randint(max(1, prompt_len // 4), prompt_len + 1))
        gmax = int(rng.randint(max(1, gen // 2), gen + 1))
        prompt = rng.randint(0, vocab, size=(plen,)).astype(int).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=gmax,
                            sampling=sampling, eos_token=eos_token,
                            arrival=t))
    return reqs


def build_tenant_trace(args, vocab: int, sampling: SamplingParams) -> list:
    """Shared-prefix multi-tenant trace (--tenants N, DESIGN.md §14):
    same-tenant requests share a seeded system prefix, which is what the
    prefix cache and the fairness admission are exercised against."""
    from repro.core.simulator import multi_tenant_trace
    recs = multi_tenant_trace(
        args.seed, args.requests, n_tenants=args.tenants, rate=args.rate,
        prompt_len=args.prompt_len, gen=args.gen, vocab=vocab,
        shared_len=args.shared_prefix_len)
    return [Request(rid=i, prompt=list(r.prompt), max_new_tokens=r.gen,
                    sampling=sampling, arrival=r.arrival, tenant=r.tenant)
            for i, r in enumerate(recs)]


def serve_arch_lockstep(cfg, mesh, run, serve_cfg, prompt_len: int,
                        gen: int) -> dict:
    """Whole-batch lockstep fallback for enc-dec / vision archs (they need
    per-request front embeddings the continuous engine does not carry)."""
    server = build_deployment(cfg, mesh, run, serve_cfg)
    slots = serve_cfg.slots
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (slots, prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    fronts = {}
    if cfg.is_encdec:
        fronts["encoder_embeds"] = jnp.zeros(
            (slots, cfg.encoder_seq, cfg.d_model),
            run.policy.compute_dtype)
    if cfg.vision_seq > 0:
        fronts["vision_embeds"] = jnp.zeros(
            (slots, cfg.vision_seq, cfg.vision_dim or cfg.d_model),
            run.policy.compute_dtype)
    t0 = time.perf_counter()
    server.submit_prefill(prompts, fronts)
    out = [server.tokens]
    for _ in range(gen - 1):
        out.append(server.step(fronts))
    toks = jnp.concatenate(out, axis=1)
    dt = time.perf_counter() - t0
    tps = round(slots * gen / dt, 2)
    print(f"[serve] arch={cfg.name} lockstep fallback generated "
          f"{toks.shape} in {dt:.2f}s ({tps} tok/s)")
    return {"tokens_per_s": tps, "lockstep": True,
            "ok": toks.shape == (slots, gen)}


def _prefix_summary(index, alloc, n_prefix_hits: int,
                    tokens_skipped: int) -> dict:
    """The summary's ``prefix`` section: index + allocator accounting."""
    return {
        "lookups_hit": index.hits,
        "lookups_miss": index.misses,
        "tokens_served": index.tokens_served,
        "admissions_hit": n_prefix_hits,
        "tokens_skipped": tokens_skipped,
        "pages_pinned": index.n_pages,
        "pages_evicted": index.n_evicted,
        "pages_allocated": alloc.n_fresh_allocs,
        "pages_shared": alloc.n_shared_allocs,
        "n_cow_forks": alloc.n_cow_forks,
    }


def serve_arch(arch: str, args, serve_cfg: ServeConfig = None,
               trace: list = None) -> dict:
    """Serve one arch through the deployment ``args`` describe and return
    the metrics summary (``ok`` False on any failure). ``trace`` replaces
    the generated request trace."""
    cfg = registry.get_config(arch)
    if args.smoke:
        cfg = registry.smoke_config(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    run = RunConfig(policy=Policy(), attn_impl="ref", moe_impl="gather")
    if serve_cfg is None:
        serve_cfg = ServeConfig.from_args(args)
    try:
        # Arch/mesh-dependent validation (EP divisibility, recurrent-arch
        # prefix rejection) — the ONE error path for invalid configs.
        serve_cfg.validate(model_cfg=cfg, mesh=mesh)
    except ServeConfigError as e:
        print(f"[serve] FAIL arch={cfg.name}: invalid serve config: {e}",
              file=sys.stderr)
        return {"ok": False, "n_requests": 0, "config_error": str(e)}
    if cfg.is_encdec or cfg.vision_seq > 0:
        return serve_arch_lockstep(cfg, mesh, run, serve_cfg,
                                   args.prompt_len, args.gen)
    sampling = serve_cfg.sampling
    if trace is None and args.tenants:
        trace = build_tenant_trace(args, cfg.vocab_size, sampling)
    elif trace is None:
        trace = build_trace(args.seed, args.requests, args.rate,
                            args.prompt_len, args.gen, cfg.vocab_size,
                            sampling)
    metrics = ServeMetrics()
    stream = None
    if args.stream:
        def stream(rid, tok, fin):
            print(f"[{cfg.name}] rid={rid} tok={tok}"
                  + (" <done>" if fin else ""))

    shed: set = set()
    leaked: list = []
    trace_out = getattr(args, "trace_out", None)
    tracer = None
    if trace_out:
        # Tick-clock tracing (DESIGN.md §15): installed process-wide so
        # every instrumented hot path emits; by default spans go to the
        # profiler alone (ProfilerTracer).
        tracer = obs_trace.Tracer(
            wall=bool(getattr(args, "trace_wall", False)))
        obs_trace.install(tracer)
    try:
        engine = build_deployment(cfg, mesh, run, serve_cfg,
                                  metrics=metrics, on_token=stream)
    except ValueError as e:
        # Anything validate() could not see statically (construction-time
        # topology problems) still fails the run, never half-serves.
        print(f"[serve] FAIL arch={cfg.name}: bad deployment: {e}",
              file=sys.stderr)
        obs_trace.install(None)
        return {"ok": False, "n_requests": 0, "config_error": str(e)}
    if tracer is not None:
        # Unified counters registry: the exporter snapshots these into the
        # trace artifact's reproCounters section.
        tracer.registry.register("serve", metrics.summary)
        tracer.registry.register("robust", metrics.robust.as_dict)
        ema = getattr(engine, "ema", None)
        if ema is None:
            ema = getattr(getattr(engine, "decode", None),
                          "routing_ema", None)
        if ema is not None:
            tracer.registry.register("routing_ema", lambda e=ema: {
                "n_updates": e.n_updates,
                "merged": [round(float(v), 6) for v in e.merged()]})

    t0 = time.perf_counter()
    if serve_cfg.fleet.enabled:
        try:
            results = engine.run(trace,
                                 kills=list(serve_cfg.fleet.kills))
        except RuntimeError as e:
            # Wedged fleet (e.g. the only decode group was killed without
            # --fleet-elastic): requests would be dropped — fail the run.
            print(f"[serve] FAIL arch={cfg.name}: fleet stalled: {e}",
                  file=sys.stderr)
            obs_trace.install(None)
            return {"ok": False, "n_requests": 0, "fleet_error": str(e)}
        shed = set(engine.shed)
    else:
        results = engine.run(trace)
    dt = time.perf_counter() - t0

    for req in trace:
        if req.rid in shed:  # explicit SLO-shed outcome (chaos/slo mode)
            print(f"[{cfg.name}] rid={req.rid} prompt={len(req.prompt)} "
                  f"SHED")
            continue
        tr = metrics.requests.get(req.rid)
        if tr is None:  # rejected at submit — never entered the engine
            print(f"[{cfg.name}] rid={req.rid} prompt={len(req.prompt)} "
                  f"REJECTED")
            continue
        toks = results[req.rid]
        tenant = f" tenant={req.tenant}" if args.tenants else ""
        print(f"[{cfg.name}] rid={req.rid}{tenant} "
              f"prompt={len(req.prompt)} "
              f"gen={len(toks)}/{req.max_new_tokens} "
              f"first_tick={tr.first_token_tick} "
              f"finish_tick={tr.finish_tick} out={toks[:8]}...")
    s = metrics.summary()
    print(f"[serve] arch={cfg.name} {s['n_requests']} requests, "
          f"{s['n_generated_tokens']} tokens in {dt:.2f}s "
          f"({s['tokens_per_s']} tok/s, ttft p50 {s['ttft_s']['p50']:.3f}s, "
          f"itl p50 {s['itl_s']['p50']:.4f}s, "
          f"queue depth max {s['queue_depth']['max']}, "
          f"max concurrent {s['max_concurrent_active']})")
    if serve_cfg.fleet.enabled:
        # Surviving pools must hold the exactly-once page invariant even
        # after kills, recoveries, and role flips.
        for g in engine.groups:
            g.worker.allocator.check()
        chaos = engine.chaos
        if chaos is not None:
            # Chaos acceptance: a drained fleet must hold ZERO pages on
            # every surviving pool — a leftover page is a leak the fault
            # path failed to roll back.
            leaked = [g.gid for g in engine.groups
                      if g.worker.allocator.pages_in_use != 0]
        st = engine.transfer.stats
        s["fleet"] = {
            "elastic": serve_cfg.fleet.elastic,
            "ticks": engine.tick_count,
            "groups": [{"gid": g.gid, "cls": g.cls, "role": g.role,
                        "flips": g.flips} for g in engine.groups],
            "events": [{"tick": e.tick, "kind": e.kind, "gid": e.gid,
                        "detail": e.detail} for e in engine.events],
            "n_flips": engine.n_flips,
            "n_killed": len([e for e in engine.events
                             if e.kind == "dead"]),
            "kv_transfers": st.n_transfers,
            "kv_pages_shipped": st.n_pages,
        }
        if chaos is not None:
            s["chaos"] = {
                "spec": serve_cfg.chaos.spec,
                "seed": serve_cfg.chaos.seed,
                "events": chaos.log(),
                "signature": chaos.log_signature(),
                "counters": metrics.robust.as_dict(),
                "n_shed": len(shed),
                "leaked_groups": leaked,
            }
            print(f"[serve] arch={cfg.name} chaos: "
                  f"spec={serve_cfg.chaos.spec!r} "
                  f"seed={serve_cfg.chaos.seed} faults={len(chaos.log())} "
                  f"sig={chaos.log_signature()} shed={len(shed)} "
                  f"retries={st.n_retries} aborts={st.n_aborts} "
                  f"fenced={metrics.robust.fenced_stale_completions}")
        roles = ",".join(f"g{g.gid}={g.cls}:{g.role}"
                         for g in engine.groups)
        print(f"[serve] arch={cfg.name} fleet: {roles} "
              f"flips={engine.n_flips} "
              f"events={len(engine.events)} transfers={st.n_transfers} "
              f"ttft_p99={s['ttft_s']['p99']:.3f}s "
              f"itl_p99={s['itl_s']['p99']:.4f}s")
    elif serve_cfg.disagg.enabled:
        st = engine.transfer.stats
        s["disagg"] = {
            "page_size": serve_cfg.paged.page_size,
            "decode_pages": engine.decode.allocator.n_pages,
            "prefill_pages": engine.prefill.allocator.n_pages,
            "decode_page_peak": engine.decode.page_peak,
            "n_preempted": engine.decode.sched.n_preempted,
            "kv_transfers": st.n_transfers,
            "kv_pages_shipped": st.n_pages,
            "kv_bytes_shipped": st.bytes,
            "prefix_full_hits": engine.n_full_hits,
        }
        print(f"[serve] arch={cfg.name} disagg: "
              f"page_size={serve_cfg.paged.page_size} "
              f"transfers={st.n_transfers} pages={st.n_pages} "
              f"preempted={engine.decode.sched.n_preempted} "
              f"full_hits={engine.n_full_hits}")
        index = engine.decode.sched.prefix_index
        if index is not None:
            s["prefix"] = _prefix_summary(
                index, engine.decode.allocator,
                engine.prefill.sched.n_prefix_hits,
                engine.prefill.sched.n_tokens_skipped)
            s["prefix"]["full_hits"] = engine.n_full_hits
            index.check()
        engine.prefill.allocator.check()
        engine.decode.allocator.check()
    elif serve_cfg.paged.enabled:
        s["paged"] = eng_occ = engine.page_occupancy()
        print(f"[serve] arch={cfg.name} paged: "
              f"page_size={serve_cfg.paged.page_size} "
              f"pool={engine.p.n_pages} peak={eng_occ['page_peak']} "
              f"preempted={eng_occ['n_preempted']}")
        index = engine.sched.prefix_index
        if index is not None:
            s["prefix"] = _prefix_summary(
                index, engine.sched.allocator,
                engine.sched.prefill.n_prefix_hits,
                engine.sched.prefill.n_tokens_skipped)
            print(f"[serve] arch={cfg.name} prefix: "
                  f"hits={index.hits} tokens_served={index.tokens_served} "
                  f"skipped={engine.sched.prefill.n_tokens_skipped} "
                  f"cow_forks={engine.sched.allocator.n_cow_forks} "
                  f"pinned={index.n_pages}")
            index.check()
        engine.sched.allocator.check()
    if serve_cfg.ep.ep_size and not serve_cfg.disagg.enabled \
            and not serve_cfg.fleet.enabled:
        s["ep"] = {
            "ep_size": serve_cfg.ep.ep_size,
            "placement_mode": serve_cfg.ep.placement,
            "n_rebalances": engine.n_rebalances,
            "ema_updates": engine.ema.n_updates,
        }
        print(f"[serve] arch={cfg.name} ep: "
              f"ep_size={serve_cfg.ep.ep_size} "
              f"placement={serve_cfg.ep.placement} "
              f"rebalances={engine.n_rebalances} "
              f"ema_updates={engine.ema.n_updates}")
    # Gate: every traced request must finish with its full token budget
    # spent (traces carry no EOS) and nothing may be rejected or dropped.
    # Rejected rids never reach metrics (submit raises before on_submit);
    # they count as unfinished here AND appear in engine.rejected. Shed
    # requests (SLO admission, chaos mode) are an EXPLICIT outcome: they
    # are excluded from the finish requirement, and in chaos mode the run
    # additionally fails when any surviving pool leaked pages.
    unfinished = [r.rid for r in trace
                  if r.rid not in shed
                  and (metrics.requests.get(r.rid) is None
                       or metrics.requests[r.rid].finish_tick is None
                       or len(results.get(r.rid, [])) != r.max_new_tokens)]
    if tracer is not None:
        obj = write_chrome_trace(tracer, trace_out,
                                 ticks=getattr(engine, "tick_count", None))
        obs_trace.install(None)
        print(f"[serve] arch={cfg.name} trace: "
              f"{len(obj['traceEvents'])} events -> {trace_out}")
        for line in format_report(obj["reproIdle"]).splitlines():
            print(f"[serve] idle: {line}")
        s["trace"] = {"path": trace_out,
                      "n_events": len(obj["traceEvents"])}
    s["ok"] = not engine.rejected and not unfinished and not leaked \
        and s["n_requests"] == len(trace) - len(shed)
    if not s["ok"]:
        print(f"[serve] FAIL arch={cfg.name}: rejected={engine.rejected} "
              f"unfinished={unfinished} leaked={leaked} "
              f"finished={s['n_requests']}"
              f"/{len(trace) - len(shed)}", file=sys.stderr)
    return s


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="default: llama3.2-3b; with --smoke and no --arch, "
                         "runs the MoE + dense smoke pair")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent KV slots (decode batch)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--rate", type=float, default=0.4,
                    help="Poisson arrival rate (requests per engine tick)")
    ap.add_argument("--prompt-len", type=int, default=48,
                    help="max prompt length (trace mixes lengths below it)")
    ap.add_argument("--gen", type=int, default=24,
                    help="max new tokens (trace mixes budgets below it)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prefill tokens per tick (default: one chunk)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (block allocator + page-table "
                         "decode, DESIGN.md §9)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache lines per page (paged mode)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical pool size in pages (default: full "
                         "reservation capacity; smaller values overcommit "
                         "and exercise preemption)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix-cached copy-on-write paged KV (DESIGN.md "
                         "§14): cached prompt prefixes mount as shared "
                         "pages and skip prefill; needs --paged or "
                         "--disagg")
    ap.add_argument("--prefix-capacity", type=int, default=None,
                    metavar="PAGES",
                    help="LRU bound on pages the prefix index may pin "
                         "(default: unbounded — allocator pressure is "
                         "the only bound)")
    ap.add_argument("--fair", action="store_true",
                    help="per-tenant deficit round-robin admission "
                         "(DESIGN.md §14): a flooding tenant cannot "
                         "starve the rest")
    ap.add_argument("--tenants", type=int, default=0,
                    help="build a shared-prefix multi-tenant trace with "
                         "this many tenants (0: classic mixed-length "
                         "Poisson trace)")
    ap.add_argument("--shared-prefix-len", type=int, default=None,
                    help="tenant shared-prefix length in tokens "
                         "(default: half of --prompt-len)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode deployment "
                         "(DESIGN.md §10): role-split workers over "
                         "separate paged pools, KV handed off as pages; "
                         "--pool-pages sizes the decode pool")
    ap.add_argument("--prefill-pool-pages", type=int, default=None,
                    help="prefill-side pool size in pages (disagg mode; "
                         "default: two max-length sequences)")
    ap.add_argument("--fleet", action="store_true",
                    help="elastic multi-group fleet (DESIGN.md §12): "
                         "N prefill + M decode groups of mixed device "
                         "classes behind a router, heartbeat failure "
                         "recovery; see --prefill-groups/--decode-groups")
    ap.add_argument("--prefill-groups", default="a40",
                    help="fleet prefill groups: an integer count or a "
                         "comma-separated device-class list, e.g. "
                         "'a40,a40' or '2' (default one a40 group)")
    ap.add_argument("--decode-groups", default="v100",
                    help="fleet decode groups: an integer count or a "
                         "comma-separated device-class list, e.g. "
                         "'v100,v100' (default one v100 group)")
    ap.add_argument("--fleet-elastic", action="store_true",
                    help="enable elastic role reassignment: idle groups "
                         "flip prefill<->decode when the bottleneck "
                         "role shifts or a role dies out")
    ap.add_argument("--kill-group", action="append", metavar="GID@TICK",
                    help="fault injection (repeatable): crash fleet group "
                         "GID at the start of tick TICK — sugar for a "
                         "crash_start@TICK:gGID entry of the ft.chaos "
                         "grammar (the full entry form is also accepted)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="seeded fault schedule (fleet mode, DESIGN.md "
                         "§13): ';'-joined ft.chaos entries "
                         "SITE[@TICK][:TARGET][%%PROB][*COUNT][~DURATION] "
                         "— e.g. 'drop%%0.6*4;hb_loss@6:g3~8'; malformed "
                         "specs exit non-zero")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos injector: the same "
                         "(seed, spec) replays the identical fault log")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="SLO-aware admission (fleet mode): shed arrivals "
                         "whose best prefill ETA exceeds this many "
                         "seconds of estimated work")
    ap.add_argument("--ep-size", type=int, default=0,
                    help="shard MoE expert weights across this many "
                         "devices of the mesh 'model' axis for decode "
                         "(DESIGN.md §11); must divide the expert count "
                         "and needs a MoE --arch — rejected otherwise, "
                         "never truncated; 0 = off")
    ap.add_argument("--ep-placement", choices=("uniform", "planned"),
                    default="uniform",
                    help="uniform: static round-robin expert placement; "
                         "planned: online heterogeneity-aware re-placement "
                         "from the observed routing EMA")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace-event JSON of the "
                         "run (tick-clock spans, request flows, counters, "
                         "idle-time attribution — DESIGN.md §15); tracing "
                         "is fully off without this flag")
    ap.add_argument("--trace-wall", action="store_true",
                    help="annotate trace spans with wall-clock readings "
                         "(opt-in; excluded from the deterministic trace "
                         "signature)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    try:
        # Parse + arch-independent validation: EVERY violation in one
        # message, one non-zero exit, before any device work.
        serve_cfg = ServeConfig.from_args(args)
        serve_cfg.validate()
    except ServeConfigError as e:
        print(f"[serve] invalid configuration: {e}", file=sys.stderr)
        return 1
    archs = [args.arch] if args.arch else \
        (list(SMOKE_ARCHS) if args.smoke else ["llama3.2-3b"])
    failed = []
    trace_out = args.trace_out
    for arch in archs:
        if trace_out and len(archs) > 1:
            # One artifact per arch (the smoke pair would overwrite).
            stem, dot, ext = trace_out.rpartition(".")
            args.trace_out = f"{stem}.{arch}.{ext}" if dot \
                else f"{trace_out}.{arch}"
        s = serve_arch(arch, args, serve_cfg)
        if not s.get("ok", True):
            failed.append(arch)
    if failed:
        print(f"[serve] FAILED archs: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
