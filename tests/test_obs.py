"""Tick-clock tracing + Perfetto export + idle attribution (DESIGN.md §15).

Covers the observability layer end to end: tracer determinism and the
zero-perturbation contract (tracing on/off yields bit-identical tokens),
hypothesis properties over random op scripts (spans well-nested per
track, flows always reference existing span/instant anchors, seeded
chaos replay gives bit-identical trace signatures), the exact idle
accounting identity ``sum(buckets) == ticks - busy`` on a REAL
fleet-under-chaos run whose exported trace carries spans from the
scheduler, engine, KV transfer, fleet controller and chaos injector plus
request flows crossing group tracks. The default tracer's spans on the
profiler's clock are covered in ``tests/test_obs_profiler.py``.
"""

import json

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.simulator import chaos_matrix
from repro.ft.chaos import FaultInjector, FaultPlan
from repro.models import stack
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.obs.export import to_chrome
from repro.obs.report import idle_report
from repro.pytree import split_params
from repro.serve.fleet import make_fleet
from repro.serve.metrics import ServeMetrics

from tests.test_serve_disagg import RUN, TINY  # noqa: F401
from tests.test_serve_fleet import _trace, mesh1, tiny_params  # noqa: F401

pytestmark = pytest.mark.obs  # CI trace-smoke job slice


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

_ctx = {}


def _mesh_params():
    """Module-lazy (1x1 mesh, tiny params) pair usable from @given tests —
    the hypothesis stub hides pytest fixtures from wrapped signatures."""
    if not _ctx:
        from repro.launch.mesh import make_mesh
        _ctx["mesh"] = make_mesh((1, 1), ("data", "model"))
        _ctx["params"] = split_params(
            stack.init_model(jax.random.PRNGKey(0), TINY))[0]
    return _ctx["mesh"], _ctx["params"]


def _fleet(mesh, params, chaos=None):
    return make_fleet(TINY, mesh, RUN, params, chaos=chaos,
                      prefill_classes=["a40", "a40"],
                      decode_classes=["v100", "v100"],
                      decode_slots=2, max_len=32, page_size=8,
                      prefill_chunk=6, metrics=ServeMetrics())


def _traced_fleet_run(mesh, params, spec=None, seed=0):
    inj = FaultInjector(FaultPlan.parse(spec), seed=seed) if spec else None
    tr = obs_trace.Tracer()
    with obs_trace.use(tr):
        fleet = _fleet(mesh, params, chaos=inj)
        # Pin the straggler factor: routing normally consults wall-clock
        # step timings (StragglerDetector), the one intentionally
        # non-deterministic input — tick-domain traces must not see it.
        fleet.router.slow_factor = lambda name: 1.0
        res = fleet.run(_trace())
    return tr, res, fleet


_STANDARD_SPEC = next(e[1] for e in chaos_matrix() if e[0] == "standard")


# ---------------------------------------------------------------------------
# Hypothesis properties over random op scripts (host-only Tracer)
# ---------------------------------------------------------------------------

_TRACKS = ("alpha", "beta")
_OPS = ("advance", "begin", "end", "instant", "flow_queued",
        "flow_step", "flow_finished", "idle")


def _run_script(script):
    """Interpret an op script leniently (end on an empty stack is skipped)
    and close every span left open, like an engine draining at exit."""
    tr = obs_trace.Tracer()
    tick, depth = 0, {t: 0 for t in _TRACKS}
    for sel, ti, rid in script:
        track = _TRACKS[ti % len(_TRACKS)]
        op = _OPS[sel % len(_OPS)]
        if op == "advance":
            tick += 1
            tr.advance(tick)
        elif op == "begin":
            tr.begin(track, f"work{rid}", rid=rid)
            depth[track] += 1
        elif op == "end":
            if depth[track]:
                tr.end(track)
                depth[track] -= 1
        elif op == "instant":
            tr.instant(track, "note", rid=rid)
        elif op == "idle":
            tr.mark_idle(track, obs_trace.IDLE_BUCKETS[rid
                                                       % len(obs_trace
                                                             .IDLE_BUCKETS)])
        else:
            stage = op[len("flow_"):]
            tr.flow(track, "queued" if stage == "queued" else
                    ("finished" if stage == "finished" else "prefill"), rid)
    for track, n in depth.items():
        for _ in range(n):
            tr.end(track)
    return tr


_SCRIPT = st.lists(st.tuples(st.integers(0, 7),    # op selector
                             st.integers(0, 1),    # track
                             st.integers(0, 5)),   # rid / bucket
                   min_size=0, max_size=120)


@settings(max_examples=30, deadline=None)
@given(_SCRIPT)
def test_spans_well_nested_per_track(script):
    """PROPERTY: exported span intervals on one track are either disjoint
    or strictly nested (stack discipline survives export), no span is
    flagged unclosed, and replaying the script is bit-identical."""
    tr = _run_script(script)
    obj = to_chrome(tr)
    xs = {}
    names = {(p, t): n for p, t, n in
             ((e["pid"], e["tid"], e["args"]["name"])
              for e in obj["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name")}
    for e in obj["traceEvents"]:
        if e["ph"] != "X":
            continue
        assert "unclosed" not in e["args"]
        xs.setdefault(names[(e["pid"], e["tid"])], []).append(
            (e["ts"], e["ts"] + e["dur"]))
    for track, ivals in xs.items():
        open_stack = []
        for t0, t1 in sorted(ivals):
            while open_stack and open_stack[-1] <= t0:
                open_stack.pop()
            if open_stack:              # overlapping => must be contained
                assert t1 <= open_stack[-1], (track, t0, t1, open_stack)
            open_stack.append(t1)
    assert tr.signature() == _run_script(script).signature()


@settings(max_examples=30, deadline=None)
@given(_SCRIPT)
def test_flows_reference_existing_spans(script):
    """PROPERTY: every flow event's parent eid names a span-begin or
    instant that exists on the same track, flow-start ("s") appears
    exactly at a rid's first stage, and "f" only for stage finished."""
    tr = _run_script(script)
    anchors = {ev.eid: ev for ev in tr.events if ev.ph in ("B", "i")}
    seen = set()
    for ev in tr.events:
        if ev.ph not in ("s", "t", "f"):
            continue
        assert ev.parent in anchors
        assert anchors[ev.parent].track == ev.track
        assert (ev.ph == "s") == (ev.flow_id not in seen)
        if ev.ph == "f":
            assert ev.name == "finished" and ev.flow_id in seen
        seen.add(ev.flow_id)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 1000))
def test_seeded_chaos_trace_bit_identical(seed):
    """PROPERTY: the same (chaos seed, spec, request trace) produces a
    bit-identical span sequence across two runs — the §15 determinism
    contract extended from fault logs to whole traces."""
    mesh, params = _mesh_params()
    a, res_a, _ = _traced_fleet_run(mesh, params, _STANDARD_SPEC, seed)
    b, res_b, _ = _traced_fleet_run(mesh, params, _STANDARD_SPEC, seed)
    assert res_a == res_b
    assert a.signature() == b.signature()
    assert [e.name for e in a.events] == [e.name for e in b.events]


# ---------------------------------------------------------------------------
# Acceptance: real fleet under chaos — trace contents + exact idle sums
# ---------------------------------------------------------------------------

def test_fleet_chaos_trace_contents_and_idle_identity(mesh1, tiny_params):
    """ACCEPTANCE: one traced fleet+chaos run carries spans/instants from
    the scheduler, engine, KV transfer, fleet controller and chaos
    injector; request flows cross group tracks; the export is valid JSON
    with positive-duration X events; and per tick track the idle buckets
    sum to (ticks - busy) EXACTLY."""
    tr, res, fleet = _traced_fleet_run(mesh1, tiny_params,
                                       _STANDARD_SPEC, seed=3)
    assert res  # requests actually finished under chaos
    obj = to_chrome(tr, ticks=fleet.tick_count)
    json.loads(json.dumps(obj))  # Perfetto-loadable (valid strict JSON)

    by_track = {}
    for ev in tr.events:
        by_track.setdefault(ev.track, set()).add((ev.ph, ev.name))
    # engine spans on group tracks (prefill workers + decode workers)
    assert any(("B", "prefill") in v for t, v in by_track.items()
               if t.startswith("g"))
    assert any(("B", "decode") in v for t, v in by_track.items()
               if t.startswith("g"))
    # scheduler flow stages, fleet + chaos control plane, kv chunks
    stages = {ev.name for ev in tr.events if ev.ph in ("s", "t", "f")}
    assert {"queued", "admitted", "finished"} <= stages
    assert "fleet" in by_track and "chaos" in by_track
    assert any(t.startswith("xfer:") for t in by_track)
    assert any(("B", "chunk") in v for t, v in by_track.items()
               if t.startswith("xfer:"))
    # flows cross tracks: some rid has flow events on >= 2 distinct tracks
    rid_tracks = {}
    for ev in tr.events:
        if ev.ph in ("s", "t", "f"):
            rid_tracks.setdefault(ev.flow_id, set()).add(ev.track)
    assert any(len(ts) >= 2 for ts in rid_tracks.values())
    # every request that finished has a full s -> ... -> f chain
    finished = {ev.flow_id for ev in tr.events if ev.ph == "f"}
    assert finished >= set(res)

    for e in obj["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] > 0

    rep = obj["reproIdle"]
    assert rep  # at least the group tracks
    for track, r in rep.items():
        if r["kind"] != "tick":
            continue
        assert r["ticks"] == fleet.tick_count
        assert sum(r["buckets"].values()) == r["idle"] \
            == r["ticks"] - r["busy"], track
        assert set(r["buckets"]) <= set(obs_trace.IDLE_BUCKETS)
    assert {"g0", "g1", "g2"} <= set(rep)
    # meta tracks (control plane) never get idle-attributed
    assert "fleet" not in rep and "chaos" not in rep


def test_tracing_disabled_is_bit_identical(mesh1, tiny_params):
    """ACCEPTANCE: running the same workload with tracing enabled vs
    disabled yields identical tokens — the tracer never touches RNG or
    control flow."""
    tr, traced, _ = _traced_fleet_run(mesh1, tiny_params,
                                      _STANDARD_SPEC, seed=3)
    assert tr.events  # the traced run actually recorded something
    assert obs_trace.TRACER is obs_trace.DEFAULT  # use() uninstalled it
    inj = FaultInjector(FaultPlan.parse(_STANDARD_SPEC), seed=3)
    fleet = _fleet(mesh1, tiny_params, chaos=inj)
    fleet.router.slow_factor = lambda name: 1.0
    untraced = fleet.run(_trace())
    assert traced == untraced


def test_unified_engine_idle_attribution(mesh1, tiny_params):
    """The single-engine path marks exactly one idle bucket per idle tick
    on its "serve" track (drain ticks at the end of a run show up as
    queue-starved by default)."""
    from repro.serve import (ContinuousBatchingEngine, Request, Scheduler,
                             make_continuous_program)
    from tests.test_serve_disagg import _prompt
    prog = make_continuous_program(TINY, mesh1, RUN, n_slots=2, max_len=32)
    with mesh1:
        params = jax.device_put(tiny_params, prog.param_shardings)
    tr = obs_trace.Tracer()
    with obs_trace.use(tr):
        eng = ContinuousBatchingEngine(
            prog, params, Scheduler(2, 32, prefill_chunk=8))
        res = eng.run([Request(rid=0, prompt=_prompt(0, 6),
                               max_new_tokens=4),
                       Request(rid=1, prompt=_prompt(1, 9),
                               max_new_tokens=4)])
        ticks = eng.tick_count
    assert sorted(res) == [0, 1]
    rep = idle_report(tr, ticks=ticks)
    r = rep["serve"]
    assert r["busy"] > 0
    assert sum(r["buckets"].values()) == r["idle"] == ticks - r["busy"]


# ---------------------------------------------------------------------------
# Exporter + registry plumbing
# ---------------------------------------------------------------------------

def test_export_embeds_registry_and_counters():
    tr = obs_trace.Tracer()
    tr.registry.register("unit", lambda: {"answer": 42})
    with obs_trace.use(tr):
        tr.advance(0)
        with tr.span("serve", "work", rid=1):
            tr.flow("serve", "queued", 1)
        tr.count("serve", "queue_depth", 3)
        tr.advance(1)
        tr.mark_idle("serve", "pool-OOM")
    obj = to_chrome(tr, ticks=2)
    assert obj["reproCounters"] == {"unit": {"answer": 42}}
    assert obj["reproIdle"]["serve"] == {
        "kind": "tick", "ticks": 2, "busy": 1, "idle": 1,
        "buckets": {"pool-OOM": 1}}
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"M", "X", "s", "C", "i"} <= phases
    counter = next(e for e in obj["traceEvents"] if e["ph"] == "C")
    assert counter["args"] == {"value": 3}


def test_null_tracer_is_inert():
    """Default-path contract: with no profile being taken, DEFAULT absorbs
    every call, reports not-busy, tallies nothing, and the span context
    manager still runs the body."""
    n = obs_trace.DEFAULT
    assert obs_trace.TRACER is n
    assert not n.enabled
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = obs_registry.PROCESS.snapshot()
    n.advance(5)
    n.begin("t", "x")
    n.end("t")
    n.flow("t", "queued", 1)
    n.count("t", "queue_depth", 3)
    n.mark_idle("t", "queue-starved")
    ran = []
    with n.span("t", "x"):
        ran.append(True)
    with obs_trace.host_span("y"):
        ran.append(True)
    assert ran == [True, True] and n.busy_this_tick("t") is False
    assert obs_registry.PROCESS.snapshot() == before
    assert idle_report(n) == {}
