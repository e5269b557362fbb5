"""Spans and scopes on the profiler's clock (DESIGN.md §15.2).

* ``obs.trace.scope`` (``jax.named_scope``) names ops and changes nothing
  else: the train step of a tiny zebra config and the paged decode step
  lower to the same text without debug info whether or not scopes are on,
  and with debug info the text holds every scope's name.
* The default tracer's spans cost nothing and tally nothing when no profile
  is taken; under ``jax.profiler.start_trace`` the engine's tick phases land
  on the host plane of the ``.xplane.pb``, nested as the engine runs them,
  and the process-wide registry counts each span once.
* Profiling never changes the tokens served.
* The benchmark's two readers of those tallies (``bench/metrics``).
"""

import contextlib
import glob
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.zebra_spmd import ZebraConfig
from repro.launch.mesh import make_mesh
from repro.models import registry, stack
from repro.models.config import ShapeConfig
from repro.models.modules import Policy, RunConfig
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.pytree import split_params
from repro.serve import (BlockAllocator, ContinuousBatchingEngine, GREEDY,
                         Request, Scheduler, make_continuous_program)
from repro.train.step import make_train_program

pytestmark = pytest.mark.obs  # CI trace-smoke job slice

ROOT = pathlib.Path(__file__).resolve().parent.parent
CFG = registry.smoke_config(registry.get_config("mixtral-d2"))
MODEL_SCOPES = ("attention", "router", "dispatch", "experts", "combine")
TRAIN_SCOPES = MODEL_SCOPES + ("head", "optimizer")
SERVE_RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32),
                      attn_impl="ref", moe_impl="gather")


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def params():
    return split_params(stack.init_model(jax.random.PRNGKey(0), CFG))[0]


def _train_lowered(mesh):
    run = RunConfig(policy=Policy(), attn_impl="chunked", moe_impl="gather",
                    remat="full")
    program = make_train_program(
        CFG, mesh, run, ShapeConfig("t", "train", 32, 4),
        zcfg=ZebraConfig(num_microbatches=2, mode="replicated"))
    p = program.param_shapes
    o = jax.eval_shape(program.init_opt, p)
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    with mesh:
        return program.train_step.lower(p, o, {"tokens": tok,
                                                "targets": tok})


def _decode_lowered(mesh, params):
    prog = make_continuous_program(CFG, mesh, SERVE_RUN, n_slots=2,
                                   max_len=32, page_size=8)
    B = prog.n_slots
    with mesh:
        state = prog.init_state()
        return prog.decode_step.lower(
            params, state, np.zeros((B, 1), np.int32),
            np.zeros((B,), np.int32),
            np.full((B, prog.max_pages), -1, np.int32),
            np.ones((B,), bool), np.zeros((B,), np.int32),
            np.ones((B,), np.int32), np.zeros((B,), np.float32),
            np.zeros((B,), np.int32), np.ones((B,), np.float32))


def _scopes(text, names):
    """The names among ``names`` that some op location's path holds as a
    component, bare or under autodiff (``jvp(head)``)."""
    return {n for n in names if re.search(rf"[/(]{n}[/)]", text)
            or re.search(rf'loc\("{n}/', text)}


@pytest.mark.parametrize("step", ["train", "paged_decode"])
def test_scopes_leave_lowered_program_unchanged(step, mesh1, params,
                                                monkeypatch):
    lower = ((lambda: _train_lowered(mesh1)) if step == "train"
             else (lambda: _decode_lowered(mesh1, params)))
    scoped = lower()
    with monkeypatch.context() as m:
        m.setattr(obs_trace, "scope", lambda name: contextlib.nullcontext())
        bare = lower()
    assert scoped.as_text() == bare.as_text()
    want = TRAIN_SCOPES if step == "train" else MODEL_SCOPES
    assert _scopes(scoped.as_text(debug_info=True), want) == set(want)
    assert _scopes(bare.as_text(debug_info=True), want) == set()


@pytest.fixture(scope="module")
def prog(mesh1):
    return make_continuous_program(CFG, mesh1, SERVE_RUN, n_slots=2,
                                   max_len=32, page_size=8)


def _engine(prog, params):
    alloc = BlockAllocator(prog.n_pages, prog.page_size, prog.max_pages)
    sched = Scheduler(2, 32, prefill_chunk=8, allocator=alloc)
    return ContinuousBatchingEngine(prog, params, sched)


def _requests():
    rng = np.random.RandomState(7)
    return [Request(rid=i, prompt=rng.randint(0, CFG.vocab_size,
                                              size=(n,)).tolist(),
                    max_new_tokens=5, sampling=GREEDY)
            for i, n in enumerate((11, 6))]


def _profiled_run(prog, params, trace_dir):
    _engine(prog, params).run(_requests())  # compile outside the profile
    eng = _engine(prog, params)
    jax.profiler.start_trace(str(trace_dir))
    try:
        res = eng.run(_requests())
    finally:
        jax.profiler.stop_trace()
    return res, eng.tick_count


def _host_spans(trace_dir):
    """repro.* host events of the newest .xplane.pb: {line id: [(name,
    start, end)]}."""
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("repro.")]
            if evs:
                out[(plane.name, i)] = evs
    return out


def _inside(ev, evs, names):
    return any(o[0] in names and o[1] <= ev[1] and ev[2] <= o[2]
               for o in evs if o is not ev)


def test_spans_land_on_the_profile_nested_and_tallied(prog, params,
                                                      tmp_path, monkeypatch):
    tally = obs_registry.Registry()
    monkeypatch.setattr(obs_registry, "PROCESS", tally)
    _engine(prog, params).run(_requests())
    assert tally.snapshot() == {}  # no profile: nothing tallied

    _, ticks = _profiled_run(prog, params, tmp_path)
    lines = _host_spans(tmp_path)
    assert len(lines) == 1  # one engine thread
    evs = next(iter(lines.values()))
    counts = {}
    for name, _, _ in evs:
        counts[name] = counts.get(name, 0) + 1
    assert counts["repro.tick"] == ticks
    for name in ("schedule", "prefill", "admit", "decode", "sync", "emit"):
        assert counts.get(f"repro.{name}", 0) > 0, name
    for ev in evs:
        if ev[0] == "repro.sync":
            assert _inside(ev, evs, ("repro.decode", "repro.admit"))
        if ev[0] in ("repro.decode", "repro.admit", "repro.schedule",
                     "repro.prefill"):
            assert _inside(ev, evs, ("repro.tick",)), ev
    assert {k: tally.get(k + ".n") for k in counts} == counts
    assert tally.get("repro.sync.s") < tally.get("repro.tick.s")


def test_profiling_leaves_tokens_unchanged(prog, params, tmp_path):
    assert obs_trace.TRACER is obs_trace.DEFAULT
    plain = _engine(prog, params).run(_requests())
    profiled, _ = _profiled_run(prog, params, tmp_path)
    assert profiled == plain
    assert all(len(t) == 5 for t in plain.values())


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,want", [
    ("tick_host_ms.serve", 1e3 * (8.0 - 6.5) / 100),
    ("tick_device_wait_ms.serve", 1e3 * 6.5 / 100)])
def test_tick_readers_arithmetic(name, want, monkeypatch):
    tally = obs_registry.Registry()
    monkeypatch.setattr(obs_registry, "PROCESS", tally)
    read = _reader(name).read
    serve = {"mix": {"driver": "serve"}}
    assert read(serve) is None  # no tick profiled
    tally.inc("repro.tick.n", 100)
    tally.inc("repro.tick.s", 8.0)
    tally.inc("repro.sync.n", 130)
    tally.inc("repro.sync.s", 6.5)
    tally.inc("repro.decode.s", 7.0)  # other spans do not enter
    assert read(serve) == pytest.approx(want)
    assert read({"mix": {"driver": "train"}}) is None
