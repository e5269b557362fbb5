"""What every driver shares: the cell's files, the seed, host spans, the
measured window, compile counting and the device's memory peak."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import time
from typing import Dict, List, Optional

import numpy as np


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def seed32(seed: int) -> int:
    """The run's seed as the 32-bit value a PRNG key takes."""
    return int(seed) % 2**32


@dataclasses.dataclass
class Cell:
    """One cell of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/mixes/<traffic>.json
    workload: dict        # bench/workloads/<name>.json (limits)
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    config = load_json(root / cfg_file)
    bench = root / "bench"
    mix = load_json(bench / "traffic" / "mixes" / f"{w['traffic']}.json")
    workload = load_json(bench / "workloads" / f"{name}.json")

    def here(metric):
        return "workloads" not in metric or name in metric["workloads"]
    e2e = [m for m in spec["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name, int(w["chips"]), config, mix, workload, e2e, per_layer)


class Spans:
    """Host spans of the harness's own calls into the program. With
    ``annotate`` each is also a profiler annotation, so the trace holds it
    on the device's clock."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.totals: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        name = f"bench.{name}"
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.totals[name] = self.totals.get(name, 0.0) + (
            time.perf_counter() - t0)


class CompileCounter:
    """Counts tracing and compilation events reported by JAX while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


class Window:
    """The measured window: arms the compile counter and, when tracing,
    runs the profiler with a ``bench.window`` span around it."""

    def __init__(self, compiles: CompileCounter, trace_dir: Optional[str]):
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None

    def __enter__(self):
        import jax
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.compiles.armed = True
        self.t0 = time.perf_counter()
        return self

    def close(self):
        """End the window now (the driver calls this once its last result
        is on the host); the profiler runs on until exit."""
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self.compiles.armed = False
            if self.trace_dir:
                self._ann.__exit__(None, None, None)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __exit__(self, *exc):
        import jax
        self.close()
        if self.trace_dir:
            jax.profiler.stop_trace()
        return False


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def free(*trees) -> None:
    """Delete the device buffers of the given pytrees now."""
    import jax
    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=None) -> tuple:
    """Largest |prog - ref| over leaves, each against max(ref leaf, median
    ref leaf); ``keep`` restricts the leaves. Returns (gap, leaf)."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    worst, where = -1.0, None
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, where = g, k
    return worst, where


def leaves(tree) -> dict:
    """Pytree -> {'a/b/c': leaf}."""
    import jax
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def flat(tree) -> Dict[str, float]:
    """Pytree of scalars -> {'a/b/c': float}."""
    return {k: float(np.asarray(x)) for k, x in leaves(tree).items()}


def flat_arrays(tree) -> Dict[str, np.ndarray]:
    """Pytree of host arrays -> {'a/b/c': array}."""
    return {k: np.asarray(x) for k, x in leaves(tree).items()}


def log(msg: str) -> None:
    import sys
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def model_config(m: dict):
    """The program's ModelConfig for a configuration file: the registry's
    architecture with every key of the file that is one of its fields."""
    import dataclasses as dc
    from repro.models import registry
    base = registry.get_config(m["arch"])
    fields = {f.name for f in dc.fields(base)}
    return dc.replace(base, **{k: v for k, v in m.items() if k in fields})


def model_module(m: dict, kind: str):
    """The configuration's own ``reference`` or ``flops`` module under
    ``bench/``: ``reference.<name>`` (default ``mixtral``) and ``<name>``
    (default ``flops``), as the file names them."""
    import importlib
    if kind == "reference":
        return importlib.import_module(
            "reference." + m.get("reference", "mixtral"))
    return importlib.import_module(m.get("flops", "flops"))


def make_mesh(shape, devices):
    """A (data, model) mesh over the first prod(shape) of ``devices``."""
    import jax
    from jax.sharding import AxisType
    n = int(np.prod(shape))
    return jax.make_mesh(tuple(shape), ("data", "model"),
                         devices=devices[:n],
                         axis_types=(AxisType.Auto, AxisType.Auto))
