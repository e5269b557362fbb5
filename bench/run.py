"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and limits are files under ``bench/`` found by name, and its driver is
``bench/drivers/<driver>.py`` as the mix names it. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` runs the same window under the
profiler and reports its per-layer metrics, each read by
``bench/metrics/<metric>.py``.

Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell needs, or a chip missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
import types

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _num(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def main(argv=None, require_chip: bool = True) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import harness
    from trace_reduce import find_xplane, load, summarize

    cell = harness.load_cell(ROOT, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        harness.log(f"no program under {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = harness.load_json(BENCH / "peaks.json")
    if require_chip:
        if devices[0].platform != "tpu":
            harness.log(f"no TPU: JAX found {devices[0].platform}")
            return 2
        if len(devices) < cell.chips:
            harness.log(f"{cell.name} needs {cell.chips} chips, found "
                        f"{len(devices)}")
            return 2
        if kind not in peaks:
            harness.log(f"no peaks for {kind!r} in bench/peaks.json")
            return 2
    devices = devices[:cell.chips]

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    trace_dir = None
    if args.trace:
        trace_dir = str(ROOT / "bench_out" / "trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, trace_dir=trace_dir,
        spans=harness.Spans(annotate=bool(args.trace)),
        compiles=harness.CompileCounter(), devices=devices, t_start=t_start)
    driver = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py")
    out = driver.run(cell, ctx)

    checks = {n: {"value": _num(v), "limit": lim} for n, v, lim
              in out["checks"]}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        path = find_xplane(trace_dir)
        summary = summarize(load(path)) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in summary.device_ops],
                "idle_gaps": [list(x) for x in summary.idle_gaps]}
        rctx = {"outcome": out, "summary": summary, "chips": len(devices),
                "peak": peaks.get(kind), "config": cell.config,
                "mix": cell.mix}
        for spec in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{spec['name']}.py")
            v = reader.read(rctx)
            if v is not None:
                result["metrics"][spec["name"]] = {"value": v,
                                                   "unit": spec["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for spec in cell.end_to_end:
            result["metrics"][spec["name"]] = {
                "value": _num(values[spec["name"]]), "unit": spec["unit"]}
    result["checks"] = checks

    harness.log(f"notes: {json.dumps(out['notes'])}")
    harness.log(f"setup_s={out['setup_s']:.3f} window_s={out['window_s']:.3f}"
                f" attempted={out['attempted']} failed={out['failed']}")
    for n, c in checks.items():
        harness.log(f"check {n} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
