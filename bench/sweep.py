"""Sweep a serving cell's arrival rate, to find its knee once.

    python bench/sweep.py --workload d2-serve-chat --rates 2,4,6,8 \
        --seconds 20 --seed 1

Not part of a benchmark run. Each rate serves one window in this process
and prints one JSON line: tokens/s, TTFT and ITL, the scheduler's queue
depth over the first and last thirds of the window, and how long the
requests due in the window took to finish after it closed. The knee is
the highest rate at which the queue does not grow over the window.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import harness
    from drivers import serve
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(ROOT, args.workload)
    compiles = harness.CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                                rate_per_s=rate))
        ctx = types.SimpleNamespace(
            seed=args.seed, seconds=args.seconds, trace_dir=None,
            spans=harness.Spans(False), compiles=compiles,
            devices=jax.devices()[:1], t_start=time.perf_counter())
        out = serve.session(cell, ctx)
        q = out["counters"]["queue_depth"]
        third = max(len(q) // 3, 1)
        print(json.dumps({
            "rate_per_s": rate, **out["end_to_end"],
            "failed": out["failed"], "attempted": out["attempted"],
            "queue_first_third": sum(q[:third]) / third,
            "queue_last_third": sum(q[-third:]) / third,
            **out["notes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
