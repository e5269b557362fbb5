"""Readings of the program, the control and planted faults, to set a
cell's limits.

    python bench/control.py --workload <name> --seeds 11,12,13 \
        [--read program,control,fault] [--seconds 10]

Not part of a benchmark run. For each seed it prints one JSON line with
the numbers ``bench/run.py`` compares, read for:

* ``program``: the program as a run drives it, against the float32
  reference (training: its checked steps, with no window; serving: a
  window of ``--seconds`` at the cell's own load, whose sampled requests
  run to completion);
* ``control``: the reference put in the program's place and computed in
  float8 (e4m3, per-tensor scale), the precision below the configuration's
  bfloat16, against the float32 reference;
* ``fault``: training, ``half_batch``: the reference on the first half of
  each batch's rows, the mean taken over them, against the whole batch (a
  step that returns its state unchanged reads 1 on the gradient by the
  measure itself, and needs no run); serving, ``altered_token``: every
  sampled served token replaced by the next vocabulary id, read against
  the reference as a served token is.

Serving reads the control and the fault on the program's own sample, so
it always runs the program's window first.
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
KEEP = ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff", "worst")


def train_readings(cell, seed: int, read=("program", "control", "fault"),
                   devices=None) -> dict:
    import jax
    import numpy as np

    import harness
    from drivers import train
    from repro.data import DataConfig, DataLoader

    mix, out = cell.mix, {}
    if "program" in read:
        b = train.build(cell, devices or jax.devices()[:1],
                        harness.Spans(False))
        st, batches, prog = train.first_steps(b, seed, mix["check_steps"])
        harness.free(st.params, st.opt_state, st.met, st.batch)
        del st
        jax.clear_caches()
    else:
        loader = DataLoader(DataConfig(vocab_size=cell.config["vocab_size"],
                                       seq_len=mix["seq"],
                                       global_batch=mix["batch"], seed=seed))
        batches = [{k: np.asarray(v) for k, v in next(loader).items()}
                   for _ in range(mix["check_steps"])]
    exact = train.reference_readings(cell, seed, batches)

    def keep(r):
        return {k: v for k, v in train.compare(r, exact).items() if k in KEEP}
    if "program" in read:
        out["program"] = keep(prog)
    if "control" in read:
        out["control"] = keep(train.reference_readings(cell, seed, batches,
                                                       precision="fp8"))
    if "fault" in read:
        half_mix = dict(mix, batch=mix["batch"] // 2)
        if mix["zebra"]["num_microbatches"] > half_mix["batch"]:
            half_mix["zebra"] = dict(mix["zebra"],
                                     num_microbatches=half_mix["batch"])
        half_cell = types.SimpleNamespace(config=cell.config, mix=half_mix)
        out["half_batch"] = keep(train.reference_readings(
            half_cell, seed, [{k: v[:half_mix["batch"]] for k, v in b.items()}
                              for b in batches]))
    return out


def serve_readings(cell, seed: int, seconds: float, devices,
                   read=("program", "control", "fault")) -> dict:
    import harness
    from drivers import serve

    ctx = types.SimpleNamespace(
        seed=seed, seconds=seconds, trace_dir=None,
        spans=harness.Spans(False), compiles=harness.CompileCounter(),
        devices=devices, t_start=time.perf_counter())
    run = serve.session(cell, ctx)
    pick, tokens, logits = run["pick"], run["tokens"], run["logits"]
    V = cell.config["vocab_size"]
    out = {"kv_pages_peak": run["notes"]["kv_pages_peak"]}
    if "program" in read:
        out["program"] = serve.readings(cell, seed, pick, tokens, logits)
    if "control" in read:
        out["control"] = serve.readings(cell, seed, pick, tokens, logits,
                                        control="fp8")
    if "fault" in read:
        out["altered_token"] = serve.readings(
            cell, seed, pick,
            {k: [(t + 1) % V for t in v] for k, v in tokens.items()},
            logits)
    return out


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--read", default="program,control,fault")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import harness
    from repro.launch.cache import enable_compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        harness.log("no TPU with the chips this cell needs")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        read = tuple(args.read.split(","))
        if cell.mix["driver"] == "train":
            rec = train_readings(cell, harness.seed32(seed), read,
                                 devices[:cell.chips])
        else:
            rec = serve_readings(cell, seed, args.seconds,
                                 devices[:cell.chips], read)
        rec.update(workload=cell.name, seed=seed,
                   seconds=time.perf_counter() - t0)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
