"""Shared fixtures: a scratch checkout holding a copy of the benchmark, the
program's sources and one tiny cell of each driver, run on the CPU, with 8
emulated devices for the expert-parallel cells."""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pytest  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))
BENCH_MODULES = ("harness", "trace_reduce", "flops", "control", "reference",
                 "reference.mixtral", "traffic", "traffic.generate",
                 "drivers", "drivers.train", "drivers.serve")

TINY = {
    "name": "tiny", "arch": "mixtral-d2", "source": "test", "reduced": [],
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 112, "d_ff_expert": 112, "vocab_size": 256,
    "n_experts": 4, "top_k": 2, "rope_theta": 10000.0,
    "capacity_factor": 1.25, "router_aux_coef": 0.01, "router_z_coef": 0.001,
}
TINY_TRAIN = {
    "driver": "train", "batch": 4, "seq": 64, "mesh": [1, 1],
    "zebra": {"mode": "replicated", "num_microbatches": 2, "n_chunks": 1},
    "optimizer": {"peak_lr": 3e-4, "end_lr_frac": 0.1, "warmup_steps": 20,
                  "total_steps": 1000, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0},
    "check_steps": 3,
}
TINY_A2A = dict(
    TINY_TRAIN, batch=8, mesh=[1, 4],
    zebra={"mode": "alltoall", "num_microbatches": 2, "n_chunks": 2})
TINY_SERVE = {
    "driver": "serve", "shape_seed": 0,
    "arrivals": {"kind": "poisson", "rate_per_s": 8.0},
    "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 64,
               "round_to": 8},
    "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "engine": {"slots": 4, "max_len": 80, "page_size": 8,
               "prefill_chunk": 16},
    "drain_s": 60, "check": {"tokens": 24, "requests": 3},
}
LIMITS = {"tiny-train": {"loss_gap": 0.02, "grad_norm_gap": 0.02,
                         "update_norm_gap": 0.02, "grad_diff": 0.2},
          "tiny-a2a": {"loss_gap": 0.02, "grad_norm_gap": 0.02,
                       "update_norm_gap": 0.02, "grad_diff": 0.2},
          "tiny-serve": {"served_logit_gap_mean": 0.02,
                         "logit_distance": 0.05}}


def write_json(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1) + "\n")


def add_cell(root, name, config, traffic, mix, limits, chips=1):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and any(
                w["name"] in m["workloads"] and
                _driver(root, w) == mix["driver"]
                for w in spec["workloads"][:-1]):
            m["workloads"].append(name)
    write_json(root / "BENCHMARK.json", spec)
    write_json(root / "bench" / "traffic" / "mixes" / f"{traffic}.json", mix)
    write_json(root / "bench" / "workloads" / f"{name}.json",
               {"limits": limits})


def _driver(root, w):
    p = root / "bench" / "traffic" / "mixes" / f"{w['traffic']}.json"
    return json.loads(p.read_text())["driver"] if p.exists() else None


@pytest.fixture
def checkout(tmp_path):
    """A checkout with the benchmark copied, the program linked, and the
    cells ``tiny-train``, ``tiny-a2a`` (four devices) and ``tiny-serve``
    added."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    os.symlink(REPO / "src", root / "src")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    write_json(root / "BENCHMARK.json", spec)
    write_json(root / "bench" / "configs" / "tiny.json", TINY)
    add_cell(root, "tiny-train", "tiny", "tiny-train", TINY_TRAIN,
             LIMITS["tiny-train"])
    add_cell(root, "tiny-a2a", "tiny", "tiny-a2a", TINY_A2A,
             LIMITS["tiny-a2a"], chips=4)
    add_cell(root, "tiny-serve", "tiny", "tiny-serve", TINY_SERVE,
             LIMITS["tiny-serve"])
    return root


def load_run(root):
    """The checkout's ``bench/run.py`` as a fresh module, with none of
    another checkout's benchmark modules left in ``sys.modules``."""
    for name in BENCH_MODULES:
        sys.modules.pop(name, None)
    while str(root / "bench") in sys.path:
        sys.path.remove(str(root / "bench"))
    sys.path.insert(0, str(root / "bench"))
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root, capsys, workload, seed=7, seconds=1.0, trace=0):
    """Run a cell in this process on the CPU; returns the result line."""
    run = load_run(root)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
