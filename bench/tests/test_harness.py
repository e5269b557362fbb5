"""The harness end to end on the CPU, at a tiny size: a cell that is only
new files and entries runs, and a new metric file is read."""

from __future__ import annotations

import json

from conftest import run_cell, write_json


def test_new_train_cell_runs_from_added_files(checkout, capsys):
    res = run_cell(checkout, capsys, "tiny-train")
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_new_serve_cell_runs_from_added_files(checkout, capsys):
    res = run_cell(checkout, capsys, "tiny-serve", seconds=2.0)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert res["failed"] == 0


def test_new_metric_file_is_read(checkout, capsys):
    (checkout / "bench" / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['outcome']['counters']['steps'])\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train"]})
    write_json(checkout / "BENCHMARK.json", spec)
    res = run_cell(checkout, capsys, "tiny-train", trace=1)
    assert res["metrics"]["steps_in_window"]["value"] == res["attempted"]
    # No peak is known for the CPU, so the reader finds nothing to read.
    assert "train_mfu" not in res["metrics"]
