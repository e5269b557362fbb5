"""The control comes out not correct: the reference computed in float8
in the program's place fails at least one of the cell's limits, while the
program passes them; and the planted faults read past the limits. At a
tiny size on the CPU; the chip readings at the cells' own sizes are in
PERF.md."""

from __future__ import annotations

import jax
import pytest

from conftest import LIMITS, load_run


def _cell(root, name):
    load_run(root)  # puts this checkout's benchmark first on the path
    import control
    import harness
    return control, harness.load_cell(root, name)


def _fails(readings: dict, limits: dict) -> bool:
    return any(readings[k] > v for k, v in limits.items())


@pytest.mark.parametrize("name", ["tiny-train", "tiny-a2a"])
def test_train_control_and_half_batch_fail(checkout, name):
    control, cell = _cell(checkout, name)
    for seed in (1, 2, 3):
        rec = control.train_readings(cell, seed,
                                     devices=jax.devices()[:cell.chips])
        assert not _fails(rec["program"], LIMITS[name]), rec
        assert _fails(rec["control"], LIMITS[name]), rec
        assert _fails(rec["half_batch"], LIMITS[name]), rec
        assert _fails(rec["dropped_shard"], LIMITS[name]), rec


def test_serve_control_and_altered_token_fail(checkout):
    control, cell = _cell(checkout, "tiny-serve")
    lim = LIMITS["tiny-serve"]
    for seed in (1, 2, 3):
        rec = control.serve_readings(cell, seed, 2.0, jax.devices()[:1])
        assert rec["program"]["requests"] >= 3, rec
        assert not _fails(rec["program"], lim), rec
        assert _fails(rec["control"], lim), rec
        assert _fails(rec["altered_token"], lim), rec
