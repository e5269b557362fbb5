"""The trace reduction on hand-made events, and on a small trace
recorded on a TPU v5e (``data/probe.xplane.pb``)."""

from __future__ import annotations

import pathlib

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(name, s, e):
    return tr.Event(name, float(s), float(e))


def test_union_merges_and_clips():
    assert tr.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == \
        [(1, 4), (5, 8), (9, 10)]


def test_idle_share_and_gaps():
    t = tr.Trace(window=(0.0, 1e9),
                 devices={"/device:TPU:0": [ev("fusion", 0, 4e8),
                                            ev("dot", 3e8, 6e8)]},
                 host_spans=[ev("bench.load", 6e8, 9e8),
                             ev("bench.wait", 8.5e8, 1e9)])
    s = tr.summarize(t)
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.6)
    assert s.idle_share == pytest.approx(0.4)
    assert s.a2a_exposed_s is None
    assert dict(s.idle_gaps) == {"bench.load": pytest.approx(0.4)}
    assert s.device_ops[0] == ("fusion", pytest.approx(0.4))


def test_exposed_all_to_all_is_averaged_over_chips():
    ops0 = [ev("all-to-all.1", 0, 4e8), ev("fusion", 2e8, 5e8)]
    ops1 = [ev("all-to-all.1", 0, 1e8), ev("fusion", 1e8, 5e8)]
    t = tr.Trace(window=(0.0, 1e9),
                 devices={"/device:TPU:0": ops0, "/device:TPU:1": ops1},
                 host_spans=[])
    s = tr.summarize(t)
    assert s.a2a_exposed_s == pytest.approx((0.2 + 0.1) / 2)
    assert s.busy_s == pytest.approx(0.5)


def test_no_tpu_plane_reads_nothing():
    assert tr.summarize(tr.Trace((0.0, 1.0), {}, [])) is None


@pytest.mark.skipif(not (DATA / "probe.xplane.pb").exists(),
                    reason="no recorded trace")
def test_recorded_trace():
    s = tr.summarize(tr.load(str(DATA / "probe.xplane.pb")))
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    names = dict(s.idle_gaps)
    assert set(names) <= {"bench.load", "bench.dispatch", "bench.wait",
                          "(none)"}
    assert s.device_ops and all(t > 0 for _, t in s.device_ops)
    assert s.device_ops[0][0] == "fusion"


def test_op_name_is_the_instruction():
    assert tr.op_name("%all-to-all.3 = (bf16[2,8]) all-to-all(...)") == \
        "all-to-all.3"
