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
    assert s.a2a_s is None and s.a2a_exposed_s is None
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


def test_all_to_all_time_and_exposed_over_two_chips():
    # Chip 0: all-to-all 0-3 under a fusion 1-2, a bare one 6-7, and one
    # that starts before the window (-1 to 1) and so counts 0-1 only.
    # Chip 1: two overlapping all-to-alls 2-5 and 4-8 (merged 2-8), a
    # fusion 3-6 over part of them. Times in 1e8 ns.
    ops0 = [ev("all-to-all.1", 0, 3e8), ev("fusion", 1e8, 2e8),
            ev("all-to-all.2", 6e8, 7e8), ev("all-to-all.0", -1e8, 1e8)]
    ops1 = [ev("all-to-all-start.3", 2e8, 5e8), ev("all-to-all.4", 4e8, 8e8),
            ev("fusion", 3e8, 6e8)]
    t = tr.Trace(window=(0.0, 1e9),
                 devices={"/device:TPU:0": ops0, "/device:TPU:1": ops1},
                 host_spans=[])
    s = tr.summarize(t)
    # a2a: chip 0 covers 0-3 and 6-7 (0.4 s), chip 1 covers 2-8 (0.6 s).
    assert s.a2a_s == pytest.approx((0.4 + 0.6) / 2)
    # exposed: chip 0 loses 1-2 to the fusion (0.3 s), chip 1 loses 3-6
    # (0.3 s).
    assert s.a2a_exposed_s == pytest.approx((0.3 + 0.3) / 2)
    assert s.a2a_exposed_s <= s.a2a_s


def test_all_to_all_in_a_loop_body_and_asynchronous():
    # A while 0-10 holds its body's ops: a fusion 1-2, a bare all-to-all
    # 3-4, and an asynchronous one started at 5 and done 7-8 with a fusion
    # 6-7 between. The while's own event hides nothing. Times in 1e8 ns.
    ops = [ev("while.1", 0, 10e8), ev("fusion.1", 1e8, 2e8),
           ev("all-to-all.2", 3e8, 4e8), ev("all-to-all-start.3", 5e8, 5.5e8),
           ev("fusion.2", 6e8, 7e8), ev("all-to-all-done.4", 7e8, 8e8)]
    t = tr.Trace(window=(0.0, 1e9), devices={"/device:TPU:0": ops},
                 host_spans=[])
    s = tr.summarize(t)
    # a2a: 3-4 and 5-8 (0.4 s); exposed: all but the fusion's 6-7 (0.3 s).
    assert s.a2a_s == pytest.approx(0.4)
    assert s.a2a_exposed_s == pytest.approx(0.3)
    assert s.busy_s == pytest.approx(1.0)


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
