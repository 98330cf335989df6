"""The trace reduction (bench/trace.py) on a trace built by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402
from bench.trace import Event  # noqa: E402

MS = 1e6


def dev(i, name, start_ms, dur_ms):
    return Event(f"/device:TPU:{i}", "XLA Ops", name, start_ms * MS,
                 dur_ms * MS)


def host(name, start_ms, dur_ms):
    return Event("/host:CPU", "python", name, start_ms * MS, dur_ms * MS)


@pytest.fixture
def events():
    # window 0..100 ms.  Device 0: fusion 0-40, all-reduce 40-50 (exposed),
    # fusion 45-60 overlapping it, an idle gap 60-90 while the host feeds,
    # fusion 90-100.  Device 1: busy 0-50 only.  Ops outside the window and
    # a non-op line are ignored.
    return [
        host("bench.window", 0, 100), host("bench.step", 0, 5),
        host("bench.feed", 58, 35), host("bench.fetch", 93, 7),
        dev(0, "fusion.1", 0, 40), dev(0, "all-reduce.3", 40, 10),
        dev(0, "fusion.2", 45, 15), dev(0, "fusion.1", 90, 10),
        dev(0, "fusion.9", 120, 10),
        Event("/device:TPU:0", "XLA Modules", "jit_step", 0, 100 * MS),
        dev(1, "fusion.1", 0, 30), dev(1, "collective-permute-done.1", 30, 20),
    ]


def test_busy_idle_and_window(events):
    red = trace.reduce(events)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["idle_share"][0] == pytest.approx(0.3)
    assert red["idle_share"][1] == pytest.approx(0.5)
    assert red["busy_s"] == pytest.approx((0.07 + 0.05) / 2)


def test_collective_exposed_only_where_no_compute_runs(events):
    red = trace.reduce(events)
    # device 0: the all-reduce runs 40-50, fusion.2 covers 45-50
    assert red["collective_exposed_share"][0] == pytest.approx(0.05)
    assert red["collective_exposed_share"][1] == pytest.approx(0.2)


def test_breakdown_names_ops_and_gaps(events):
    red = trace.reduce(events)
    ops = dict(red["device_ops"])
    # summed over the window, averaged over the two devices
    assert ops["fusion.1"] == pytest.approx((0.05 + 0.03) / 2)
    assert "fusion.9" not in ops
    assert len(red["device_ops"]) <= trace.TOP
    assert red["idle_gaps"][0] == ["bench.feed", pytest.approx(0.03)]


def test_loops_count_by_the_ops_of_their_body():
    # a while loop 0-60 on the ops line encloses its body's ops; the
    # collective inside it is exposed where no other body op runs
    evs = [host("bench.window", 0, 100),
           dev(0, "%while.9 = (s32[], bf16[8,2048]{1,0:T(8,128)}) while(x)",
               0, 60),
           dev(0, "%fusion.2 = bf16[8,2048]{1,0:T(8,128)} fusion(a)", 0, 30),
           dev(0, "%all-reduce.1 = f32[4]{0} all-reduce(b)", 30, 20),
           dev(0, "%fusion.3 = f32[2]{0} fusion(c)", 55, 5)]
    red = trace.reduce(evs)
    assert red["idle_share"][0] == pytest.approx(0.4)
    assert red["collective_exposed_share"][0] == pytest.approx(0.2)
    ops = dict(red["device_ops"])
    assert ops == {"fusion.2 bf16[8,2048]": pytest.approx(0.03),
                   "all-reduce.1 f32[4]": pytest.approx(0.02),
                   "fusion.3 f32[2]": pytest.approx(0.005)}


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([host("bench.window", 0, 10)])


def test_interval_helpers():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.overlap([(0, 2), (5, 9)], [(1, 6)]) == 2
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
