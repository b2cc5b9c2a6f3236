"""The trace reduction on a small recorded trace (two devices, a window
of 1000 ns)."""
import os

import pytest

from chipbench import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return reduce.Trace.from_json(f.read())


def test_window_is_the_window_span(trace):
    assert reduce.window(trace) == (0, 1000)


def test_busy_is_the_union_of_op_intervals(trace):
    # TPU:0: [100,400] + [500,600] + [800,900] = 500; TPU:1: 100 + 400 + 100 = 600
    assert reduce.device_busy(trace, "/device:TPU:0", 0, 1000) == [(100, 400), (500, 600), (800, 900)]
    assert reduce.busy_ns(trace, 0, 1000) == pytest.approx(550)
    assert reduce.idle_share(trace, 0, 1000) == pytest.approx(0.45)
    assert reduce.busy_ns(trace, 150, 350) == pytest.approx((200 + 100) / 2)


def test_collective_exposure(trace):
    # TPU:0: all-reduce [500,600] minus fusion [550,580] = 70; TPU:1: 400 alone
    assert reduce.exposed_collective_share(trace, 0, 1000) == pytest.approx((70 + 400) / 2 / 1000)
    compute_only = reduce.Trace({"/device:TPU:0": [(0, 10, "fusion.1")]}, [])
    assert reduce.exposed_collective_share(compute_only, 0, 100) is None


def test_exposed_collective_reader(trace):
    from chipbench import harness

    read = harness._load_reader(harness.BENCH_DIR, "exposed_collective.train")
    run = harness.Run(kind="train", chips=2, peak={}, sizes={}, mix={}, metrics={}, numbers={},
                      attempted=1, failed=0, memory_peak_bytes=0, window_s=1.0, trace=trace,
                      trace_window=(0, 1000))
    assert read(run) == pytest.approx(100 * (70 + 400) / 2 / 1000)
    run.trace = reduce.Trace({"/device:TPU:0": [(0, 10, "fusion.1")]}, [])
    assert read(run) is None


def test_idle_gaps_are_named_by_the_covering_span(trace):
    gaps = reduce.idle_gaps(trace, 0, 1000)
    # TPU:0 gaps: [0,100] batch(90)/none, [400,500] dispatch, [600,800] sync, [900,1000] wait
    assert gaps[0] == ["chipbench.sync", pytest.approx(200e-9)]
    assert sorted(g[0] for g in gaps[1:]) == ["chipbench.batch", "chipbench.dispatch", "chipbench.wait"]


def test_top_ops(trace):
    ops = dict(reduce.top_ops(trace, 0, 1000))
    assert ops["fusion.1"] == pytest.approx((200 + 100 + 100 + 100) / 2 / 1e9)
    assert list(ops)[:2] == ["fusion.1", "collective-permute.5"]


def test_interval_arithmetic():
    assert reduce.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert reduce.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
