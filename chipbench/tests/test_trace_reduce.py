"""The trace reduction on a small recorded trace, worked by hand."""

import json
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        raw = json.load(f)
    return {"devices": {k: [tuple(e) for e in v] for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_interval_arithmetic():
    assert tr.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.total([(0, 1), (2, 4)]) == 3


def test_window_is_the_harness_span(trace):
    assert tr.window_of(trace) == (0.0, 6.0)
    trace["host"] = []
    assert tr.window_of(trace) == (0.0, 6.0)  # first op start to last op end


def test_busy_union_and_idle_share(trace):
    # dev0 busy [0,2) + [3,6) = 5 s, dev1 [0,6) = 6 s
    assert tr.busy_seconds(trace, 0.0, 6.0) == pytest.approx(5.5)
    s = tr.summarize(trace)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(1 - 5.5 / 6)
    # clipped to [1, 5): dev0 [1,2)+[3,5) = 3, dev1 4
    assert tr.busy_seconds(trace, 1.0, 5.0) == pytest.approx(3.5)


def test_kernel_time_by_name(trace):
    secs, calls = tr.kernel_seconds(trace, r"_fwd_kernel", 0.0, 6.0)
    assert (secs, calls) == (pytest.approx(0.5), 0.5)  # 1 s on one of two devices
    assert tr.kernel_seconds(trace, r"no_such", 0.0, 6.0) == (0.0, 0.0)


def test_exposed_collective_time(trace):
    # dev0: collectives [3,4) + [4.5,5.5) = 2 s, exposed only [3,4) = 1 s
    # dev1: collective [4,6) = 2 s, all exposed
    both, exposed = tr.collective_seconds(trace, 0.0, 6.0)
    assert both == pytest.approx(2.0)
    assert exposed == pytest.approx(1.5)


def test_breakdown(trace):
    s = tr.summarize(trace)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx((1 + 2 + 4) / 2)
    assert ops["all-gather-start"] == pytest.approx(0.5)
    # dev0 idles [2,3): 0.2 s under train_batch, 0.8 s under fence -> fence
    assert s["breakdown"]["idle_gaps"] == [["chipbench/fence", pytest.approx(1.0)]]
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_span_self_time(trace):
    # train_batch [0,2.2) holds place [0.5,1.0): self time 1.7 s
    assert tr.span_self_seconds(trace, "chipbench/train_batch", 0.0, 6.0) == pytest.approx(1.7)
