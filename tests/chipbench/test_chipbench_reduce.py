"""The reduction from a trace to busy time, operations and idle gaps, on
hand-made intervals (no profiler, no chip)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import reduce_xplane as rx  # noqa: E402


def test_union_merges_overlaps_and_drops_empty_intervals():
    assert rx.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 6.5)]) == [
        (0, 3), (5, 7)]
    assert rx.total(rx.union([(0, 2), (1, 3), (5, 7)])) == 5


def test_gaps_are_the_complement_inside_the_window():
    busy = [(2, 4), (6, 7)]
    assert rx.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert rx.gaps(busy, 3, 6.5) == [(4, 6)]
    assert rx.gaps([], 1, 2) == [(1, 2)]


def test_self_time_goes_to_the_innermost_event():
    events = [("while", 0, 10), ("fusion.1", 1, 4), ("fusion.2", 4, 6),
              ("custom-call", 12, 15), ("fusion.1", 16, 17)]
    assert rx.self_times(events) == {
        "while": 5, "fusion.1": 4, "fusion.2": 2, "custom-call": 3}


def test_gap_attribution_prefers_the_shortest_covering_span():
    spans = [("bench.step", 0, 10), ("bench.loss_fetch", 4, 6)]
    got = rx.attribute([(3, 7), (9, 12)], spans, default="nobody")
    assert got == {"bench.step": 3, "bench.loss_fetch": 2, "nobody": 2}


def test_reduce_gives_busy_window_idle_and_breakdown():
    ns = 1e9
    trace = {
        "devices": {
            0: [("fusion", 0 * ns, 2 * ns), ("all-reduce.1", 2 * ns, 3 * ns),
                ("fusion", 4 * ns, 8 * ns)],
            1: [("fusion", 0 * ns, 8 * ns)],
        },
        "host": [("bench.feed", 3 * ns, 4.5 * ns)],
    }
    red = rx.reduce(trace)
    assert red["devices"] == 2
    assert red["window_s"] == 8
    assert red["busy_s"] == pytest.approx((7 + 8) / 2)
    assert red["device_ops"][0] == ["fusion", pytest.approx((6 + 8) / 2)]
    assert red["idle_gaps"] == [["bench.feed", pytest.approx(0.5)]]
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert rx.reduce({"devices": {}, "host": []}) is None


def test_idle_share_reader_uses_the_reduction():
    from chipbench.common import idle_share

    assert idle_share({"trace": {"busy_s": 7.5, "window_s": 10.0}}) == 25.0
    assert idle_share({"trace": None}) is None
