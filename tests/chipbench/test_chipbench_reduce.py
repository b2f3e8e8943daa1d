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


# An XSpace serialized by the protobuf library: a device plane with two
# operations 4 us apart, and a host plane whose one line holds the
# scheduler's spans (`generation.step` 0.5-4.5 us around `.decode_fetch`
# 1-2.5 and `.decode_dispatch` 2.5-3, `.idle_wait` 4.5-4.9), an
# ``http.generate`` and a foreign event that both cover the whole gap, and
# the three other prefixes' spans before the gap.
HOST_XSPACE = bytes.fromhex(
    "0a6f0801120d2f6465766963653a5450553a301a2208011207584c41204f7073"
    "22080801100018c0843d220b080110c096b10218c0843d223808011234080112"
    "3025667573696f6e2e31203d206633325b385d20667573696f6e286633325b38"
    "5d202578292c206b696e643d6b4c6f6f700af802080212092f686f73743a4350"
    "551a74080712097363686564756c6572220b080110a0c21e188092f401220a08"
    "0210c0843d18e0c65b220b080310a0cb980118a0c21e220b080410a0d4920218"
    "80b51822090805100018809bee0222070806100018904e2208080710904e1890"
    "4e2209080810a09c0118904e22090809100018809bee02221808091214080912"
    "10536f6d654f746865723a3a5468696e672215080812110808120d696f2e6e65"
    "78745f6261746368221b0807121708071213747261696e2e737465705f646973"
    "706174636822120806120e0806120a62656e63682e7374657022150805121108"
    "05120d687474702e67656e6572617465221c080412180804121467656e657261"
    "74696f6e2e69646c655f7761697422220803121e0803121a67656e6572617469"
    "6f6e2e6465636f64655f6469737061746368221f0802121b0802121767656e65"
    "726174696f6e2e6465636f64655f66657463682217080112130801120f67656e"
    "65726174696f6e2e73746570")


def test_load_admits_the_program_s_spans_and_the_gaps_take_their_names(
        tmp_path):
    session = tmp_path / "plugins" / "profile" / "2026_10_04"
    session.mkdir(parents=True)
    (session / "host.xplane.pb").write_bytes(HOST_XSPACE)
    trace = rx.load(str(tmp_path))
    assert {name for name, _, _ in trace["host"]} == {
        "generation.step", "generation.decode_fetch",
        "generation.decode_dispatch", "generation.idle_wait",
        "bench.step", "train.step_dispatch", "io.next_batch"}
    # a handler's span lives as long as its request: it names no gap
    assert not "http.generate".startswith(rx.HOST_PREFIXES)
    red = rx.reduce(trace, default="between-decode-steps")
    named = dict(red["idle_gaps"])
    assert set(named) == {
        "generation.decode_fetch", "generation.decode_dispatch",
        "generation.step", "generation.idle_wait", "between-decode-steps"}
    # the default label keeps only what no span of the loop thread covers
    assert named["between-decode-steps"] < named["generation.decode_fetch"]
    assert sum(named.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
