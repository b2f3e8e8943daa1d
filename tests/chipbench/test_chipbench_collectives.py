"""`chipbench.collectives` on hand-made intervals and event names as a
v5e trace of the ZeRO-2 step has them (no profiler, no chip): which events
are collectives, how an asynchronous pair is matched, and that only the
part of a collective nothing else covers counts as exposed."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import collectives as co  # noqa: E402
from chipbench import program_trace as pt  # noqa: E402

AG_START = ('%all-gather-start.7 = (f32[576,768]{1,0}, f32[2304,768]{1,0}) '
            'all-gather-start(f32[576,768]{1,0} %param.3), channel_id=9, '
            'replica_groups=[1,4]<=[4], dimensions={0}')
AG_DONE = ('%all-gather-done.7 = f32[2304,768]{1,0} all-gather-done('
           '(f32[576,768]{1,0}, f32[2304,768]{1,0}) %all-gather-start.7)')
AG_DONE_OTHER = ('%all-gather-done.8 = f32[2304,768]{1,0} all-gather-done('
                 '(f32[576,768]{1,0}, f32[2304,768]{1,0}) '
                 '%all-gather-start.8)')
RS = ('%reduce-scatter.2 = f32[576,768]{1,0} reduce-scatter(f32[2304,768]'
      '{1,0} %fusion.9), channel_id=3, replica_groups=[1,4]<=[4], '
      'dimensions={0}, to_apply=%add')
AR = ('%all-reduce.5 = f32[]{:T(128)} all-reduce(f32[] %reduce.1), '
      'channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add')
# XLA:TPU's asynchronous collective fusion, as the real four-chip trace of
# the ZeRO-2 step names it (my chip run, PR 34): the done names the start
# only through get-tuple-elements
ASYNC_START = ('%async-collective-start = (f32[1,1,590592]{2,1,0:T(1,128)}, '
               'f32[4,1,590592]{2,1,0:T(1,128)}, s32[2]{0:S(4)}, u32[]{:S(2)}) '
               'fusion(f32[1,1,590592]{2,1,0:T(1,128)} %reshape.5353), '
               'kind=kCustom')
ASYNC_DONE = ('%async-collective-done = f32[4,1,590592]{2,1,0:T(1,128)} '
              'fusion(f32[1,1,590592]{2,1,0:T(1,128)} %get-tuple-element.7766, '
              'f32[4,1,590592]{2,1,0:T(1,128)} %get-tuple-element.7767)')
AG_SYNC = ('%all-gather.152 = f32[4,1,690048]{2,1,0:T(1,128)S(1)} all-gather('
           'f32[1,1,690048]{2,1,0:T(1,128)S(1)} %copy-done.159), channel_id=44, '
           'replica_groups=[1,4]<=[4], dimensions={0}')
# asynchronous, but no collective: a slice and a copy the chip overlaps
SLICE_DONE = ('%slice-done.449 = f32[886784]{0:T(1024)S(1)} async-done((('
              'f32[3545860]{0:T(1024)}), f32[886784]{0:T(1024)S(1)}, '
              's32[]{:S(2)}) %slice-start.449)')
FUSION = '%fusion.406 = bf16[48,512,3072]{2,1,0} fusion(%x), kind=kOutput'
LOOP = '%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b'
# a fusion that only has a collective's name in its own: not a collective
LOOKALIKE = ('%all-reduce-ish_fusion.1 = f32[8]{0} fusion(f32[8] %x), '
             'kind=kLoop')


@pytest.mark.parametrize("event,want", [
    (AG_START, ("all-gather", "-start")), (AG_DONE, ("all-gather", "-done")),
    (RS, ("reduce-scatter", "")), (AR, ("all-reduce", "")),
    (AG_SYNC, ("all-gather", "")),
    (ASYNC_START, ("async-collective", "-start")),
    (ASYNC_DONE, ("async-collective", "-done")),
    (SLICE_DONE, None), (FUSION, None), (LOOP, None), (LOOKALIKE, None),
    ("not an instruction", None),
])
def test_a_collective_is_known_by_its_opcode(event, want):
    assert co.phase(event) == want


def test_an_asynchronous_pair_is_in_flight_from_its_start_to_its_done():
    events = [(AG_START, 0, 2), (FUSION, 2, 30), (AG_DONE, 30, 31),
              (RS, 40, 50), (FUSION, 50, 60)]
    assert sorted(co.in_flight(events)) == [(0, 31), (40, 50)]
    # a done whose start lies before the traced stretch, and a start whose
    # done lies after it, count for their own events
    assert sorted(co.in_flight([(AG_DONE_OTHER, 5, 9), (AG_START, 20, 22)])
                  ) == [(5, 9), (20, 22)]
    assert co.in_flight([(FUSION, 0, 5)]) == []
    # the collective fusion's done closes the open start of its kind, and
    # an all-reduce in between is a collective of its own
    assert sorted(co.in_flight([
        (ASYNC_START, 0, 1), (FUSION, 1, 10), (AR, 10, 12), (FUSION, 12, 30),
        (ASYNC_DONE, 30, 31), (AG_SYNC, 31, 33)])) == [
            (0, 31), (10, 12), (31, 33)]


def test_one_overlapped_and_one_exposed_collective():
    events = [
        # hidden but for its two own events: arithmetic runs in between
        (AG_START, 0, 2), (FUSION, 2, 30), (AG_DONE, 30, 31),
        # exposed whole: nothing else runs while it lasts
        (RS, 40, 50),
        (FUSION, 50, 60),
        # a loop's own event covers its body and hides nothing
        (LOOP, 70, 90), (AR, 72, 76), (FUSION, 76, 90),
    ]
    got = co.seconds(events)
    assert got["in_flight"] == 31 + 10 + 4
    assert got["exposed"] == (2 + 1) + 10 + 4
    assert co.seconds([(FUSION, 0, 5), (LOOKALIKE, 5, 9)]) == {}
    # as the ZeRO-2 step has them: a hidden all-gather with a synchronous
    # all-reduce inside its flight, which nothing hides
    assert co.seconds([
        (ASYNC_START, 0, 1), (FUSION, 1, 10), (AR, 10, 12), (FUSION, 12, 30),
        (ASYNC_DONE, 30, 31), (AG_SYNC, 31, 33), (SLICE_DONE, 33, 34)]) == {
            "in_flight": 33, "exposed": 1 + 2 + 1 + 2}


def test_summarize_gives_the_chips_mean_and_nothing_on_one_chip():
    ms = 1e6
    trace = {
        "spans": [], "modules": {}, "paths": {}, "roots": {},
        "ops": {
            0: [(FUSION, 0, 90 * ms), (RS, 90 * ms, 100 * ms)],
            1: [(AG_START, 0, 1 * ms), (FUSION, 1 * ms, 99 * ms),
                (AG_DONE, 99 * ms, 100 * ms)],
        }}
    red = pt.summarize(trace)
    assert red["devices"] == 2 and red["window_s"] == pytest.approx(0.1)
    assert red["collective_s"] == {
        "in_flight": pytest.approx((0.010 + 0.100) / 2),
        "exposed": pytest.approx((0.010 + 0.002) / 2)}
    trace["ops"] = {0: [(FUSION, 0, 90 * ms)]}
    assert pt.summarize(trace)["collective_s"] == {}
