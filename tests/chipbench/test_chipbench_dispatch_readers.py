"""The five per-layer metrics that read the serving loop's dispatch,
its lock and the interpreter's collections from the program's own
registry: each is an entry of BENCHMARK.json in both serving cells and
none of the training ones, and its reader gives the number from a
registry that holds the series and None, without raising, from one that
does not (the program before these series existed)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402

SERVE = ["gpt2-medium-chat-steady", "sdar-30b-a3b-chat-steady"]
TRAIN = ["bert-base-pretrain-1chip", "bert-base-pretrain-zero2-4chip"]
NEW = {     # name: unit, better, layer, moves
    "decode_dispatch_ms_p50.serve": ("ms", "lower", "scheduler",
                                     "itl_ms_p95"),
    "dispatch_call_ms_p50.serve": ("ms", "lower", "step functions",
                                   "itl_ms_p95"),
    "lock_wait_ms_max.serve": ("ms", "lower", "scheduler",
                               "latency_ms_per_token"),
    "gc_pause_ms_max.serve": ("ms", "lower", "scheduler",
                              "latency_ms_per_token"),
    "decode_overlap_share.serve": ("%", "higher", "scheduler",
                                   "itl_ms_p95"),
}
DISPATCH_MS = [2.0, 1.0, 3.0, 2.5, 1.5]       # p50 2.0
CALL_MS = [1.2, 0.8, 1.0]                     # p50 1.0
LOCK_MS = [0.01, 37.0, 0.02]                  # max 37.0
GC_MS = [0.3, 121.5, 2.0]                     # max 121.5
WANT = {"decode_dispatch_ms_p50.serve": 2.0,
        "dispatch_call_ms_p50.serve": 1.0,
        "lock_wait_ms_max.serve": 37.0,
        "gc_pause_ms_max.serve": 121.5,
        # 30 more steps in the window, 27 of them overlapped
        "decode_overlap_share.serve": 90.0}


def registry_obs():
    """``obs`` as a serving run hands it over: the registry before the
    window (histograms then emptied) and after it."""
    reg = MetricsRegistry()
    lbl = ("engine",)
    steps = reg.counter("generation_decode_steps_total",
                        labelnames=lbl).labels("e")
    over = reg.counter("generation_decode_overlapped_total",
                       labelnames=lbl).labels("e")
    steps.inc(100)
    over.inc(60)
    before = reg.snapshot()
    steps.inc(30)
    over.inc(27)
    for family, values in (("generation_dispatch_ms", DISPATCH_MS),
                           ("generation_dispatch_call_ms", CALL_MS),
                           ("generation_lock_wait_ms", LOCK_MS)):
        h = reg.histogram(family, labelnames=lbl).labels("e")
        for v in values:
            h.observe(v)
    gc_pause = reg.histogram("generation_gc_pause_ms")
    for v in GC_MS:
        gc_pause.observe(v)
    return {"counters_before": before, "counters_after": reg.snapshot(),
            "trace": None}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_entry_is_in_the_serving_cells_only(name):
    bench = run.load_json(REPO, "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    unit, better, layer, moves = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": layer,
                     "moves": moves, "workloads": SERVE}
    for cell in SERVE:
        assert entry in run.metrics_of(bench, "per_layer", cell)
    for cell in TRAIN:
        assert entry not in run.metrics_of(bench, "per_layer", cell)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_reader_reads_the_series_or_keeps_silent(name):
    bench = run.load_json(REPO, "BENCHMARK.json")
    read = run.load_reader(REPO, bench, "per_layer", name)
    assert read(registry_obs()) == pytest.approx(WANT[name])
    # a program without the series (the parent): nothing, and no raise
    empty = MetricsRegistry().snapshot()
    assert read({"counters_before": empty, "counters_after": empty,
                 "trace": None}) is None


def test_the_overlap_share_is_silent_on_a_window_without_steps():
    bench = run.load_json(REPO, "BENCHMARK.json")
    read = run.load_reader(REPO, bench, "per_layer",
                           "decode_overlap_share.serve")
    obs = registry_obs()
    assert read(dict(obs, counters_before=obs["counters_after"])) is None
