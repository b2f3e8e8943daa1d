"""`state_merge_share.serve`: the share of a serving window's decode
steps whose dispatch wrote rows the host had changed over the device's
per-slot state.  It is an entry of BENCHMARK.json in both serving cells
and none of the training ones, and its reader gives the number from a
registry that holds the counter and None, without raising, from one
that does not (the program before the per-slot state stayed on the
device) or from a window without steps."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402

NAME = "state_merge_share.serve"
SERVE = ["gpt2-medium-chat-steady", "sdar-30b-a3b-chat-steady"]
TRAIN = ["bert-base-pretrain-1chip", "bert-base-pretrain-zero2-4chip"]


def registry_obs(merges=True):
    """``obs`` as a serving run hands it over: 40 steps in the window,
    10 of them with a merge (the counter's family absent where
    ``merges`` is false)."""
    reg = MetricsRegistry()
    lbl = ("engine",)
    steps = reg.counter("generation_decode_steps_total",
                        labelnames=lbl).labels("e")
    merged = (reg.counter("generation_state_merges_total",
                          labelnames=lbl).labels("e") if merges else None)
    steps.inc(100)
    if merged:
        merged.inc(70)
    before = reg.snapshot()
    steps.inc(40)
    if merged:
        merged.inc(10)
    return {"counters_before": before, "counters_after": reg.snapshot(),
            "trace": None}


def reader():
    bench = run.load_json(REPO, "BENCHMARK.json")
    return run.load_reader(REPO, bench, "per_layer", NAME)


def test_the_entry_is_the_last_and_in_the_serving_cells_only():
    bench = run.load_json(REPO, "BENCHMARK.json")
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "latency_ms_per_token", "workloads": SERVE}
    for cell in SERVE:
        assert entry in run.metrics_of(bench, "per_layer", cell)
    for cell in TRAIN:
        assert entry not in run.metrics_of(bench, "per_layer", cell)


def test_the_reader_reads_merges_over_steps_in_the_window():
    assert reader()(registry_obs()) == pytest.approx(25.0)


@pytest.mark.parametrize("what", ["no counter", "empty registry",
                                  "no steps"])
def test_the_reader_keeps_silent_where_there_is_nothing_to_read(what):
    if what == "no counter":            # the parent's program
        obs = registry_obs(merges=False)
    elif what == "empty registry":
        empty = MetricsRegistry().snapshot()
        obs = {"counters_before": empty, "counters_after": empty,
               "trace": None}
    else:
        obs = registry_obs()
        obs = dict(obs, counters_before=obs["counters_after"])
    assert reader()(obs) is None
